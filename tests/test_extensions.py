"""Coverage extensions: Scheme2Blocked/Scheme2 equivalence, grouped-MoE
invariance, adaptive decode-budget behaviour, sharding variants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:  # dev-only dep: degrade to per-test skips when missing
    from tests._hypothesis_compat import given, settings, st
except ImportError:
    from _hypothesis_compat import given, settings, st

from repro.core import (
    BernoulliStragglers,
    Scheme2,
    Scheme2Blocked,
    make_regular_ldpc,
    peel_decode_adaptive,
    second_moment,
)
from repro.data import make_linear_problem
from repro.models import moe as MOE


def test_scheme2_blocked_equals_scheme2_when_k_equals_K():
    """nb = 1 block: the blocked scheme must reduce exactly to Scheme 2."""
    prob = make_linear_problem(m=256, k=40, seed=0)
    mom = second_moment(prob.X, prob.y)
    code = make_regular_ldpc(40, l=3, r=6, seed=0)
    s2 = Scheme2.build(code, mom, lr=prob.lr, decode_iters=6)
    s2b = Scheme2Blocked.build(code, mom, lr=prob.lr, decode_iters=6)
    theta = jax.random.normal(jax.random.PRNGKey(0), (40,))
    mask = jnp.zeros(code.N, bool).at[jnp.array([3, 17])].set(True)
    g1, u1 = s2.gradient(theta, mask)
    g2, u2 = s2b.gradient(theta, mask)
    np.testing.assert_allclose(g1, g2, rtol=1e-4, atol=1e-4)
    assert int(u1) == int(u2)


def test_scheme2_blocked_block_order():
    """Blocked flat gradient must align coordinates with M's row partition."""
    prob = make_linear_problem(m=256, k=60, seed=1)
    mom = second_moment(prob.X, prob.y)
    code = make_regular_ldpc(20, l=3, r=6, seed=1)  # 3 blocks
    s2b = Scheme2Blocked.build(code, mom, lr=prob.lr, decode_iters=40)
    theta = jax.random.normal(jax.random.PRNGKey(1), (60,))
    g, u = s2b.gradient(theta, jnp.zeros(code.N, bool))
    assert int(u) == 0
    np.testing.assert_allclose(g, mom.M @ theta - mom.b, rtol=2e-3, atol=2e-3)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 100), groups=st.sampled_from([1, 2, 4]))
def test_moe_grouped_matches_ungrouped_high_capacity(seed, groups):
    """With capacity high enough that nothing drops, grouped routing is
    token-order invariant and must equal the global routing exactly."""
    key = jax.random.PRNGKey(seed)
    p = MOE.init_moe(key, 16, 32, 4, dtype=jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 4, 16))
    y1, _, _ = MOE.moe_forward(p, x, n_experts=4, top_k=2, capacity_factor=16.0)
    y2, _, _ = MOE.moe_forward(p, x, n_experts=4, top_k=2,
                               capacity_factor=16.0, groups=groups)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=2e-4, atol=2e-4)


def test_moe_capacity_dropping_monotone():
    """Lower capacity factor -> more dropped tokens -> output moves toward
    the shared/zero path; outputs must stay finite either way."""
    key = jax.random.PRNGKey(0)
    p = MOE.init_moe(key, 16, 32, 4, dtype=jnp.float32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 32, 16))
    y_lo, _, _ = MOE.moe_forward(p, x, n_experts=4, top_k=2,
                                 capacity_factor=0.25)
    y_hi, _, _ = MOE.moe_forward(p, x, n_experts=4, top_k=2,
                                 capacity_factor=8.0)
    assert np.isfinite(np.asarray(y_lo)).all()
    # dropped tokens contribute 0 -> lower-capacity output has smaller norm
    assert float(jnp.linalg.norm(y_lo)) <= float(jnp.linalg.norm(y_hi)) + 1e-3


def test_adaptive_decode_rounds_track_stragglers():
    """The paper's 'decoding effort adapts to realized stragglers':
    rounds_used must be (weakly) increasing in the erasure count."""
    code = make_regular_ldpc(128, l=3, r=6, seed=0)
    rng = np.random.default_rng(0)
    cw = jnp.asarray(code.encode(rng.standard_normal(128)), jnp.float32)
    rounds = []
    for s in (1, 10, 40):
        erased = np.zeros(code.N, bool)
        erased[rng.choice(code.N, s, replace=False)] = True
        rx = jnp.where(jnp.asarray(erased), 0.0, cw)
        res = peel_decode_adaptive(code, rx, jnp.asarray(erased))
        rounds.append(int(res.rounds_used))
    assert rounds[0] <= rounds[1] <= rounds[2] + 1


def test_seq_shard_kv_spec_generation():
    """H1 knob: KV-head-indivisible caches get sequence-sharded specs."""
    from jax.sharding import PartitionSpec as P
    from repro.configs import get_config
    from repro.launch.mesh import make_abstract_mesh
    from repro.models import Model
    from repro.sharding import cache_sharding

    mesh = make_abstract_mesh((16, 16), ("data", "model"))
    cfg = get_config("qwen3-1.7b")  # kv=8 does not divide 16
    model = Model(cfg)
    cache = jax.eval_shape(lambda: model.init_cache(128, 32768))
    base = cache_sharding(cfg, mesh, cache)
    opt = cache_sharding(cfg, mesh, cache, seq_shard_kv=True)
    k_base = base["blocks"]["sub0"]["k"].spec
    k_opt = opt["blocks"]["sub0"]["k"].spec
    assert k_base == P(None, "data", None, None, None)   # replicated over model
    assert k_opt == P(None, "data", "model", None, None)  # seq dim sharded


def test_reduced_configs_contract():
    """Assignment contract: every reduced config is <=2 layers, d_model<=512,
    <=4 experts."""
    from repro.configs import get_config, list_configs
    for name in list_configs():
        cfg = get_config(name)
        r = cfg.reduced()
        assert r.n_layers <= 2 and r.d_model <= 512
        if r.moe:
            assert r.moe.n_experts <= 4
        # same family/technique knobs preserved
        assert r.family == cfg.family
        assert (r.moe is None) == (cfg.moe is None)
        assert (r.mla is None) == (cfg.mla is None)
        assert (r.rwkv is None) == (cfg.rwkv is None)
