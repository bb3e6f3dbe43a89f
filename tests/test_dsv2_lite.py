"""DeepSeek-V2-Lite through the system's training path, against the plain
reference (``repro.models.dsv2_reference``), at small widths on the CPU:
the forward, loss and gradient; one chip's expert share; gates without
renormalisation; and the per-bucket coded training step."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.grad_agg import CodedAggregator
from repro.models import Model
from repro.models import dsv2_reference as R
from repro.models import moe as MOE
from repro.models.moe import capacity_for
from repro.optim import adamw_init
from repro.train.coded_step import CodedTrainStep

S = 32
K = 4


def _small(held=4, offset=2, capacity_factor=0.8):
    """Reduced DeepSeek-V2-Lite: one dense and two MoE layers, 8 routed
    experts (top 3) of which ``held`` are this chip's, tight capacity so
    that tokens drop."""
    cfg = get_config("deepseek-v2-lite").reduced()
    return dataclasses.replace(
        cfg, n_layers=3, dtype="float32",
        moe=dataclasses.replace(cfg.moe, n_experts=8, top_k=3, held=held,
                                held_offset=offset,
                                capacity_factor=capacity_factor))


def _ref_cfg(cfg, model, tokens=S):
    return dict(n_heads=cfg.n_heads, qk_nope=cfg.mla.qk_nope,
                qk_rope=cfg.mla.qk_rope, v_head=cfg.mla.v_head,
                rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
                n_experts=cfg.moe.n_experts, top_k=cfg.moe.top_k,
                capacity=capacity_for(tokens, cfg.moe.n_experts,
                                      cfg.moe.top_k, cfg.moe.capacity_factor),
                expert_offset=cfg.moe.held_offset,
                norm_topk_prob=cfg.moe.norm_topk_prob,
                aux_coef=model.moe_aux_coef)


@pytest.fixture(scope="module")
def small():
    cfg = _small()
    model = Model(cfg, remat=False, attn_chunk=8, attn_remat=True)
    params = model.init(jax.random.PRNGKey(1))
    ids = jax.random.randint(jax.random.PRNGKey(2), (K, S + 1), 0, cfg.vocab)
    batch = {"tokens": ids[:, :-1], "labels": ids[:, 1:]}
    return cfg, model, params, batch


def _rel(a, b):
    num = sum(float(jnp.sum((x - y) ** 2)) for x, y in
              zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    den = sum(float(jnp.sum(y ** 2)) for y in jax.tree.leaves(b))
    return np.sqrt(num / den)


def test_published_widths_registered():
    cfg = get_config("deepseek-v2-lite")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.d_ff, cfg.vocab) == (
        27, 2048, 16, 10944, 102400)
    assert (cfg.mla.kv_lora, cfg.mla.q_lora, cfg.mla.qk_nope, cfg.mla.qk_rope,
            cfg.mla.v_head) == (512, 0, 128, 64, 128)
    m = cfg.moe
    assert (m.n_experts, m.top_k, m.d_ff_expert, m.n_shared, m.first_dense,
            m.norm_topk_prob, m.held) == (64, 6, 1408, 2, 1, False, 0)
    assert cfg.rope_theta == 1e4
    assert get_config("deepseek-v2-236b").moe.norm_topk_prob is False
    assert get_config("kimi-k2-1t-a32b").moe.norm_topk_prob is True


def test_forward_loss_and_gradient_match_the_reference(small):
    cfg, model, params, batch = small
    one = jax.tree.map(lambda a: a[:1], batch)
    loss, grads = jax.jit(jax.value_and_grad(model.loss_fn))(params, one)
    want_loss, want = R.shard_grad(params, one["tokens"][0],
                                   one["labels"][0], _ref_cfg(cfg, model))
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    assert _rel(grads, want) < 1e-5
    # the expert share's parameters are the held experts only
    assert R.layers_of(params)[1]["ffn"]["experts"]["up"]["w"].shape[0] == 4


@pytest.mark.parametrize("norm", [True, False])
def test_expert_shares_add_up_to_the_whole_layer(norm):
    """Eight shares of one expert each: their outputs, with the shared
    expert counted once, are the uncut layer's output."""
    d, E, T = 32, 8, 48
    kw = dict(n_experts=E, top_k=3, capacity_factor=0.7,
              norm_topk_prob=norm)
    fwd = jax.jit(MOE.moe_forward, static_argnames=(*kw, "expert_offset"))
    whole = MOE.init_moe(jax.random.PRNGKey(0), d, 16, E, n_shared=1)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T // 2, d))
    y, aux, dropped = fwd(whole, x, **kw)
    shared = MOE.mlp(whole["shared"], x)
    total = jnp.zeros_like(y)
    drops = 0
    for e in range(E):
        share = MOE.init_moe(jax.random.PRNGKey(0), d, 16, E, n_shared=1,
                             held=1, expert_offset=e)
        for a, b in zip(jax.tree.leaves(share["experts"]),
                        jax.tree.leaves(whole["experts"])):
            np.testing.assert_array_equal(a[0], b[e])
        ys, aux_s, dr = fwd(share, x, expert_offset=e, **kw)
        np.testing.assert_allclose(float(aux_s), float(aux), rtol=1e-6)
        total, drops = total + ys, drops + int(dr)
    np.testing.assert_allclose(total - (E - 1) * shared, y, atol=1e-5)
    assert drops == int(dropped) > 0


def test_gates_not_renormalised():
    """``norm_topk_prob=False``: each routed expert's output is weighted by
    its softmax probability as it is; True divides by the top-k sum."""
    d, E = 16, 4
    p = MOE.init_moe(jax.random.PRNGKey(3), d, 8, E)
    x = jax.random.normal(jax.random.PRNGKey(4), (1, 5, d))
    xt = x[0]
    probs = jax.nn.softmax(xt @ p["router"]["w"], axis=-1)
    g, idx = jax.lax.top_k(probs, 2)
    outs = jnp.stack([MOE.mlp(jax.tree.map(lambda a: a[e], p["experts"]), xt)
                      for e in range(E)])                  # (E, T, d)
    picked = jnp.take_along_axis(outs, idx.T[:, :, None], 0)  # (2, T, d)
    for norm in (False, True):
        w = g / g.sum(-1, keepdims=True) if norm else g
        want = jnp.einsum("tk,ktd->td", w, picked)
        y, _, _ = MOE.moe_forward(p, x, n_experts=E, top_k=2,
                                  capacity_factor=8.0, norm_topk_prob=norm)
        np.testing.assert_allclose(y[0], want, rtol=1e-5, atol=1e-6)
    assert float(g.sum(-1).max()) < 0.999      # the two choices differ


@pytest.fixture(scope="module")
def coded(small):
    """One coded step (its programs compiled once for the module)."""
    agg = CodedAggregator.build(K, decode_iters=8, decode_backend="sparse")
    return agg, CodedTrainStep(small[1], agg, bucket_cap=2048)


def test_coded_step_without_stragglers_is_the_mean_gradient(small, coded):
    cfg, model, params, batch = small
    agg, step = coded
    res = step(jax.tree.map(jnp.copy, params), adamw_init(params), batch,
               np.zeros(agg.n_workers, bool))
    got = step.grads_from_sums(params, res.sums)
    loss_and_grad = jax.jit(jax.value_and_grad(model.loss_fn))
    shard = [loss_and_grad(params, jax.tree.map(lambda a: a[i:i + 1], batch))
             for i in range(K)]
    mean = jax.tree.map(lambda *g: sum(g) / K, *[g for _, g in shard])
    assert res.unresolved == 0
    assert _rel(got, mean) < 1e-5
    losses = [float(loss) for loss, _ in shard]
    np.testing.assert_allclose(res.loss, float(np.mean(losses)), rtol=1e-6)
    assert res.dropped > 0
    # every bucket is cut to the cap, at least 512 lanes, the last padded
    for s in step.stages(params):
        assert len(res.sums[s.name]) == s.layout.n_buckets
        assert s.layout.cap % 512 == 0 and s.layout.cap <= 2048


@pytest.mark.parametrize("erased", [(0, 2), (1, 4, 5), (0, 1, 2, 5)])
def test_coded_step_at_fixed_masks_matches_the_reference(small, coded,
                                                         erased):
    cfg, model, params, batch = small
    agg, step = coded
    mask = np.zeros(agg.n_workers, bool)
    mask[list(erased)] = True
    res = step(jax.tree.map(jnp.copy, params), adamw_init(params), batch,
               mask)
    h_cols = np.stack([np.flatnonzero(r) for r in agg.code.H != 0])
    left = R.peel_erased(h_cols, mask, 8)[:K]
    assert res.unresolved == int(left.sum())
    want_loss, want = R.coded_grad(params, batch["tokens"], batch["labels"],
                                   ~left, _ref_cfg(cfg, model))
    got = step.grads_from_sums(params, res.sums)
    if left.all():
        assert max(float(jnp.abs(g).max()) for g in jax.tree.leaves(got)) == 0
    else:
        assert _rel(got, want) < 1e-5
    np.testing.assert_allclose(res.loss, float(want_loss), rtol=1e-5)


def _walk(jaxpr, out):
    for v in list(jaxpr.invars) + list(jaxpr.outvars):
        if hasattr(v, "aval") and hasattr(v.aval, "shape"):
            out.append(int(np.prod(v.aval.shape)))
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            out.append(int(np.prod(v.aval.shape)))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else [p]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _walk(inner, out)


def test_no_buffer_is_K_deep_in_the_whole_model(small, coded):
    """No program of the step holds an array of K × (all parameters): the
    shard gradients exist one layer at a time."""
    cfg, model, params, batch = small
    agg, step = coded
    n_params = sum(a.size for a in jax.tree.leaves(params))
    tokens = batch["tokens"].reshape(K, 1, S)
    labels = batch["labels"].reshape(K, 1, S)
    sizes: list = []
    _walk(jax.make_jaxpr(step._fwd)(params, tokens, labels).jaxpr, sizes)
    x = jnp.zeros((K, 1, S, cfg.d_model))
    for s in step.stages(params):
        if s.kind == "head":
            from repro.train.coded_step import _head_params
            jx = jax.make_jaxpr(step._grad["head"], static_argnums=0)(
                s.layout, _head_params(params), x, labels)
        elif s.kind == "embed":
            jx = jax.make_jaxpr(step._grad["embed"], static_argnums=0)(
                s.layout, params["embed"], tokens, x)
        else:
            b = jnp.int32(s.where[2]) if s.where[0] == "blocks" else None
            jx = jax.make_jaxpr(step._grad["layer"], static_argnums=0)(
                (s.layout, s.spec), step._stacked(params, s.where), b, x, x)
        _walk(jx.jaxpr, sizes)
        _walk(jax.make_jaxpr(step._reduce)(
            jnp.zeros((K, s.layout.cap)), jnp.zeros(agg.n_workers, bool)
        ).jaxpr, sizes)
    sums = {s.name: tuple(jnp.zeros(s.layout.cap)
                          for _ in range(s.layout.n_buckets))
            for s in step.stages(params)}
    _walk(jax.make_jaxpr(step._opt)(params, adamw_init(params), sums).jaxpr,
          sizes)
    assert max(sizes) < K * n_params / 2
    assert max(sizes) >= K * 2048        # the walk does see the buckets


def test_spans_and_counters_are_recorded(small, coded, tmp_path, capsys):
    from repro.obs import ObsSession
    from repro.obs.report import main as report

    cfg, model, params, batch = small
    agg, step = coded
    session = ObsSession(tmp_path / "m.jsonl")
    try:
        mask = np.zeros(agg.n_workers, bool)
        mask[[0, 2]] = True
        res = step(jax.tree.map(jnp.copy, params), adamw_init(params),
                   batch, mask)
        reg = session.registry
        names = {e["name"] for e in session.tracer.events}
    finally:
        session.finish(quiet=True)
    assert {"train/inputs", "train/grads", "train/reduce",
            "train/fetch"} <= names
    for s in step.stages(params):
        assert reg.get("grad_agg.buckets_total", layer=s.name).value == \
            s.layout.n_buckets
    assert reg.get("grad_agg.unresolved_total").value == res.unresolved == 2
    assert reg.get("moe.tokens_dropped_total").value == res.dropped > 0
    report([str(tmp_path / "m.jsonl")])
    text = capsys.readouterr().out
    assert "== coded training step ==" in text
    assert "moe_tokens_dropped[-]: " + str(res.dropped) in text


def test_jit_scopes_name_the_layers(small):
    cfg, model, params, batch = small
    text = jax.jit(model.loss_fn).lower(
        params, jax.tree.map(lambda a: a[:1], batch)).as_text(
            debug_info=True)
    for scope in ("mla", "moe/dispatch", "moe/experts", "moe/combine"):
        assert scope in text, scope


def test_launch_train_coded_agg_runs_the_model():
    from repro.launch.train import main

    hist = main(["--arch", "deepseek-v2-lite", "--steps", "2", "--batch",
                 "4", "--seq", "16", "--coded-agg", "--n-shards", "4",
                 "--straggler-q0", "0.2", "--decode-backend", "sparse",
                 "--log-every", "0"])
    assert len(hist) == 2 and np.isfinite(hist).all()


def test_the_benchmark_copy_of_the_reference_agrees(small):
    import importlib.util
    from pathlib import Path

    path = Path(__file__).resolve().parents[1] / "bench" / "kinds" / \
        "dsv2_reference.py"
    spec = importlib.util.spec_from_file_location("bench_dsv2_ref", path)
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    cfg, model, params, batch = small
    rc = _ref_cfg(cfg, model)
    resolved = np.array([True, False, True, True])
    a = R.coded_grad(params, batch["tokens"], batch["labels"], resolved, rc)
    b = copy.coded_grad(params, batch["tokens"], batch["labels"], resolved,
                        rc)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
