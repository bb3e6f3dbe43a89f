"""LDPC-coded gradient aggregation (beyond-paper core/grad_agg.py)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import BernoulliStragglers, CodedAggregator
from repro.core.grad_agg import BucketLayout


def test_zero_stragglers_exact_sum():
    agg = CodedAggregator.build(16, redundancy=0.5, row_weight=4, seed=0)
    rng = np.random.default_rng(0)
    partials = jnp.asarray(rng.standard_normal((16, 33)), jnp.float32)
    total, unresolved = agg.aggregate(partials, jnp.zeros(agg.n_workers, bool))
    np.testing.assert_allclose(total, partials.sum(axis=0), rtol=1e-4, atol=1e-4)
    assert int(unresolved) == 0


def test_parity_recovers_single_systematic_erasure():
    agg = CodedAggregator.build(16, redundancy=0.5, row_weight=4, seed=0,
                                decode_iters=20)
    rng = np.random.default_rng(1)
    partials = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    mask = jnp.zeros(agg.n_workers, bool).at[5].set(True)  # shard 5 straggles
    total, unresolved = agg.aggregate(partials, mask)
    assert int(unresolved) == 0
    np.testing.assert_allclose(total, partials.sum(axis=0), rtol=1e-4, atol=1e-4)


def test_unrecovered_shards_zero_filled():
    # erase more than the code can peel: totals = sum over recovered only
    agg = CodedAggregator.build(8, redundancy=0.25, row_weight=3, seed=0,
                                decode_iters=10)
    rng = np.random.default_rng(2)
    partials = jnp.asarray(rng.standard_normal((8, 4)), jnp.float32)
    mask = jnp.zeros(agg.n_workers, bool).at[jnp.arange(6)].set(True)
    total, unresolved = agg.aggregate(partials, mask)
    assert int(unresolved) > 0
    # sanity: the result equals the sum over exactly the recovered systematic set
    G = jnp.asarray(agg.code.G, jnp.float32)
    sym = G @ partials
    from repro.core.decoder import peel_decode
    dec = peel_decode(agg.code, jnp.where(mask[:, None], 0.0, sym), mask, 10)
    rec = ~np.asarray(dec.erased[:8])
    expect = np.asarray(partials)[rec].sum(axis=0)
    np.testing.assert_allclose(total, expect, rtol=1e-4, atol=1e-4)


def test_bernoulli_unbiased_scaled():
    agg = CodedAggregator.build(32, redundancy=0.5, row_weight=4, seed=3,
                                decode_iters=8)
    rng = np.random.default_rng(3)
    partials = jnp.asarray(rng.standard_normal((32, 5)), jnp.float32)
    model = BernoulliStragglers(0.1)

    @jax.jit
    def one(key):
        total, _ = agg.aggregate(partials, model.sample(key, agg.n_workers))
        return total

    keys = jax.random.split(jax.random.PRNGKey(0), 800)
    totals = jax.vmap(one)(keys)
    mean = np.asarray(totals.mean(axis=0))
    gt = np.asarray(partials.sum(axis=0))
    scale = float(mean @ gt / (gt @ gt))
    assert 0.85 < scale <= 1.001  # (1 - q_D) close to 1 for q0=0.1 w/ parity


def test_flatten_roundtrip():
    """A gradient tree cut into equal buckets (the last zero-padded) and
    put back together."""
    tree = {"a": jnp.arange(700.0).reshape(7, 100),
            "b": {"c": jnp.arange(5.0)}}
    lay = BucketLayout.of(tree, max_cap=512)
    assert (lay.cap, lay.n_buckets, lay.size) == (512, 2, 705)
    buckets = lay.pack(tree)
    assert [b.shape for b in buckets] == [(512,), (512,)]
    np.testing.assert_array_equal(buckets[1][705 - 512:], 0.0)
    rt = lay.unpack(buckets)
    np.testing.assert_allclose(rt["a"], tree["a"])
    np.testing.assert_allclose(rt["b"]["c"], tree["b"]["c"])
    # a group smaller than the cap is one bucket, at least 512 lanes
    assert BucketLayout.of({"x": jnp.ones(10)}).cap == 512


def test_end_to_end_coded_training_linear_model():
    """Coded aggregation drives data-parallel GD to convergence on a linear
    model with Bernoulli stragglers — the 'technique applied to any loss'."""
    rng = np.random.default_rng(4)
    k, m, shards = 30, 640, 16
    X = jnp.asarray(rng.standard_normal((m, k)) / np.sqrt(m), jnp.float32)
    theta_star = jnp.asarray(rng.standard_normal(k), jnp.float32)
    y = X @ theta_star
    agg = CodedAggregator.build(shards, redundancy=0.5, row_weight=4, seed=5)
    Xs = X.reshape(shards, m // shards, k)
    ys = y.reshape(shards, m // shards)
    lr = 1.0 / float(jnp.linalg.norm(X, 2)) ** 2
    model = BernoulliStragglers(0.15)

    @jax.jit
    def step(theta, key):
        partials = jax.vmap(lambda Xb, yb: Xb.T @ (Xb @ theta - yb))(Xs, ys)
        g, _ = agg.aggregate(partials, model.sample(key, agg.n_workers))
        return theta - lr * g

    theta = jnp.zeros(k)
    key = jax.random.PRNGKey(6)
    for t in range(500):
        key, k1 = jax.random.split(key)
        theta = step(theta, k1)
    err = float(jnp.linalg.norm(theta - theta_star) / jnp.linalg.norm(theta_star))
    assert err < 0.05, err
