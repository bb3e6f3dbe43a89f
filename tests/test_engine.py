"""CodedComputeEngine: batched decode parity, pipeline-stage equivalence,
and Scheme-Protocol conformance.

Batched-decode contract (mirrors the backend-parity contract in
test_decoder_backends.py): ``decode_batch`` of B independent erasure
patterns follows BIT-IDENTICAL erasure trajectories to a Python loop of B
single-pattern ``decode`` calls on every backend — solvability is an exact
count and the resolved neighbour per check is uniquely determined — while
decoded *values* agree up to f32 summation order (the batched dense path
lowers matvecs to batched GEMMs, the batch-major sparse round re-associates
row sums).  Values are therefore held to the decoder's value contract,
:func:`repro.core.decoder.peel_error_bound`: each decode within the
peel-chain error bound of the exact codeword.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    CodedComputeEngine,
    FixedCountStragglers,
    Scheme,
    Scheme2,
    make_ldgm,
    make_regular_ldpc,
    peel_decode,
    peel_decode_batch,
    run_pgd,
    scheme_registry,
    second_moment,
)
from repro.core.decoder import peel_error_bound
from repro.data import make_linear_problem

BACKENDS = ("dense", "sparse", "pallas")


def _batch_instance(code, *, B, V, q, seed):
    rng = np.random.default_rng(seed)
    sh = (B, code.K) if V is None else (B, code.K, V)
    msgs = rng.standard_normal(sh)
    cws = np.einsum("nk,bk...->bn...", code.G, msgs)
    erased = rng.random((B, code.N)) < q
    emask = erased if V is None else erased[:, :, None]
    rx = jnp.asarray(np.where(emask, 0.0, cws), jnp.float32)
    return cws, rx, jnp.asarray(erased)


def _assert_batch_matches_loop(code, cws, rx, erased, iters):
    B = rx.shape[0]
    for backend in BACKENDS:
        bat = peel_decode_batch(code, rx, erased, iters, backend=backend)
        assert bat.values.shape == rx.shape
        assert bat.erased.shape == erased.shape
        assert int(bat.rounds_used) == iters
        for i in range(B):
            single = peel_decode(code, rx[i], erased[i], iters,
                                 backend=backend)
            # bit-for-bit: identical erasure trajectory endpoint per element
            np.testing.assert_array_equal(
                np.asarray(bat.erased[i]), np.asarray(single.erased),
                err_msg=f"backend={backend} element={i}: mask diverged")
            # values: the peel-chain value contract against the exact
            # codeword, for the single AND the batched decode (their f32
            # summation orders differ, so bits may too)
            ok = ~np.asarray(single.erased)
            truth = np.asarray(cws[i])
            for name, got in (("single", single.values),
                              ("batch", bat.values[i])):
                got = np.asarray(got)
                bound = peel_error_bound(code, erased[i], got, iters)
                dev = np.abs(got.astype(np.float64) - truth)
                bad = dev[ok] > bound[ok]
                assert not bad.any(), (
                    f"backend={backend} element={i} {name}: "
                    f"{int(bad.sum())} values outside the peel-chain bound "
                    f"(worst excess {float((dev[ok] - bound[ok]).max())})")


@pytest.mark.parametrize("K,B,V,q,seed", [
    (20, 6, None, 0.25, 0),      # the paper's (40, 20) code, scalar queries
    (60, 5, 3, 0.30, 1),         # N = 120: not a multiple of 128, payload V
    (100, 9, None, 0.40, 2),     # heavy erasures: ragged unresolved counts
    (128, 4, 1, 0.20, 3),        # explicit V=1 (not squeezed)
])
def test_batched_decode_matches_single_loop(K, B, V, q, seed):
    code = make_regular_ldpc(K, l=3, r=6, seed=seed)
    cws, rx, erased = _batch_instance(code, B=B, V=V, q=q, seed=seed)
    _assert_batch_matches_loop(code, cws, rx, erased, iters=8)


def test_batched_decode_matches_single_loop_ldgm():
    code = make_ldgm(32, 16, row_weight=4, seed=2)
    cws, rx, erased = _batch_instance(code, B=6, V=4, q=0.3, seed=5)
    _assert_batch_matches_loop(code, cws, rx, erased, iters=6)


def test_batched_ragged_unresolved_counts():
    """Batch elements with wildly different straggler loads (0%..100%) keep
    per-element trajectories: the clean element fully recovers while the
    saturated one stays fully erased, in ONE launch."""
    code = make_regular_ldpc(64, l=3, r=6, seed=4)
    rng = np.random.default_rng(4)
    msgs = rng.standard_normal((4, code.K))
    cws = np.einsum("nk,bk->bn", code.G, msgs)
    erased = np.zeros((4, code.N), bool)
    erased[1] = rng.random(code.N) < 0.15
    erased[2] = rng.random(code.N) < 0.45
    erased[3] = True
    rx = jnp.asarray(np.where(erased, 0.0, cws), jnp.float32)
    for backend in BACKENDS:
        bat = peel_decode_batch(code, rx, jnp.asarray(erased), code.N,
                                backend=backend)
        counts = np.asarray(bat.erased.sum(axis=1))
        assert counts[0] == 0
        assert counts[3] == code.N  # r >= 2: nothing ever solvable
        for i in range(4):
            single = peel_decode(code, rx[i], jnp.asarray(erased[i]), code.N,
                                 backend=backend)
            np.testing.assert_array_equal(np.asarray(bat.erased[i]),
                                          np.asarray(single.erased))


def test_batched_rejects_bad_rank():
    code = make_regular_ldpc(20, l=3, r=6, seed=0)
    with pytest.raises(ValueError):
        peel_decode_batch(code, jnp.zeros((code.N,)), jnp.zeros((code.N,), bool), 2)


# ------------------------------------------------------------ engine stages


def test_engine_stages_compose_to_scheme2_gradient():
    """encode→erase→decode→epilogue through the engine == Scheme2.gradient."""
    prob = make_linear_problem(m=256, k=60, seed=0)
    code = make_regular_ldpc(60, l=3, r=6, seed=0)
    mom = second_moment(prob.X, prob.y)
    s2 = Scheme2.build(code, mom, lr=prob.lr, decode_iters=8)
    eng = s2.engine
    theta = jax.random.normal(jax.random.PRNGKey(0), (60,))
    mask = jnp.zeros(code.N, bool).at[jnp.array([3, 17, 90])].set(True)

    # hand-composed stages
    z = eng.erase(s2.C @ theta, mask)
    dec = eng.decode(z, mask)
    c_hat, unresolved = eng.systematic(dec)
    g_manual = c_hat - jnp.where(unresolved, 0.0, s2.b)

    g, n_unres = s2.gradient(theta, mask)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_manual),
                               rtol=1e-6, atol=1e-6)
    assert int(n_unres) == int(unresolved.sum())


def test_engine_encode_is_systematic():
    code = make_regular_ldpc(40, l=3, r=6, seed=1)
    eng = CodedComputeEngine(code)
    payload = jnp.asarray(np.random.default_rng(0).standard_normal((40, 3)),
                          jnp.float32)
    symbols = eng.encode(payload)
    assert symbols.shape == (code.N, 3)
    np.testing.assert_allclose(np.asarray(symbols[:code.K]),
                               np.asarray(payload), rtol=1e-5, atol=1e-5)


def test_engine_gradient_batch_matches_loop():
    """Scheme2.gradient_batch == per-query Scheme2.gradient (one launch)."""
    prob = make_linear_problem(m=256, k=60, seed=1)
    code = make_regular_ldpc(60, l=3, r=6, seed=1)
    mom = second_moment(prob.X, prob.y)
    for backend in ("dense", "sparse", "pallas"):
        s2 = Scheme2.build(code, mom, lr=prob.lr, decode_iters=8,
                           decode_backend=backend)
        rng = np.random.default_rng(2)
        B = 5
        theta_B = jnp.asarray(rng.standard_normal((B, 60)), jnp.float32)
        mask_B = jnp.asarray(rng.random((B, code.N)) < 0.2)
        g_B, u_B = s2.gradient_batch(theta_B, mask_B)
        assert g_B.shape == (B, 60)
        for i in range(B):
            g, u = s2.gradient(theta_B[i], mask_B[i])
            assert int(u_B[i]) == int(u)
            np.testing.assert_allclose(np.asarray(g_B[i]), np.asarray(g),
                                       rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("backend,V,decode_erases", [
    ("pallas", 512, True),            # symbol-major: the kernel zeroes rows
    ("pallas", 4, False),             # lane-major
    ("pallas_tiled", 256, False),
    ("sparse", 256, False),
])
def test_recover_ignores_erased_values(backend, V, decode_erases):
    """``recover`` equals erase → decode → epilogue bit for bit, with NaN
    on the erased rows, whether the erase runs or the symbol-major decode
    zeroes those rows itself."""
    code = make_regular_ldpc(20, l=3, r=6, seed=0)
    eng = CodedComputeEngine(code, backend=backend, decode_iters=10)
    rng = np.random.default_rng(V)
    z = eng.encode(jnp.asarray(rng.standard_normal((code.K, V)), jnp.float32))
    mask = jnp.asarray(np.isin(np.arange(code.N), [0, 3, 7, 21, 30]))
    assert eng._decode_erases(z) is decode_erases
    got_v, got_u = eng.recover(jnp.where(mask[:, None], jnp.nan, z), mask)
    want_v, want_u = eng.systematic(eng.decode(eng.erase(z, mask), mask))
    np.testing.assert_array_equal(np.asarray(got_u), np.asarray(want_u))
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(want_v))


def test_engine_rejects_unknown_backend():
    code = make_regular_ldpc(20, l=3, r=6, seed=0)
    with pytest.raises(ValueError):
        CodedComputeEngine(code, backend="nope")


def test_engine_adaptive_decode_budget():
    """adaptive=True engines treat decode_iters as the round budget."""
    code = make_regular_ldpc(64, l=3, r=6, seed=5)
    rng = np.random.default_rng(5)
    cw = jnp.asarray(code.encode(rng.standard_normal(code.K)), jnp.float32)
    erased = jnp.asarray(rng.random(code.N) < 0.25)
    rx = jnp.where(erased, 0.0, cw)
    eng = CodedComputeEngine(code, decode_iters=1, adaptive=True)
    dec1 = eng.decode(rx, erased)
    assert int(dec1.rounds_used) <= 1
    eng_full = CodedComputeEngine(code, decode_iters=code.N, adaptive=True)
    dec = eng_full.decode(rx, erased)
    assert int(dec.erased.sum()) <= int(dec1.erased.sum())


# -------------------------------------------------- Scheme Protocol matrix


def _build_all_schemes():
    """One small instance of EVERY scheme in the registry."""
    from repro.core import Scheme1, Scheme2Blocked
    from repro.core.schemes import (GradientCodingFR, Karakus, MDSLee,
                                    Replication, Uncoded)

    prob = make_linear_problem(m=128, k=40, seed=3)
    mom = second_moment(prob.X, prob.y)
    code40 = make_regular_ldpc(40, l=3, r=6, seed=0)     # K == k
    code20 = make_regular_ldpc(20, l=3, r=6, seed=0)     # K | k (2 blocks)
    w = 8
    return {
        "scheme1": Scheme1.build(code20, mom, lr=prob.lr),
        "scheme2": Scheme2.build(code40, mom, lr=prob.lr, decode_iters=6),
        "scheme2-blocked": Scheme2Blocked.build(code20, mom, lr=prob.lr,
                                                decode_iters=6),
        "uncoded": Uncoded(prob.X, prob.y, w=w, lr=prob.lr),
        "replication": Replication(prob.X, prob.y, w=w, lr=prob.lr, r=2),
        "karakus": Karakus.build(prob.X, prob.y, w, lr=prob.lr, seed=0),
        "mds-lee": MDSLee.build(prob.X, prob.y, w, lr=prob.lr, K_code=4),
        "gradient-coding-fr": GradientCodingFR(prob.X, prob.y, w=w, s=1,
                                               lr=prob.lr),
    }


def test_every_registered_scheme_satisfies_protocol_under_run_pgd():
    """The Protocol replaces the old ad-hoc duck typing: every scheme in the
    registry is a runtime instance of Scheme AND actually runs under the
    shared run_pgd driver."""
    instances = _build_all_schemes()
    registry = scheme_registry()
    assert set(instances) == set(registry), "registry/test instance drift"
    for name, scheme in instances.items():
        assert isinstance(scheme, Scheme), f"{name} violates the Protocol"
        assert isinstance(scheme, registry[name])
        res = run_pgd(scheme, jnp.zeros(40), FixedCountStragglers(1),
                      steps=3, key=jax.random.PRNGKey(0))
        assert res.errors.shape == (3,)
        assert res.theta.shape == (40,)
        assert np.isfinite(np.asarray(res.theta)).all(), name


def test_protocol_rejects_non_schemes():
    @dataclasses.dataclass
    class NotAScheme:
        w: int = 4

    assert not isinstance(NotAScheme(), Scheme)
