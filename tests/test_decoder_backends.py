"""Decoder-backend parity: dense vs sparse vs fused-Pallas.

The contract (see core/decoder.py's backend matrix): every backend makes
bit-identical *decoding-trajectory* decisions — which checks are solvable,
which coordinate each solvable check resolves, and therefore the exact
erasure mask after every round — because solvability is an exact count of
erased neighbours and all backends resolve the first-erased-column
neighbour.  Decoded *values* agree up to f32 summation order (each backend
accumulates a check's row sum in a different association), so values are
compared with tight tolerances and, independently, against the true
codeword on recovered coordinates.

Shapes deliberately include non-multiples of 128 (the Pallas wrapper must
pad once and unpad exactly), scalar ``(N,)`` payloads, wide ``(N, V)``
payloads, and the all-erased / none-erased edge cases.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.decoder import (
    peel_decode,
    peel_decode_adaptive,
    peel_round,
    peel_round_sparse,
    resolve_backend,
)
from repro.core.ldpc import make_ldgm, make_regular_ldpc

BACKENDS = ("dense", "sparse", "pallas")


def _random_instance(code, *, V, q, seed):
    rng = np.random.default_rng(seed)
    msg = rng.standard_normal((code.K,) if V is None else (code.K, V))
    cw = jnp.asarray(code.encode(msg), jnp.float32)
    erased = jnp.asarray(rng.random(code.N) < q)
    rx = jnp.where(erased if cw.ndim == 1 else erased[:, None], 0.0, cw)
    return cw, rx, erased


def _assert_backend_parity(code, cw, rx, erased, iters):
    results = {
        b: peel_decode(code, rx, erased, iters, backend=b) for b in BACKENDS
    }
    ref = results["dense"]
    truth = np.asarray(cw)
    # The decode itself has f32 cancellation error vs the true codeword
    # (resolving values through chains of near-cancelling row sums); anchor
    # the truth tolerance to the dense reference's own deviation so this
    # stays a parity test, not a conditioning test.
    ok_ref = ~np.asarray(ref.erased)
    ref_dev = float(np.max(np.abs(np.asarray(ref.values)[ok_ref]
                                  - truth[ok_ref]), initial=0.0))
    truth_atol = max(5e-2, 3.0 * ref_dev)
    for name, res in results.items():
        # bit-for-bit: identical erasure trajectory endpoint & round count
        np.testing.assert_array_equal(
            np.asarray(res.erased), np.asarray(ref.erased),
            err_msg=f"backend={name}: erasure mask diverged")
        assert int(res.rounds_used) == iters
        assert res.values.shape == cw.shape
        # values: f32-summation-order agreement with the dense reference.
        # Anchored to the same conditioning measure as the truth check: on
        # an ill-conditioned instance the resolution chain amplifies each
        # backend's (different) per-round rounding by the same factor it
        # amplifies dense's deviation from the codeword.
        np.testing.assert_allclose(
            np.asarray(res.values), np.asarray(ref.values),
            rtol=truth_atol, atol=truth_atol,
            err_msg=f"backend={name}: values diverged from dense")
        # and every recovered coordinate matches the true codeword
        ok = ~np.asarray(res.erased)
        got = np.asarray(res.values)
        np.testing.assert_allclose(
            got[ok], truth[ok], rtol=truth_atol, atol=truth_atol,
            err_msg=f"backend={name}: recovered values != codeword")
    return ref


@pytest.mark.parametrize("K,V,q,seed", [
    (20, None, 0.25, 0),     # the paper's (40, 20) code, scalar payload
    (20, 3, 0.25, 1),        # tiny non-128 payload
    (40, None, 0.35, 2),
    (60, 8, 0.30, 3),        # N = 120: not a multiple of 128
    (100, 7, 0.40, 4),       # odd everything
    (128, 130, 0.30, 5),     # payload wider than one 128 tile
    (256, 1, 0.20, 6),       # explicit V=1 (not squeezed)
])
def test_backends_agree_on_regular_codes(K, V, q, seed):
    code = make_regular_ldpc(K, l=3, r=6, seed=seed)
    cw, rx, erased = _random_instance(code, V=V, q=q, seed=seed)
    _assert_backend_parity(code, cw, rx, erased, iters=10)


@pytest.mark.parametrize("l,r,K", [(3, 6, 48), (4, 8, 64), (3, 9, 90)])
def test_backends_agree_across_degree_profiles(l, r, K):
    code = make_regular_ldpc(K, l=l, r=r, seed=11)
    cw, rx, erased = _random_instance(code, V=5, q=0.3, seed=13)
    _assert_backend_parity(code, cw, rx, erased, iters=8)


@pytest.mark.parametrize("seed", range(4))
def test_backends_agree_on_ldgm(seed):
    code = make_ldgm(32, 16, row_weight=4, seed=seed)
    cw, rx, erased = _random_instance(code, V=4, q=0.3, seed=seed + 50)
    _assert_backend_parity(code, cw, rx, erased, iters=6)


@pytest.mark.parametrize("backend", BACKENDS)
def test_none_erased_is_identity(backend):
    code = make_regular_ldpc(40, l=3, r=6, seed=0)
    cw, rx, _ = _random_instance(code, V=None, q=0.0, seed=0)
    res = peel_decode(code, rx, jnp.zeros(code.N, bool), 5, backend=backend)
    assert not bool(res.erased.any())
    np.testing.assert_array_equal(np.asarray(res.values), np.asarray(cw))


@pytest.mark.parametrize("backend", BACKENDS)
def test_all_erased_stays_erased(backend):
    code = make_regular_ldpc(40, l=3, r=6, seed=0)
    erased = jnp.ones(code.N, bool)
    rx = jnp.zeros((code.N,), jnp.float32)
    res = peel_decode(code, rx, erased, 5, backend=backend)
    # no check ever has exactly one erased neighbour (r >= 2): nothing moves
    assert bool(res.erased.all())
    np.testing.assert_array_equal(np.asarray(res.values), np.asarray(rx))


def test_single_round_sparse_matches_dense_exactly_on_mask():
    """Round-level check, not just the D-round endpoint."""
    code = make_regular_ldpc(64, l=3, r=6, seed=7)
    rng = np.random.default_rng(7)
    cw = jnp.asarray(code.encode(rng.standard_normal((64, 4))), jnp.float32)
    erased = jnp.asarray(rng.random(code.N) < 0.3)
    rx = jnp.where(erased[:, None], 0.0, cw)
    H = jnp.asarray(code.H, jnp.float32)
    v_d, e_d = rx, erased
    v_s, e_s = rx, erased
    idx = jnp.asarray(code.check_idx)
    coeff = jnp.asarray(code.check_coeff)
    for _ in range(6):
        v_d, e_d = peel_round(H, jnp.asarray(code.H_mask), v_d, e_d)
        v_s, e_s = peel_round_sparse(idx, coeff, v_s, e_s)
        np.testing.assert_array_equal(np.asarray(e_d), np.asarray(e_s))
        # Values: the two rounds associate each check's row sum
        # differently (dense matvec vs the sparse compensated chain), so a
        # near-cancelling sum bounds the ABSOLUTE error of the resolved
        # value, not its relative error.
        np.testing.assert_allclose(np.asarray(v_d), np.asarray(v_s),
                                   rtol=1e-3, atol=1e-3)


def test_adaptive_sparse_matches_dense_rounds():
    code = make_regular_ldpc(100, l=3, r=6, seed=9)
    cw, rx, erased = _random_instance(code, V=None, q=0.25, seed=9)
    d = peel_decode_adaptive(code, rx, erased, backend="dense")
    s = peel_decode_adaptive(code, rx, erased, backend="sparse")
    assert int(d.rounds_used) == int(s.rounds_used)
    np.testing.assert_array_equal(np.asarray(d.erased), np.asarray(s.erased))
    np.testing.assert_allclose(np.asarray(d.values), np.asarray(s.values),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("K,q,seed", [(20, 0.25, 0), (64, 0.30, 7),
                                      (100, 0.05, 9), (60, 0.0, 1)])
def test_adaptive_pallas_matches_dense_stopping_rule(K, q, seed):
    """The fused adaptive kernel's in-kernel while_loop must reproduce the
    dense/sparse while_loops exactly: same round count (the early-exit
    'decoding effort tracks stragglers' knob) and same erasure endpoint."""
    code = make_regular_ldpc(K, l=3, r=6, seed=seed)
    cw, rx, erased = _random_instance(code, V=3, q=q, seed=seed)
    d = peel_decode_adaptive(code, rx, erased, backend="dense")
    p = peel_decode_adaptive(code, rx, erased, backend="pallas")
    assert int(d.rounds_used) == int(p.rounds_used)
    np.testing.assert_array_equal(np.asarray(d.erased), np.asarray(p.erased))
    truth = np.asarray(cw)
    ok = ~np.asarray(d.erased)
    dev = float(np.max(np.abs(np.asarray(d.values)[ok] - truth[ok]),
                       initial=0.0))
    tol = max(5e-4, 3.0 * dev)
    np.testing.assert_allclose(np.asarray(p.values), np.asarray(d.values),
                               rtol=tol, atol=tol)


def test_adaptive_pallas_budget_respected():
    code = make_regular_ldpc(64, l=3, r=6, seed=3)
    cw, rx, erased = _random_instance(code, V=None, q=0.3, seed=3)
    res = peel_decode_adaptive(code, rx, erased, 1, backend="pallas")
    ref = peel_decode(code, rx, erased, 1, backend="dense")
    assert int(res.rounds_used) <= 1
    np.testing.assert_array_equal(np.asarray(res.erased),
                                  np.asarray(ref.erased))


def test_fused_decode_is_one_kernel_launch():
    """The whole fixed-D pallas decode must be a SINGLE pallas_call — the
    per-round relaunch (D launches, D re-pads) is exactly what PR 1
    removed."""
    from repro.kernels.ldpc_peel.ops import _peel_decode_impl

    code = make_regular_ldpc(40, l=3, r=6, seed=0)
    H = jnp.asarray(code.H, jnp.float32)
    v = jnp.zeros((code.N, 4), jnp.float32)
    e = jnp.zeros((code.N,), bool)
    fn = _peel_decode_impl.__wrapped__  # un-jitted impl
    jaxpr = jax.make_jaxpr(
        lambda H, v, e: fn(H, v, e, iters=10, interpret=True))(H, v, e)
    assert str(jaxpr).count("pallas_call") == 1


def test_batched_and_adaptive_fused_decodes_are_one_kernel_launch():
    """The engine-era kernels keep the one-launch property: B patterns per
    launch (grid over the batch) and the adaptive early-exit decode
    (in-kernel while_loop) each lower to a single pallas_call."""
    from repro.kernels.ldpc_peel.ops import (_peel_decode_adaptive_impl,
                                             _peel_decode_batch_impl)

    code = make_regular_ldpc(40, l=3, r=6, seed=0)
    H = jnp.asarray(code.H, jnp.float32)
    vB = jnp.zeros((6, code.N, 4), jnp.float32)
    eB = jnp.zeros((6, code.N), bool)
    fn = _peel_decode_batch_impl.__wrapped__
    jaxpr = jax.make_jaxpr(
        lambda H, v, e: fn(H, v, e, iters=10, interpret=True))(H, vB, eB)
    assert str(jaxpr).count("pallas_call") == 1

    v = jnp.zeros((code.N, 4), jnp.float32)
    e = jnp.zeros((code.N,), bool)
    fn = _peel_decode_adaptive_impl.__wrapped__
    jaxpr = jax.make_jaxpr(
        lambda H, v, e: fn(H, v, e, max_iters=40, interpret=True))(H, v, e)
    assert str(jaxpr).count("pallas_call") == 1


# ------------------------------------------- symbol-major wide payloads --

def _gradagg_mask(kind: str, N: int = 40, K: int = 20) -> np.ndarray:
    """Erasure masks on the (40, 20) code of the gradient-bucket cell."""
    m = np.zeros(N, bool)
    if kind == "one_systematic":
        m[3] = True
    elif kind == "parity_heavy":
        m[[1, 22, 25, 27, 30, 31, 33, 36, 38]] = True
    elif kind == "unresolvable":
        m[:K] = True                      # every systematic symbol
    elif kind == "all":
        m[:] = True
    return m


@pytest.mark.parametrize("kind", ["none", "one_systematic", "parity_heavy",
                                  "unresolvable", "all"])
@pytest.mark.parametrize("D", [0, 1, 10])
@pytest.mark.parametrize("V", [128, 1000, 4096])
def test_symbol_major_matches_sparse_and_lane_major(V, D, kind):
    """The symbol-major decode (through ``backend="pallas"`` where the
    payload is past the crossover, directly at 128 lanes): the same final
    mask as sparse and the lane-major kernel, bit for bit; values to the
    f32 tolerance of the tiled tests; erased coordinates it leaves
    unresolved exactly 0 (a nonzero payload there must not leak)."""
    from repro.core.decoder import decode_layout, pick_tile_lanes
    from repro.kernels.ldpc_peel import (peel_decode_pallas,
                                         peel_decode_symbol_major_pallas)

    code = make_regular_ldpc(20, l=3, r=6, seed=0)
    H = jnp.asarray(code.H, jnp.float32)
    rng = np.random.default_rng(V + D)
    cw = jnp.asarray(code.encode(rng.standard_normal((code.K, V))),
                     jnp.float32)
    erased = jnp.asarray(_gradagg_mask(kind))
    rx = jnp.where(erased[:, None], 0.0, cw)
    if V >= 512:
        assert decode_layout("pallas", V) == "symbol_major"
        sym_v, sym_e = peel_decode(code, cw, erased, D, backend="pallas")[:2]
    else:
        assert decode_layout("pallas", V) == "lane_major"
        sym_v, sym_e = peel_decode_symbol_major_pallas(
            H, cw, erased, D, max_degree=code.check_idx.shape[1],
            bv=pick_tile_lanes(code, V))
    sp = peel_decode(code, rx, erased, D, backend="sparse")
    lane_v, lane_e = peel_decode_pallas(H, rx, erased, D)
    np.testing.assert_array_equal(np.asarray(sym_e), np.asarray(sp.erased))
    np.testing.assert_array_equal(np.asarray(sym_e), np.asarray(lane_e))
    if kind == "unresolvable" and D:
        assert 0 < int(sym_e.sum()) < int(erased.sum())
    got = np.asarray(sym_v)
    for ref in (sp.values, lane_v):
        np.testing.assert_allclose(got, np.asarray(ref), rtol=1e-3, atol=1e-3)
    assert (got[np.asarray(sym_e)] == 0.0).all()


def test_symbol_major_is_one_kernel_launch():
    """The trajectory solve is XLA on the (p, N) H and the mask; the
    payload pass is one pallas_call."""
    from repro.kernels.ldpc_peel.ops import _peel_decode_symbol_major_impl

    code = make_regular_ldpc(20, l=3, r=6, seed=0)
    H = jnp.asarray(code.H, jnp.float32)
    v = jnp.zeros((code.N, 1024), jnp.float32)
    e = jnp.zeros((code.N,), bool)
    fn = _peel_decode_symbol_major_impl.__wrapped__
    jaxpr = jax.make_jaxpr(lambda H, v, e: fn(
        H, v, e, iters=10, slots=5, bv=512, chunk=512, interpret=True))(H, v, e)
    assert str(jaxpr).count("pallas_call") == 1


def test_decode_layout_follows_the_payload_shape():
    """Symbol-major only for the resident "pallas" fixed-D decode of a
    payload at least 512 lanes wide, whatever N; each trace of a fixed-D
    Pallas decode counts its layout once."""
    from repro.core.decoder import decode_layout, vmem_bytes_estimate
    from repro.obs import metrics, report

    code = make_regular_ldpc(20, l=3, r=6, seed=0)          # N = 40
    assert decode_layout("pallas", 4096) == "symbol_major"
    assert decode_layout("pallas", 512) == "symbol_major"
    assert decode_layout("pallas", 511) == "lane_major"
    assert decode_layout("pallas", 128) == "lane_major"
    assert decode_layout("pallas", 1) == "lane_major"
    # the lsq cells' code: too big for the resident kernel, so tiled
    assert vmem_bytes_estimate((8192, 16384)) > 8 * 2**20
    for backend in ("pallas_tiled", "pallas_seeded", "sparse", "dense"):
        assert decode_layout(backend, 1) == "lane_major"
        assert decode_layout(backend, 4096) == "lane_major"

    e = jnp.zeros((code.N,), bool)

    def layouts(*Vs):
        with metrics.recording() as reg:
            for V in Vs:
                jax.eval_shape(lambda v: peel_decode(
                    code, v, e, 10, backend="pallas").values,
                    jax.ShapeDtypeStruct((code.N, V), jnp.float32))
        entries = list(reg.snapshot().values())
        return {v["labels"]["layout"]: int(v["value"]) for v in entries
                if v["name"] == "decoder.layout_total"}, entries

    assert layouts(4096)[0] == {"symbol_major": 1}
    assert layouts(1, 8)[0] == {"lane_major": 2}
    counts, entries = layouts(4096, 1)
    assert counts == {"symbol_major": 1, "lane_major": 1}
    text = report.summarize({}, entries)
    assert "layout[layout=symbol_major]: 1" in text
    assert "layout[layout=lane_major]: 1" in text


def test_neighbor_table_invariants():
    for code in (make_regular_ldpc(64, l=3, r=6, seed=1),
                 make_ldgm(32, 16, row_weight=4, seed=1)):
        idx, coeff = code.check_idx, code.check_coeff
        p = code.p
        assert idx.shape == coeff.shape and idx.shape[0] == p
        assert idx.dtype == np.int32 and coeff.dtype == np.float32
        mask = code.H != 0.0
        r_max = idx.shape[1]
        assert r_max == int(mask.sum(axis=1).max())
        for i in range(p):
            cols = np.flatnonzero(mask[i])
            assert (idx[i, : cols.size] == cols).all()          # ascending
            assert (idx[i, cols.size:] == code.N).all()         # sentinel pad
            np.testing.assert_array_equal(coeff[i, : cols.size],
                                          code.H[i, cols].astype(np.float32))
            assert (coeff[i, cols.size:] == 0.0).all()
        # column-side table (the scatter-free batched round's gather table)
        vidx = code.var_idx
        assert vidx.shape[0] == code.N and vidx.dtype == np.int32
        assert vidx.shape[1] == int(mask.sum(axis=0).max())
        for j in range(code.N):
            rows = np.flatnonzero(mask[:, j])
            assert (vidx[j, : rows.size] == rows).all()
            assert (vidx[j, rows.size:] == p).all()


def test_resolve_backend_matrix():
    code = make_regular_ldpc(20, l=3, r=6, seed=0)       # N = 40 (small)
    big = make_regular_ldpc(256, l=3, r=6, seed=0)       # N = 512
    on_cpu = jax.default_backend() != "tpu"
    if on_cpu:
        assert resolve_backend("auto", code) == "dense"
        assert resolve_backend("auto", big) == "sparse"
    for b in ("dense", "sparse", "pallas", "pallas_tiled"):
        assert resolve_backend(b, code) == b
    # since the fused adaptive kernel landed, adaptive keeps pallas
    assert resolve_backend("pallas", code, adaptive=True) == "pallas"
    # raw (H, Hb) tuples: dense only
    tup = (jnp.asarray(code.H, jnp.float32), jnp.asarray(code.H_mask))
    assert resolve_backend("auto", tup) == "dense"
    with pytest.raises(ValueError):
        resolve_backend("sparse", tup)
    with pytest.raises(ValueError):
        resolve_backend("nope", code)
    # the VMEM estimate the TPU "auto" dispatch uses: the old N<=512
    # resident cutoff falls out of the default 8 MiB budget at rate 1/2
    from repro.core.decoder import (_DEFAULT_VMEM_BUDGET_BYTES,
                                    vmem_bytes_estimate)
    assert vmem_bytes_estimate(big) <= _DEFAULT_VMEM_BUDGET_BYTES
    assert vmem_bytes_estimate((1024, 2048)) > _DEFAULT_VMEM_BUDGET_BYTES


def test_tuple_code_still_decodes_dense():
    """Back-compat: callers passing raw (H, Hb) keep working via dense."""
    code = make_regular_ldpc(40, l=3, r=6, seed=2)
    cw, rx, erased = _random_instance(code, V=None, q=0.25, seed=2)
    ref = peel_decode(code, rx, erased, 8, backend="dense")
    tup = (jnp.asarray(code.H, jnp.float32), jnp.asarray(code.H_mask))
    got = peel_decode(tup, rx, erased, 8)
    np.testing.assert_array_equal(np.asarray(got.erased), np.asarray(ref.erased))
    np.testing.assert_array_equal(np.asarray(got.values), np.asarray(ref.values))
