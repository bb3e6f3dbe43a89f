"""The edge-permuted decode: a flooding round on the code's edge array.

``core/benes.py`` routes a Beneš network for the permutation between the
code's variable-major and check-major edge layouts; the kernel
(``kernels/ldpc_peel`` ``decode_edge_permuted``, interpret mode here)
moves each round's values and erasure flags through it and back.  These
tests hold the router to every permutation it is given, and the kernel
to the XLA sparse decode: the same final erasure mask bit for bit, values
within ``peel_error_bound`` of the exact codeword, unresolved and known
rows returned as they came in, NaN survivors kept.
"""
import functools

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.benes import (VREG_SLOTS, apply_benes, build_edge_network,
                              route_benes)
from repro.core.decoder import peel_decode, peel_error_bound
from repro.core.ldpc import LDPCCode, make_regular_ldpc
from repro.kernels.ldpc_peel import peel_decode_edge_permuted_pallas


def _irregular_code(K: int = 300, p: int = 140, seed: int = 3) -> LDPCCode:
    """H = [P  −I] with 2 to 6 message columns a row: both neighbour
    tables are padded (rows of 3 to 7 entries, columns of 0 and up)."""
    rng = np.random.default_rng(seed)
    P = np.zeros((p, K))
    for i in range(p):
        cols = rng.choice(K, int(rng.integers(2, 7)), replace=False)
        P[i, cols] = rng.standard_normal(cols.size)
    H = np.hstack([P, -np.eye(p)])
    G = np.vstack([np.eye(K), P])
    return LDPCCode(H=H, G=G, N=K + p, K=K, l=3, r=7, kind="ldgm", seed=seed)


@functools.lru_cache(maxsize=None)
def _code(name: str) -> LDPCCode:
    if name == "irregular":
        return _irregular_code()
    return make_regular_ldpc(int(name.split("-")[1]) // 2, l=3, r=6, seed=0)


CODES = ("36-256", "36-1024", "irregular")


def _mask(code: LDPCCode, kind: str, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if kind == "none":
        return np.zeros(code.N, bool)
    if kind == "all":
        return np.ones(code.N, bool)
    if kind == "worker":                 # 16 workers, each lost w.p. 0.3
        return (rng.random(16) < 0.3)[np.arange(code.N) * 16 // code.N]
    return rng.permutation(code.N) < code.N // 2


@pytest.mark.parametrize("case", [("random", 1 << m) for m in range(1, 13)]
                         + [("code", "36-256"), ("code", "irregular")])
def test_benes_network_routes_every_permutation(case):
    """Applied in numpy, the routed network moves each slot's content to
    its destination exactly, and the stages reversed move it back; for a
    code, each variable-major edge slot lands on its check-major slot."""
    kind, arg = case
    rng = np.random.default_rng(7)
    if kind == "random":
        perm = rng.permutation(arg)
        words = route_benes(perm)
        x = rng.standard_normal(arg)
        want = np.empty_like(x)
        want[perm] = x
        out = apply_benes(x, words)
        np.testing.assert_array_equal(out, want)
        np.testing.assert_array_equal(apply_benes(out, words, inverse=True),
                                      x)
        return
    code = _code(arg)
    net = build_edge_network(code.check_idx, code.check_coeff, code.N)
    g = net.geometry
    Np, pp = g.var_vregs * VREG_SLOTS, g.check_vregs * VREG_SLOTS
    # label each variable-major edge slot (t, j) by j + 1, the rest by 0
    var = np.zeros(g.slots, np.int64)
    for t in range(g.l_max):
        real = code.var_idx[:, t] < code.p
        var[t * Np:t * Np + code.N] = np.where(real, np.arange(code.N) + 1, 0)
    moved = apply_benes(var, net.words)
    want = np.zeros(g.slots, np.int64)
    for s in range(g.r_max):
        real = code.check_idx[:, s] < code.N
        want[s * pp:s * pp + code.p] = np.where(real,
                                                code.check_idx[:, s] + 1, 0)
        np.testing.assert_array_equal(
            net.coeff[s * pp:s * pp + code.p],
            np.where(real, code.check_coeff[:, s], 0.0))
    np.testing.assert_array_equal(moved, want)
    np.testing.assert_array_equal(apply_benes(moved, net.words,
                                              inverse=True), var)


@pytest.mark.parametrize("mask", ["none", "worker", "half", "all"])
@pytest.mark.parametrize("D", [0, 1, 10])
@pytest.mark.parametrize("name", CODES)
def test_edge_permuted_matches_sparse(name, D, mask):
    """The final erasure mask and unresolved count equal the sparse
    decode's bit for bit; resolved values lie within the value contract
    of the exact codeword; erased rows left unresolved and known rows come
    back as they came in."""
    code = _code(name)
    seed = 100 * CODES.index(name) + 10 * D + len(mask)
    rng = np.random.default_rng(seed)
    cw = code.encode(rng.standard_normal(code.K)).astype(np.float32)
    erased = _mask(code, mask, seed)
    # a nonzero payload on erased rows: never read, returned if unresolved
    rx = np.where(erased, rng.standard_normal(code.N), cw).astype(np.float32)
    sp = peel_decode(code, jnp.where(erased, 0.0, rx), erased, D,
                     backend="sparse")
    v, e = peel_decode_edge_permuted_pallas(code.edge_network,
                                            jnp.asarray(rx),
                                            jnp.asarray(erased), D)
    v, e = np.asarray(v), np.asarray(e)
    np.testing.assert_array_equal(e, np.asarray(sp.erased))
    assert int(e.sum()) == int(np.asarray(sp.erased).sum())
    if D == 10 and mask in ("worker", "half"):
        assert int(e.sum()) < int(erased.sum())
    np.testing.assert_array_equal(v[~erased], rx[~erased])
    np.testing.assert_array_equal(v[e], rx[e])
    bound = peel_error_bound(code, erased, v, rounds=D)
    done = erased & ~e
    assert (np.abs(v - cw)[done] <= bound[done]).all()


def test_edge_permuted_keeps_nan_survivors_and_a_wide_payload():
    """A NaN on a received symbol stays NaN and is never taken for an
    erasure (the trajectory is the sparse decode's); the checks it feeds
    resolve to NaN, as on every backend.  A payload of V columns decodes
    column by column on the one trajectory."""
    code = _code("36-256")
    rng = np.random.default_rng(11)
    cw = code.encode(rng.standard_normal((code.K, 3))).astype(np.float32)
    erased = _mask(code, "half", 11)
    rx = np.where(erased[:, None], 0.0, cw).astype(np.float32)
    j = int(np.flatnonzero(~erased)[0])
    rx[j, 1] = np.nan
    sp = peel_decode(code, rx, erased, 10, backend="sparse")
    v, e = peel_decode_edge_permuted_pallas(code.edge_network,
                                            jnp.asarray(rx),
                                            jnp.asarray(erased), 10)
    v, e = np.asarray(v), np.asarray(e)
    np.testing.assert_array_equal(e, np.asarray(sp.erased))
    assert np.isnan(v[j, 1]) and not e[j]
    np.testing.assert_array_equal(np.isnan(v), np.isnan(np.asarray(
        sp.values)))
    bound = peel_error_bound(code, erased, np.where(np.isnan(v), 0.0, v),
                             rounds=10)
    done = (erased & ~e)[:, None] & ~np.isnan(v)
    assert (np.abs(v - cw)[done] <= bound[done]).all()
