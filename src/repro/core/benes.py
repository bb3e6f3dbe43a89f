"""Beneš permutation networks, routed on the host, and the edge network of
an LDPC code.

A Beneš network on ``n = 2^m`` slots is ``2m − 1`` stages of XOR-partner
exchanges: stage ``k`` swaps the contents of slot ``x`` and slot
``x ^ (1 << bit(k))`` wherever ``x``'s switch bit for that stage is set
(a switch is symmetric: ``x`` and its partner share the bit).  The stage
bits run ``m−1, …, 1, 0, 1, …, m−1``, and every permutation of the slots
has a setting; :func:`route_benes` finds one with the looping algorithm,
one level per bit, each level's cycles 2-coloured at once by pointer
doubling (vectorised numpy, no per-cycle Python loop).  Each stage is an
involution, so the stages run in reverse order apply the inverse
permutation with the same switches.

Stage ``k``'s switch bits are stored as bit ``k % 32`` of word ``k // 32``
of one ``uint32`` word per slot (one word up to ``n = 2^16``).

:func:`build_edge_network` lays an LDPC code's Tanner-graph edges out
twice — variable-major (slot ``t`` of variable ``j``, checks ascending)
and check-major (slot ``s`` of check ``i``, neighbours ascending), each
as slot-major planes — and routes the permutation between the two.  The
edge-permuted decode kernel (``kernels/ldpc_peel``) moves a flooding
round's values through it and reduces over a check's or a variable's
edges elementwise over planes.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

__all__ = ["stage_bits", "route_benes", "apply_benes", "EdgeGeometry",
           "EdgeNetwork", "edge_geometry", "build_edge_network",
           "VREG_SLOTS"]

# One f32 vreg (8 sublanes x 128 lanes): the edge planes are whole vregs.
VREG_SLOTS = 1024


def stage_bits(m: int) -> tuple[int, ...]:
    """Exchange bit of each of the ``2m − 1`` stages on ``2^m`` slots."""
    return tuple(range(m - 1, 0, -1)) + tuple(range(m))


def _log2(n: int) -> int:
    m = int(n).bit_length() - 1
    if n < 1 or (1 << m) != n:
        raise ValueError(f"a Beneš network needs a power-of-two size; got {n}")
    return m


def route_benes(perm) -> np.ndarray:
    """Switch words ``(ceil((2m−1)/32), n) uint32`` that move the content
    of slot ``a`` to slot ``perm[a]`` (``n = len(perm) = 2^m``)."""
    sigma = np.asarray(perm, np.int64).copy()
    n = sigma.size
    m = _log2(n)
    if not np.array_equal(np.sort(sigma), np.arange(n)):
        raise ValueError("perm is not a permutation of range(n)")
    S = 2 * m - 1
    sw = np.zeros((S, n), bool)
    pos = np.arange(n, dtype=np.int64)
    inv = np.empty(n, np.int64)
    for level in range(m - 1):
        b = m - 1 - level
        B = 1 << b
        inv[sigma] = pos
        ip = pos ^ B                       # input partner
        f = inv[sigma[ip] ^ B]             # two steps round the cycle
        # Each cycle alternates input- and output-partner edges; f's two
        # orbits on it are its even and odd nodes, coloured apart: the
        # orbit with the smaller least slot takes sub-network 0.
        low, g = pos.copy(), f
        for _ in range(b + 1):             # orbits are at most 2^b long
            low = np.minimum(low, low[g])
            g = g[g]
        h = low > low[ip]                  # the sub-network each slot takes
        s1 = h != ((pos >> b) & 1).astype(bool)
        s2 = np.empty(n, bool)
        s2[sigma] = h != ((sigma >> b) & 1).astype(bool)
        sw[level], sw[S - 1 - level] = s1, s2
        nxt = np.empty(n, np.int64)
        nxt[pos ^ (s1 * B)] = sigma ^ (s2[sigma] * B)
        sigma = nxt
    sw[m - 1] = sigma != pos               # the middle stage, bit 0
    words = np.zeros(((S + 31) // 32, n), np.uint32)
    for k in range(S):
        words[k // 32] |= sw[k].astype(np.uint32) << np.uint32(k % 32)
    return words


def apply_benes(x, words, *, inverse: bool = False) -> np.ndarray:
    """The network applied in numpy along ``x``'s first axis: ``out[perm[a]]
    = x[a]``, or the inverse with ``inverse=True``."""
    x = np.asarray(x)
    n = x.shape[0]
    bits = stage_bits(_log2(n))
    pos = np.arange(n)
    ks = range(len(bits) - 1, -1, -1) if inverse else range(len(bits))
    for k in ks:
        sw = ((words[k // 32] >> np.uint32(k % 32)) & 1).astype(bool)
        sw = sw.reshape((n,) + (1,) * (x.ndim - 1))
        x = np.where(sw, x[pos ^ (1 << bits[k])], x)
    return x


class EdgeGeometry(NamedTuple):
    """Static shape of an edge network, in whole ``VREG_SLOTS`` vregs:
    ``var_vregs`` per variable-major plane (N padded), ``check_vregs`` per
    check-major plane (p padded), ``l_max`` / ``r_max`` planes, and
    ``slot_vregs`` in all (a power of two)."""
    var_vregs: int
    check_vregs: int
    l_max: int
    r_max: int
    slot_vregs: int

    @property
    def slots(self) -> int:
        return self.slot_vregs * VREG_SLOTS

    @property
    def stages(self) -> int:
        return 2 * _log2(self.slots) - 1

    @property
    def vmem_bytes(self) -> int:
        """The edge-permuted kernel's VMEM working set: switch words,
        check-major weights and a payload column with its mask, each
        double-buffered, and the two edge planes."""
        words = -(-self.stages // 32) * self.slot_vregs
        vregs = (2 * (words + self.r_max * self.check_vregs
                      + 4 * self.var_vregs) + 2 * self.slot_vregs)
        return vregs * VREG_SLOTS * 4


class EdgeNetwork(NamedTuple):
    """A code's edge permutation, routed: ``words (n_words, slots) uint32``
    move variable-major slot ``t·Np + j`` to check-major slot ``s·pp + i``
    for every edge ``(i, j)``, and ``coeff (r_max·pp,) f32`` holds the
    check-major edge weights (0 on padding slots, which are never edges)."""
    geometry: EdgeGeometry
    words: np.ndarray
    coeff: np.ndarray


def _padded(n: int) -> int:
    return max(VREG_SLOTS, -(-n // VREG_SLOTS) * VREG_SLOTS)


def edge_geometry(check_idx, N: int) -> EdgeGeometry:
    """The :class:`EdgeGeometry` of the code with neighbour table
    ``check_idx (p, r_max)`` (sentinel ``N``), without routing it."""
    check_idx = np.asarray(check_idx)
    p, r_max = check_idx.shape
    l_max = int(max(np.bincount(check_idx[check_idx < N], minlength=1).max(),
                    1))
    Np, pp = _padded(N), _padded(p)
    slots = 1 << max(int(l_max * Np - 1).bit_length(),
                     int(r_max * pp - 1).bit_length(),
                     _log2(VREG_SLOTS))
    return EdgeGeometry(Np // VREG_SLOTS, pp // VREG_SLOTS, l_max, r_max,
                        slots // VREG_SLOTS)


def build_edge_network(check_idx, check_coeff, N: int) -> EdgeNetwork:
    """Route the variable-major → check-major edge permutation of the code
    whose neighbour table is ``check_idx (p, r_max)`` (sentinel ``N``) /
    ``check_coeff``.  Slots that are no edge are paired in ascending
    order; their check-major weight is 0."""
    geom = edge_geometry(check_idx, N)
    check_idx = np.asarray(check_idx)
    chk, s = np.nonzero(check_idx < N)          # ascending check, then slot
    var = check_idx[chk, s].astype(np.int64)
    order = np.lexsort((chk, var))              # by variable, checks ascending
    first = np.searchsorted(var[order], var[order])
    t = np.empty_like(var)
    t[order] = np.arange(var.size) - first
    src = t * (geom.var_vregs * VREG_SLOTS) + var
    dst = s.astype(np.int64) * (geom.check_vregs * VREG_SLOTS) + chk
    perm = np.empty(geom.slots, np.int64)
    perm[src] = dst
    free_src = np.ones(geom.slots, bool)
    free_src[src] = False
    free_dst = np.ones(geom.slots, bool)
    free_dst[dst] = False
    perm[free_src] = np.flatnonzero(free_dst)
    coeff = np.zeros(geom.r_max * geom.check_vregs * VREG_SLOTS, np.float32)
    coeff[dst] = np.asarray(check_coeff)[chk, s]
    return EdgeNetwork(geom, route_benes(perm), coeff)
