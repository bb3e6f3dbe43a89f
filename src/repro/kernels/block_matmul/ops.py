"""jit'd wrappers: padding + tile-size selection for the matmul kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core.padding import pad_axis_to
from repro.kernels import detect_interpret
from repro.kernels.block_matmul.kernel import matmul_kernel_call

__all__ = ["block_matmul", "coded_matvec", "encode_gm"]


def _pad(x, m0, m1):
    return pad_axis_to(pad_axis_to(x, m0, 0), m1, 1)


@partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def block_matmul(A, B, *, bm=128, bn=128, bk=128,
                 interpret: bool | None = None):
    """General tiled A @ B with automatic padding to tile multiples.
    ``interpret=None``: compiled on TPU, interpret mode elsewhere."""
    M, N = A.shape[0], B.shape[1]
    bm = min(bm, max(8, M))
    bn = min(bn, max(8, N))
    bk = min(bk, max(8, A.shape[1]))
    Ap = _pad(A.astype(jnp.float32), bm, bk)
    Bp = _pad(B.astype(jnp.float32), bk, bn)
    out = matmul_kernel_call(Ap, Bp, bm=bm, bn=bn, bk=bk,
                             interpret=detect_interpret(interpret))
    return out[:M, :N]


def coded_matvec(C, theta, *, interpret: bool | None = None):
    """Worker-side z = C @ theta (the per-step hot op of Scheme 2)."""
    return block_matmul(C, theta[:, None], interpret=interpret)[:, 0]


def encode_gm(G, M, *, interpret: bool | None = None):
    """Moment encode C = G @ M (one-time preprocessing at scale)."""
    return block_matmul(G, M, interpret=interpret)
