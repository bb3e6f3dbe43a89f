"""Tiled matmul kernel: C = A @ B with (bm, bn, bk) VMEM tiles.

Used for the paper's two dense hot spots:
  * one-time moment encode  C = G @ M        (N x K) @ (K x k)
  * per-step worker compute z = C_local @ θ  (rows x k) @ (k x 1-ish)

MXU notes: all three tile dims default to 128 (the MXU systolic shape);
accumulation is f32 regardless of input dtype; the k-loop is the innermost
grid dimension so each output tile stays resident in VMEM while A/B tiles
stream through.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import detect_interpret

__all__ = ["matmul_kernel_call"]


def _mm_kernel(a_ref, b_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot(
        a_ref[...], b_ref[...],
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul_kernel_call(A: jax.Array, B: jax.Array, *, bm: int = 128,
                       bn: int = 128, bk: int = 128,
                       interpret: bool | None = None):
    """A (M, K) @ B (K, N) -> (M, N) f32. Dims must be tile multiples
    (ops.py pads)."""
    M, K = A.shape
    K2, N = B.shape
    assert K == K2
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        _mm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=detect_interpret(interpret),
    )(A, B)
