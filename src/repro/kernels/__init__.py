"""Pallas TPU kernels for the compute hot spots.

Each kernel package contains:
  kernel.py — pl.pallas_call with explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd wrapper (padding, grid setup, epilogue)
  ref.py    — pure-jnp oracle used by the allclose test sweeps

Off-TPU the kernels run in interpret mode (the kernel body is evaluated as
plain JAX ops); the BlockSpecs are written for TPU VMEM/MXU tiling
(128-aligned matmul dims, f32 accumulation), and ``tests/test_tpu_compile.py``
compiles the decode kernels for a described TPU v5e.

Kernels:
  ldpc_peel       — fused check-node pass of the peeling decoder (the paper's
                    per-step master-side hot loop)
  block_matmul    — tiled C = A @ B (moment encode G@M; worker matvec C@theta)
  flash_attention — causal online-softmax attention (zoo serving/training)

Every kernel entry takes ``interpret=None`` by default, resolved by
:func:`detect_interpret`: compiled on TPU, interpret mode elsewhere — so no
kernel runs interpreted on the chip unless a caller asks for it.
"""
import jax

__all__ = ["detect_interpret"]


def detect_interpret(interpret: bool | None) -> bool:
    """Pallas runs compiled only on TPU; anywhere else use interpret mode."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
