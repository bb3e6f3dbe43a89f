"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS for 512 placeholder
devices before any jax import; tests and benchmarks see the single real CPU
device).
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_mesh", "make_abstract_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    """TPU v5e production mesh: 16x16 = 256 chips/pod; 2 pods = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (reduced integration tests use e.g. (2, 2))."""
    return jax.make_mesh(shape, axes)


def make_abstract_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Device-free ``AbstractMesh(axis_sizes, axis_names)`` for AOT
    lowering against a described mesh."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(shape, axes)
