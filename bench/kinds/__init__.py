"""Data builders and work counts, one module per kind of configuration
(a configuration file names its ``kind``), the plain reference and the
configurations' codes."""
import jax

__all__ = ["seed_key"]


def seed_key(seed: int):
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)
