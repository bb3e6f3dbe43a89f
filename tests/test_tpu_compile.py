"""AOT compiles of the main path's Pallas kernels for a described TPU v5e.

No chip is attached: JAX describes a ``v5e:2x2`` topology and the TPU
compiler (Mosaic for the kernels) compiles for one of its chips, so what
interpret mode cannot show — unaligned slices, unsupported gathers, VMEM
overruns — fails here at no chip time.  Sizes are the chip smoke's: the
paper problem at k = K = 8192, i.e. the (3,6)-regular rate-1/2 code with
N = 16384 and p = 8192, a scalar payload, and the check tile the decoder
picks for that code; the resident kernel compiles at the largest code
``backend="auto"`` still routes to it.

The topology is described inside a module fixture (never at import,
collection or in ``conftest.py``: one process at a time may load the TPU
library), with the persistent compilation cache off around the compiles.
Kernels Mosaic cannot lower must refuse a compiled launch up front.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.decoder import (_DEFAULT_VMEM_BUDGET_BYTES, pick_tile_bp,
                                vmem_bytes_estimate)
from repro.core.ldpc import seeded_structure
from repro.kernels.ldpc_peel import kernel as K
from repro.kernels.ldpc_peel import ops

N, P = 16384, 8192                      # the chip smoke's code


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _resident_shape():
    """The largest rate-1/2 (p, N) that ``auto`` still sends to the
    resident kernel under the default VMEM budget."""
    p = 64
    while (vmem_bytes_estimate((p + 64, 2 * (p + 64)))
           <= _DEFAULT_VMEM_BUDGET_BYTES):
        p += 64
    return p, 2 * p


def _compile(fn, *args, **static):
    compiled = fn.lower(*args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()   # Mosaic, not interpreted
    assert compiled.memory_analysis() is not None
    return compiled


def test_tile_and_resident_sizes_follow_the_dispatch():
    assert pick_tile_bp((P, N)) == 32
    p, n = _resident_shape()
    assert vmem_bytes_estimate((p, n)) <= _DEFAULT_VMEM_BUDGET_BYTES
    assert vmem_bytes_estimate((P, N)) > _DEFAULT_VMEM_BUDGET_BYTES


def test_resident_fixed(one_chip):
    p, n = _resident_shape()
    S = lambda *a: _spec(one_chip, *a)
    _compile(ops._peel_decode_impl, S((p, n)), S((n,)), S((n,), jnp.bool_),
             iters=10, interpret=False)


def test_resident_batch_adaptive(one_chip):
    p, n = _resident_shape()
    S = lambda *a: _spec(one_chip, *a)
    _compile(ops._peel_decode_batch_adaptive_impl, S((p, n)), S((4, n)),
             S((4, n), jnp.bool_), S((4,), jnp.int32), interpret=False)


def test_tiled_fixed(one_chip):
    S = lambda *a: _spec(one_chip, *a)
    _compile(ops._peel_decode_tiled_impl, S((P, N)), S((N,)),
             S((N,), jnp.bool_), iters=10, interpret=False,
             bp=pick_tile_bp((P, N)))


def test_tiled_batch_adaptive(one_chip):
    """The telemetry-budget master decode (batched adaptive at B = 1)."""
    S = lambda *a: _spec(one_chip, *a)
    _compile(ops._peel_decode_batch_adaptive_tiled_impl, S((P, N)),
             S((1, N)), S((1, N), jnp.bool_), S((1,), jnp.int32),
             interpret=False, bp=pick_tile_bp((P, N)))


@pytest.mark.parametrize("adaptive", [False, True])
def test_seeded_dense_tile(one_chip, adaptive):
    spec = seeded_structure(P, N, 8, 0)          # make_seeded_ldpc's (4, 8)
    S = lambda *a: _spec(one_chip, *a)
    if adaptive:
        _compile(ops._peel_decode_adaptive_seeded_impl, S((N,)),
                 S((N,), jnp.bool_), spec=spec, max_iters=10,
                 interpret=False, bp=pick_tile_bp((P, N)))
    else:
        _compile(ops._peel_decode_seeded_impl, S((N,)), S((N,), jnp.bool_),
                 spec=spec, iters=10, interpret=False,
                 bp=pick_tile_bp((P, N)))


def test_check_pass(one_chip):
    S = lambda *a: _spec(one_chip, *a)
    _compile(K.check_pass, S((256, 512)), S((512, 128)), S((512, 1)),
             interpret=False)


def test_kernel_launch_is_scoped_and_keeps_its_name(one_chip):
    """The launch sits in the ``kernel`` name scope, and its op is still
    named after the kernel function, as the profile shows it."""
    p, n = _resident_shape()
    S = lambda *a: _spec(one_chip, *a)
    hlo = _compile(ops._peel_decode_impl, S((p, n)), S((n,)),
                   S((n,), jnp.bool_), iters=10, interpret=False).as_text()
    call = next(line for line in hlo.splitlines()
                if "tpu_custom_call" in line)
    assert call.lstrip().startswith("%decode_fused.")
    assert "/kernel/decode_fused/pallas_call" in call


def _symbol_major_launch(one_chip, p: int = 20, n: int = 40,
                         V: int = 655360):
    """By default the gradient-bucket cell's decode at a tenth of its
    width: the (40, 20) code, a (40, V) payload, D = 10, the decoder's
    lane tile."""
    from repro.core.decoder import pick_tile_lanes

    bv = pick_tile_lanes((p, n), V)
    S = lambda *a: _spec(one_chip, *a)
    return _compile(ops._peel_decode_symbol_major_impl, S((p, n)), S((n, V)),
                    S((n,), jnp.bool_), iters=10, slots=5, bv=bv, chunk=512,
                    interpret=False)


@pytest.mark.parametrize("shape", ["gradagg", "resident"])
def test_symbol_major_fixed(one_chip, shape):
    """Unlike the replay kernel, the symbol-major decode lowers: its rows
    are read at dynamic sublane offsets from SMEM indices, not gathered.
    It fits VMEM for the gradient-bucket code and for the largest code
    ``auto`` sends to the resident kernel (the narrowest lane tile)."""
    p, n = (20, 40) if shape == "gradagg" else _resident_shape()
    compiled = _symbol_major_launch(one_chip, p, n, V=max(65536, n))
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**20


def test_symbol_major_launch_is_scoped_and_keeps_its_name(one_chip):
    hlo = _symbol_major_launch(one_chip).as_text()
    call = next(line for line in hlo.splitlines()
                if "tpu_custom_call" in line)
    assert call.lstrip().startswith("%decode_symbol_major.")
    assert "/kernel/decode_symbol_major/pallas_call" in call


# Kernels whose in-kernel gathers Mosaic rejects: a compiled launch must
# fail up front with a clear error, never deep in the compiler (or fall
# back to another backend unannounced).  No topology needed.

def test_seeded_gather_refuses_compiled_launch():
    spec = seeded_structure(64, 128, 8, 0)
    v, e = jnp.zeros((8, 128)), jnp.zeros((1, 128))
    with pytest.raises(NotImplementedError, match="seeded_mode='gather'"):
        K.decode_seeded(spec, v, e, iters=2, bp=64, interpret=False,
                        mode="gather")


def test_fused_encode_refuses_compiled_launch():
    st = seeded_structure(64, 128, 8, 0)
    y = jnp.zeros((128, 128))
    with pytest.raises(NotImplementedError, match="fused seeded encode"):
        K.encode_seeded_fused(st, y, jnp.zeros((1, 1), jnp.int32),
                              n_out=128, interpret=False)


def test_replay_kernel_refuses_compiled_launch():
    idx = jnp.zeros((8, 6), jnp.int32)
    with pytest.raises(NotImplementedError, match="replay kernel"):
        K.decode_replay(idx, jnp.zeros((8, 6)), jnp.zeros((8, 1)),
                        jnp.zeros((8, 1), jnp.int32), jnp.zeros((256, 128)),
                        jnp.zeros((256, 1)), rounds=1, maxseg=8, n_real=128,
                        interpret=False)


def test_auto_never_picks_gather_on_tpu(monkeypatch):
    """``seeded_mode="auto"`` resolves to the dense tile on TPU whatever
    the FLOPs model prefers, since the gather round cannot compile."""
    from repro.core import decoder
    from repro.core.ldpc import make_seeded_ldpc

    code = make_seeded_ldpc(64, seed=0)
    assert decoder._resolve_seeded_mode("auto", code, 1, 16) == "gather"
    monkeypatch.setattr(decoder.jax, "default_backend", lambda: "tpu")
    assert decoder._resolve_seeded_mode("auto", code, 1, 16) == "dense_tile"
    np.testing.assert_equal(
        decoder._resolve_seeded_mode("gather", code, 1, 16), "gather")
