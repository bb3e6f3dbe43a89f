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


@pytest.fixture(scope="module")
def lsq_code():
    """A (3,6)-regular code of the lsq cells' size, parity-only."""
    from repro.core.ldpc import make_parity_only_ldpc

    return make_parity_only_ldpc(P, l=3, r=6, seed=0)


def test_edge_permuted_fixed(one_chip, lsq_code):
    """The edge-permuted decode at the lsq cells' size (N = 16384, V = 1),
    launched inside the master program's ``decode`` scope: the op keeps
    the kernel's name and the profile reads it under
    ``decode/kernel/decode_edge_permuted``."""
    net = lsq_code.edge_network
    assert net.geometry.slots == 65536 and net.words.shape == (1, 65536)
    S = lambda *a: _spec(one_chip, *a)

    @jax.jit
    def master(words, coeff, v, e):
        with jax.named_scope("decode"):
            return ops._peel_decode_edge_permuted_impl(
                words, coeff, v, e, iters=10, geom=net.geometry,
                interpret=False)

    compiled = _compile(master, S((64, 8, 128), jnp.int32),
                        S((48, 8, 128)), S((N,)), S((N,), jnp.bool_))
    call = next(line for line in compiled.as_text().splitlines()
                if "tpu_custom_call" in line)
    assert call.lstrip().startswith("%decode_edge_permuted.")
    scopes = [p for p in call.split('op_name="')[1].split('"')[0].split("/")
              if not p.startswith("jit(")]
    assert "/".join(scopes[:3]) == "decode/kernel/decode_edge_permuted"
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2**20


def test_auto_takes_the_edges_for_the_lsq_code_on_tpu(lsq_code, monkeypatch):
    """On TPU the lsq-size code's fixed single-pattern decode of a narrow
    payload runs on its edge network (counted once per trace), and its
    sync driver places no H; the (40, 20) code stays resident or
    symbol-major, and the batched-adaptive entry stays streamed."""
    from repro.core import decoder
    from repro.core.engine import CodedComputeEngine
    from repro.core.ldpc import make_regular_ldpc
    from repro.distributed.master import master_decode_operand
    from repro.obs import metrics

    monkeypatch.setattr(decoder.jax, "default_backend", lambda: "tpu")
    layout = decoder.decode_layout
    assert decoder.resolve_backend("auto", lsq_code) == "pallas_tiled"
    assert layout("pallas_tiled", 1, lsq_code) == "edge_permuted"
    assert layout("pallas_tiled", decoder._EDGE_PERMUTED_MAX_V,
                  lsq_code) == "edge_permuted"
    assert layout("pallas_tiled", decoder._EDGE_PERMUTED_MAX_V + 1,
                  lsq_code) == "lane_major"
    gradagg = make_regular_ldpc(20, l=3, r=6, seed=0)
    assert decoder.resolve_backend("auto", gradagg) == "pallas"
    assert layout("pallas", 1, gradagg) == "lane_major"
    assert layout("pallas", 4096, gradagg) == "symbol_major"

    v, e = jax.ShapeDtypeStruct((N,), jnp.float32), jax.ShapeDtypeStruct(
        (N,), jnp.bool_)
    with metrics.recording() as reg:
        jax.eval_shape(lambda v, e: decoder.peel_decode(
            lsq_code, v, e, 10).values, v, e)
    counts = {x["labels"]["layout"]: int(x["value"])
              for x in reg.snapshot().values()
              if x["name"] == "decoder.layout_total"}
    assert counts == {"edge_permuted": 1}
    jaxpr = str(jax.make_jaxpr(lambda v, e, b: decoder.peel_decode_batch_adaptive(
        lsq_code, v, e, 10, budgets=b).values)(
            jnp.zeros((1, N)), jnp.zeros((1, N), bool),
            jnp.zeros((1,), jnp.int32)))
    assert "decode_fused_batch_adaptive_tiled" in jaxpr
    assert "decode_edge_permuted" not in jaxpr

    eng = CodedComputeEngine(lsq_code)
    assert master_decode_operand(eng, None, fixed_width=1) is None


def test_master_decode_operand_places_h_where_a_program_reads_it(
        monkeypatch):
    """The sync driver's fixed-D decode on the edges reads no H; a driver
    with another decode (the telemetry budget's and the pipelined folds'
    batched one), the adaptive engine and a payload past the crossover
    do."""
    from repro.core import decoder
    from repro.core.engine import CodedComputeEngine
    from repro.core.ldpc import make_regular_ldpc
    from repro.distributed.master import master_decode_operand

    monkeypatch.setattr(decoder.jax, "default_backend", lambda: "tpu")
    code = make_regular_ldpc(128, l=3, r=6, seed=0)
    budget = 300_000                  # too small for the resident kernel
    eng = CodedComputeEngine(code, vmem_budget_bytes=budget)
    assert decoder.resolve_backend("auto", code,
                                   vmem_budget_bytes=budget) == "pallas_tiled"
    dev = jax.devices("cpu")[0]
    assert master_decode_operand(eng, dev, fixed_width=1) is None
    wide = decoder._EDGE_PERMUTED_MAX_V + 1
    for H in (master_decode_operand(eng, dev),
              master_decode_operand(eng, dev, fixed_width=wide),
              master_decode_operand(CodedComputeEngine(
                  code, vmem_budget_bytes=budget, adaptive=True), dev,
                  fixed_width=1)):
        assert H.shape == (code.p, code.N)


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
