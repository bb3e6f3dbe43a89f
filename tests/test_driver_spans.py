"""The sync drivers' host spans and their programs' name scopes: the
labels a profile shows for their host work and device ops."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Scheme2, make_regular_ldpc, second_moment
from repro.core.grad_agg import CodedAggregator
from repro.data import make_linear_problem
from repro.distributed import (
    DistributedCodedAggregator,
    DistributedCodedGD,
    WorkerTopology,
    make_worker_mesh,
)
from repro.obs import metrics, trace

K, W = 64, 8
SPANS = ["step/inputs", "worker/launch", "master/dispatch", "master/fetch",
         "master/decode"]          # in the order they finish
MASK = np.array([False, True] + [False] * (W - 2))


def _gd(backend="sparse", budget_mode="fixed", master_decode="single"):
    code = make_regular_ldpc(K, l=3, r=6, seed=0)
    prob = make_linear_problem(m=4 * K, k=K, seed=0)
    scheme = Scheme2.build(code, second_moment(prob.X, prob.y), lr=prob.lr,
                           decode_iters=8, decode_backend=backend)
    return DistributedCodedGD(scheme, WorkerTopology(W, code.N),
                              make_worker_mesh(), budget_mode=budget_mode,
                              master_decode=master_decode)


def _agg(backend="sparse"):
    agg = CodedAggregator.build(n_shards=K, redundancy=0.5, row_weight=4,
                                seed=0, decode_backend=backend)
    return DistributedCodedAggregator(agg, WorkerTopology(W, agg.n_workers),
                                      make_worker_mesh())


def _call(driver):
    """One call of the driver's entry point; returns its array result."""
    if isinstance(driver, DistributedCodedGD):
        return driver.step(jnp.zeros(K), MASK)[0]
    partials = jax.random.normal(jax.random.PRNGKey(0), (K, 5))
    return driver.aggregate(partials, MASK)[0]


@pytest.fixture(scope="module", params=["gd", "agg"])
def driver(request):
    d = _gd() if request.param == "gd" else _agg()
    _call(d)                                     # compile outside the tests
    return d


def test_spans_nest_once_per_call(driver):
    with trace.tracing() as tr:
        _call(driver)
    events = tr.events
    assert [e["name"] for e in events] == SPANS
    at = {e["name"]: (e["ts"], e["ts"] + e["dur"]) for e in events}
    decode = at["master/decode"]
    for child in ("master/dispatch", "master/fetch"):
        assert decode[0] <= at[child][0] <= at[child][1] <= decode[1]
    assert at["master/dispatch"][1] <= at["master/fetch"][0]
    # the earlier spans end before master/decode opens
    assert at["step/inputs"][1] <= at["worker/launch"][0] <= decode[0]


def test_untraced_call_records_nothing_and_matches(driver):
    with trace.tracing() as tr, metrics.recording() as reg:
        traced = _call(driver)
    n_events, n_metrics = len(tr.events), len(reg)
    assert trace.active_tracer() is None and metrics.active() is None
    plain = _call(driver)
    assert len(tr.events) == n_events and len(reg) == n_metrics
    assert np.array_equal(np.asarray(traced), np.asarray(plain))


def _scopes(lowered) -> set:
    """Name-scope paths in a compiled program's HLO metadata (what a
    profile's ops carry): each ``op_name`` without its program, its
    primitive and the ``jit(...)``-style components of transformations,
    cut at the first loop."""
    out = set()
    for name in re.findall(r'op_name="([^"]+)"',
                           lowered.compile().as_text()):
        parts = [p for p in name.split("/")[1:-1]
                 if not re.match(r"^[\w.-]*\(.*\)$", p)]
        out.add("/".join(parts).split("/while")[0])
    return out


def _lower_master(d):
    if isinstance(d, DistributedCodedAggregator):
        partials = jnp.zeros((K, 5))
        z = d._worker_program(d._G_sharded, partials, jnp.asarray(MASK))
        return d._master_program.lower(z, jnp.asarray(MASK), d._decode_H)
    theta, mask = jnp.zeros(K), jnp.asarray(MASK)
    z = d._launch_workers(theta, mask)
    budget = np.asarray([8], np.int32)
    if d.master_decode == "sharded":
        return d._master_program.lower(*d._sharded_tables, z, mask, theta,
                                       budget)
    return d._master_program.lower(z, mask, theta, budget, d._decode_H)


@pytest.mark.parametrize("build, want", [
    (lambda: _gd(), {"decode", "update"}),
    (lambda: _gd(budget_mode="telemetry"), {"decode", "update"}),
    (lambda: _gd(master_decode="sharded"), {"decode", "update"}),
    (lambda: _gd("pallas"), {"decode", "update",
                             "decode/kernel/decode_fused"}),
    (lambda: _agg(), {"decode", "sum"}),
    (lambda: _agg("pallas"), {"decode", "sum",
                              "decode/kernel/decode_fused"}),
], ids=["gd", "gd-telemetry", "gd-sharded", "gd-pallas", "agg",
        "agg-pallas"])
def test_master_programs_carry_the_scopes(build, want):
    scopes = _scopes(_lower_master(build()))
    assert want <= scopes, sorted(scopes)
