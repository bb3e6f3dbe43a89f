"""The readers of the sync drivers' host spans on small hand-made traces,
and what a run leaves enabled."""
import io
import json
import time

import pytest

from bench import harness
from bench.trace_reduce import Interval, Trace, reduce

NEW = ("host.dispatch_idle_ms", "host.fetch_idle_ms")


def _trace(spans=True, ops=True):
    """Two calls of 30 ns.  In each: idle 0..3 under ``step/inputs``,
    3..5 under ``worker/launch``; the worker runs 5..8 while
    ``master/dispatch`` (4..9) enqueues, so 8..9 is idle under it; the
    master runs 9..20 and the host pulls its scalars in ``master/fetch``
    (9..23), idle 20..23; 23..30 is idle under ``bench/call`` alone."""
    device, host = [], []
    for t in (0, 30):
        device += [Interval(t + 5, t + 8, "fusion.1", "worker_program"),
                   Interval(t + 9, t + 20, "decode_fused.1",
                            "master_program")]
        host += [Interval(t, t + 30, "bench/call"),
                 Interval(t + 3, t + 5, "worker/launch"),
                 Interval(t + 4, t + 23, "master/decode")]
        if spans:
            host += [Interval(t, t + 3, "step/inputs"),
                     Interval(t + 4, t + 9, "master/dispatch"),
                     Interval(t + 9, t + 23, "master/fetch")]
    modules = [Interval(iv.start, iv.end, iv.module) for iv in device]
    return Trace(ops=device if ops else [], modules=modules, spans=host)


def _read(name, trace):
    summary = reduce(trace)
    return harness.load_part("metrics", name).read(
        harness.Reading(summary, {}, {}))


def test_span_labels_split_the_idle():
    idle = dict(reduce(_trace()).idle_by_label)
    assert idle["step/inputs"] == pytest.approx(6e-9)
    # master/dispatch is open over 4..9, but worker/launch, the shorter
    # span, over 4..5
    assert idle["worker/launch"] == pytest.approx(4e-9)
    assert idle["master/dispatch"] == pytest.approx(2e-9)
    assert idle["master/fetch"] == pytest.approx(6e-9)
    assert idle["bench/call"] == pytest.approx(14e-9)
    assert "master/decode" not in idle
    assert sum(idle.values()) == pytest.approx(60e-9 - 28e-9)


@pytest.mark.parametrize("name, ms", [
    ("host.dispatch_idle_ms", 1e-6 * (6 + 4 + 2) / 2),
    ("host.fetch_idle_ms", 1e-6 * 6 / 2),
])
def test_reader_gives_idle_per_call(name, ms):
    assert _read(name, _trace()) == pytest.approx(ms)


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("spans, ops", [(False, True), (True, False)],
                         ids=["parent-spans", "no-device-ops"])
def test_reader_is_silent_without_its_input(name, spans, ops):
    assert _read(name, _trace(spans=spans, ops=ops)) is None


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_runs_leave_no_registry_or_tracer(trace, tmp_path):
    from repro.obs import metrics, trace as obs_trace

    out, err = io.StringIO(), io.StringIO()
    rc = harness.run("gradagg-k20-b25m.sync-bern0.1", 7, 0.2, trace,
                     t_start=time.perf_counter(), require_chip=False,
                     overrides={"dim": 4096},
                     cell_kw={"cache_dir": tmp_path},
                     peaks=harness.peaks_for("TPU v5 lite"), out=out,
                     err=err)
    assert rc == 0, err.getvalue()
    assert metrics.active() is None and obs_trace.active_tracer() is None
    result = json.loads(out.getvalue().strip().splitlines()[-1])
    assert result["correct"]
    # the CPU's profile has no device ops: the new readers stay silent
    assert not set(NEW) & set(result["metrics"])
