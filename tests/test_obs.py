"""Observability layer: metrics registry semantics, span tracer export,
ObsSession lifecycle, and the JSONL report renderer."""
import json
import logging

import numpy as np
import pytest

from repro.obs import (
    ObsSession,
    enable_default_logging,
    metrics,
    report,
    trace,
)


# ------------------------------------------------------------- registry


def test_counter_gauge_info_semantics():
    reg = metrics.MetricsRegistry()
    c = reg.counter("x.total", driver="sync")
    c.inc()
    c.inc(2.5)
    assert c.value == pytest.approx(3.5)
    with pytest.raises(ValueError):
        c.inc(-1.0)
    g = reg.gauge("x.gauge")
    assert not g.updated
    g.set(1.0)
    g.set(2.0)                          # last write wins
    assert g.value == 2.0 and g.updated
    i = reg.info("x.info", {"a": 1})
    reg.info("x.info", {"b": 2})        # last write wins
    assert i.info == {"b": 2}


def test_labels_make_distinct_series_and_render_sorted():
    reg = metrics.MetricsRegistry()
    reg.counter("steps", driver="sync").inc()
    reg.counter("steps", driver="pipeline").inc(2)
    assert reg.counter("steps", driver="sync").value == 1.0
    snap = reg.snapshot()
    assert snap["steps{driver=sync}"]["value"] == 1.0
    assert snap["steps{driver=pipeline}"]["value"] == 2.0
    # label values are stringified; keys render sorted
    reg.gauge("g", b=2, a=1)
    assert "g{a=1,b=2}" in reg.snapshot()


def test_kind_mismatch_rejected():
    reg = metrics.MetricsRegistry()
    reg.counter("m")
    with pytest.raises(ValueError, match="already"):
        reg.gauge("m")
    with pytest.raises(ValueError, match="already"):
        reg.histogram("m", bins=(0, 1))


def test_histogram_buckets_and_bin_contract():
    reg = metrics.MetricsRegistry()
    h = reg.histogram("rounds", bins=(0, 1, 2, 4))
    # edges E define E+1 buckets: (-inf,0], (0,1], (1,2], (2,4], (4,inf)
    h.observe_many([0, 1, 2, 3, 5])
    assert h.counts.tolist() == [1, 1, 1, 1, 1]
    assert h.count == 5 and h.total == pytest.approx(11.0)
    assert h.min == 0.0 and h.max == 5.0
    assert h.mean == pytest.approx(11.0 / 5)
    # re-fetch without bins returns the same series; different bins reject
    assert reg.histogram("rounds") is h
    with pytest.raises(ValueError, match="different bin edges"):
        reg.histogram("rounds", bins=(0, 1))
    with pytest.raises(ValueError, match="needs bins"):
        reg.histogram("fresh")
    # 'name' stays usable as a label key (positional-only metric name)
    reg.counter("c", name="x").inc()


def test_recording_scopes_and_restores():
    assert metrics.active() is None
    with metrics.recording() as outer:
        assert metrics.active() is outer
        inner = metrics.MetricsRegistry()
        with metrics.recording(inner):
            assert metrics.active() is inner
        assert metrics.active() is outer
    assert metrics.active() is None


def test_export_jsonl_round_trips(tmp_path):
    reg = metrics.MetricsRegistry()
    reg.counter("a.total").inc(3)
    reg.histogram("a.h", bins=metrics.ROUND_BINS, driver="sync").observe(4)
    path = reg.export_jsonl(tmp_path / "obs" / "m.jsonl")
    meta, entries = report.load_jsonl(path)
    assert meta["schema"] == 1 and meta["n_metrics"] == 2
    by_kind = {e["kind"] for e in entries}
    assert by_kind == {"counter", "histogram"}
    h = next(e for e in entries if e["kind"] == "histogram")
    assert h["labels"] == {"driver": "sync"}
    assert sum(h["counts"]) == 1 and h["sum"] == 4.0
    # every line is plain JSON (the export IS the wire format)
    for line in path.read_text().splitlines():
        json.loads(line)


# ------------------------------------------------------ compile counter


def _fresh_program():
    """A jitted function JAX has never seen: its first call compiles."""
    import jax

    def program(x):
        return x * 3.0 + 1.0

    return jax.jit(program)


def test_compile_under_recording_is_counted():
    import jax.numpy as jnp

    x = jnp.ones(4)
    with metrics.recording() as reg:
        _fresh_program()(x).block_until_ready()
    assert reg.counter("jax.compiles").value >= 1
    stages = {m["labels"]["stage"]: m["value"]
              for m in reg.snapshot().values()
              if m["name"] == "jax.compile_seconds"}
    assert {"trace", "lower", "backend"} <= set(stages)
    assert all(v >= 0.0 for v in stages.values())
    assert stages["backend"] > 0.0


def test_compile_with_no_registry_adds_nothing():
    import jax.numpy as jnp

    x = jnp.ones(4)
    with metrics.recording() as reg:     # the listener is registered
        pass
    idle = metrics.MetricsRegistry()
    assert metrics.active() is None
    _fresh_program()(x).block_until_ready()
    assert len(reg) == 0 and len(idle) == 0


def test_compile_listener_is_registered_once(monkeypatch):
    from jax import monitoring

    added = []
    monkeypatch.setattr(monitoring, "register_event_duration_secs_listener",
                        added.append)
    monkeypatch.setattr(metrics, "_listening", False)
    try:
        for _ in range(3):
            metrics.enable()
        with metrics.recording():
            pass
    finally:
        metrics.disable()
    assert added == [metrics._on_jax_duration]


def test_nested_compile_events_count_each_second_once(monkeypatch):
    monkeypatch.setattr(metrics, "_compile_cover", [])
    with metrics.recording() as reg:
        # an inner trace of 0.2 s ends inside an outer one of 0.5 s
        metrics._on_jax_duration(
            "/jax/core/compile/jaxpr_trace_duration", 0.2)
        metrics._on_jax_duration(
            "/jax/core/compile/jaxpr_trace_duration", 0.5)
        metrics._on_jax_duration(
            "/jax/core/compile/backend_compile_duration", 1e-9)
        metrics._on_jax_duration("/jax/other/event", 7.0)
    assert reg.counter("jax.compile_seconds",
                       stage="trace").value == pytest.approx(0.5, abs=1e-3)
    assert reg.counter("jax.compiles").value == 1
    assert reg.get("jax.compile_seconds", stage="lower") is None


# --------------------------------------------------------------- tracer


def test_tracer_spans_lanes_and_metric_feed(tmp_path):
    tr = trace.Tracer()
    with metrics.recording() as reg, trace.tracing(tr):
        with trace.span("master/decode", lane="master", step=3):
            pass
        tr.complete("pipeline/step", trace.now_us() - 50, 50,
                    lane="pipeline", step=0)
    assert [e["name"] for e in tr.events] == ["master/decode",
                                              "pipeline/step"]
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in tr.events)
    # distinct lanes -> distinct synthetic tids
    assert tr.events[0]["tid"] != tr.events[1]["tid"]
    assert tr.events[0]["args"]["step"] == 3
    # finished spans feed per-phase counters into the active registry
    assert reg.counter("trace.span_count", name="master/decode").value == 1
    assert reg.counter("trace.span_seconds",
                       name="pipeline/step").value > 0
    doc = json.loads(tr.export(tmp_path / "t.trace.json").read_text())
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert names == {"master", "pipeline"}


def test_span_is_null_context_when_tracing_off():
    assert trace.active_tracer() is None
    cm = trace.span("anything", lane="x")
    with cm:
        pass                        # shared null context: free, reusable
    assert cm is trace.span("other")


# ------------------------------------------------------------ ObsSession


def test_obs_session_exports_both_files(tmp_path, capsys):
    path = tmp_path / "run.jsonl"
    session = ObsSession.start(path)
    reg, tr = metrics.active(), trace.active_tracer()
    assert reg is not None and tr is not None
    reg.counter("x").inc()
    with trace.span("phase/a"):
        pass
    session.finish()
    session.finish()                      # idempotent
    assert metrics.active() is None and trace.active_tracer() is None
    meta, entries = report.load_jsonl(path)
    assert any(e["name"] == "x" for e in entries)
    doc = json.loads(path.with_suffix(".trace.json").read_text())
    assert any(e["name"] == "phase/a" for e in doc["traceEvents"])
    # status line goes to stderr — stdout stays pure for --json surfaces
    out = capsys.readouterr()
    assert "[obs]" in out.err and out.out == ""


def test_null_session_is_inert():
    session = ObsSession.start(None)
    assert metrics.active() is None and trace.active_tracer() is None
    session.finish()                      # no-op, no files


def test_report_renders_summary(tmp_path, capsys):
    path = tmp_path / "run.jsonl"
    session = ObsSession.start(path)
    reg = metrics.active()
    reg.counter("distributed.steps_total", driver="pipeline").inc(4)
    reg.histogram("distributed.step.rounds", bins=metrics.ROUND_BINS,
                  driver="pipeline").observe_many([3, 4, 5, 6])
    reg.histogram("distributed.straggler.tracking_error",
                  bins=metrics.FRACTION_BINS,
                  driver="pipeline").observe_many([0.1, 0.2])
    reg.info("engine.dispatch", {"resolved_backend": "sparse"},
             backend="auto", resolved="sparse", N=128)
    with trace.span("master/decode"):
        pass
    session.finish(quiet=True)
    assert report.main([str(path),
                        "--trace", str(path.with_suffix(".trace.json"))]) == 0
    out = capsys.readouterr().out
    assert "distributed.steps_total" in out or "steps" in out
    assert "master/decode" in out
    assert "tracking" in out


# ------------------------------------------- report on partial inputs


def test_report_metrics_only_no_trace_file(tmp_path, capsys):
    # The common partial export: a metrics JSONL with no .trace.json
    # sibling (no tracer was active, or the file was not shipped).  The
    # report must render from the JSONL alone — --trace is opt-in.
    path = tmp_path / "run.jsonl"
    with metrics.recording() as reg:
        reg.counter("distributed.steps_total", driver="sync").inc(2)
        reg.export_jsonl(path)
    assert not path.with_suffix(".trace.json").exists()
    assert report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "steps" in out


def test_report_empty_run(tmp_path, capsys):
    # A registry that recorded nothing still exports a meta header; the
    # report must render the run section, not crash on zero entries.
    path = tmp_path / "empty.jsonl"
    metrics.MetricsRegistry().export_jsonl(path)
    assert report.main([str(path)]) == 0
    assert "metrics: 0" in capsys.readouterr().out
    # ... and a completely empty file (no meta line either) works too
    bare = tmp_path / "bare.jsonl"
    bare.write_text("")
    assert report.main([str(bare)]) == 0
    assert "metrics: 0" in capsys.readouterr().out


def test_report_unknown_metric_names(tmp_path, capsys):
    # Names no report section knows about (user-defined instrumentation,
    # or a newer exporter than this report) must not crash the renderer —
    # they count toward the run total and are otherwise skipped.
    path = tmp_path / "unknown.jsonl"
    with metrics.recording() as reg:
        reg.counter("sched_cache.hit").inc(7)
        reg.gauge("sched_cache.hit_rate").set(0.875)
        reg.histogram("my.custom.latency",
                      bins=metrics.LATENCY_BINS).observe(0.25)
        reg.info("user.build", {"commit": "abc123"})
        reg.export_jsonl(path)
    assert report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "metrics: 4" in out


def test_report_zero_count_histogram_renders():
    # A histogram that was created but never observed has count 0; the
    # known-name sections must render its mean as nan, not divide away.
    reg = metrics.MetricsRegistry()
    reg.histogram("distributed.step.rounds", bins=metrics.ROUND_BINS,
                  driver="sync")
    entries = [v for v in reg.snapshot().values()]
    text = report.summarize({}, entries)
    assert "rounds_used" in text and "nan" in text


def test_enable_default_logging_idempotent():
    logger = logging.getLogger("repro")
    before_handlers = list(logger.handlers)
    before_level = logger.level
    try:
        assert enable_default_logging() is logger
        n = len(logger.handlers)
        assert n == len(before_handlers) + 1
        enable_default_logging()              # idempotent: no second handler
        assert len(logger.handlers) == n
        assert logger.level == logging.DEBUG
    finally:
        logger.handlers = before_handlers
        logger.setLevel(before_level)


# ------------------------------------- engine dispatch discoverability


def test_engine_debug_info_surfaces_in_snapshot():
    from repro.core import make_regular_ldpc
    from repro.core.engine import CodedComputeEngine

    code = make_regular_ldpc(64, l=3, r=6, seed=0)
    with metrics.recording() as reg:
        CodedComputeEngine(code, backend="sparse", decode_iters=4)
    snap = reg.snapshot()
    infos = [v for v in snap.values() if v["name"] == "engine.dispatch"]
    assert len(infos) == 1
    assert infos[0]["info"]["resolved_backend"] == "sparse"
    resolves = [v for v in snap.values()
                if v["name"] == "decoder.resolve_total"]
    assert resolves and resolves[0]["labels"]["resolved"] == "sparse"
