"""Host-side span tracer emitting Chrome/Perfetto ``trace_event`` JSON.

Spans bracket HOST phases of the runtime — worker launch, master decode,
fold dispatch, serving waves, AOT lower/compile — and export as complete
(``"ph": "X"``) events that chrome://tracing and ui.perfetto.dev open
directly.  Two recording styles:

* :func:`span` — a context manager around synchronous host work::

      with span("master/decode", lane="master", step=t):
          ...

* :meth:`Tracer.complete` — async-safe stamping for the pipelined driver:
  the dispatch timestamp is taken when work is enqueued and the complete
  event is emitted later at queue-pull time, where the host is ALREADY
  blocking on fetched values.  No ``block_until_ready`` is ever added to
  measure a span; what is traced is host-observed dispatch→drain latency,
  not device execution.

Like :mod:`repro.obs.metrics`, tracing is off-by-default free: with no
tracer enabled, :func:`span` returns a shared null context manager and the
hot-path cost is one module-attribute read.  When a metrics registry is
also active, each finished span feeds ``trace.span_seconds`` /
``trace.span_count{name=...}`` counters so :mod:`repro.obs.report` can
render a per-phase time breakdown from the JSONL alone.

Which spans reach a device profile: ``Tracer(jax_annotations=True)``
wraps each synchronous :func:`span` in ``jax.profiler.TraceAnnotation``,
so it lands on the profiler's host plane on the same clock as the device
ops (``bench/trace_reduce.py`` labels idle device time with it).
:meth:`Tracer.complete` stamps (the pipelined driver's async spans) do
not: they exist only in this tracer's own export, because the profiler's
host events are offsets from the start of its trace, not this module's
``perf_counter`` clock.  jax is imported lazily and only in that mode.
"""
from __future__ import annotations

import contextlib
import json
import threading
import time
from pathlib import Path

from . import metrics as _metrics

__all__ = [
    "Tracer", "enable_tracing", "disable_tracing", "active_tracer",
    "tracing", "span", "now_us",
]


def now_us() -> int:
    """Monotonic microsecond clock shared by all span timestamps."""
    return time.perf_counter_ns() // 1000


_NULL_CM = contextlib.nullcontext()


class Tracer:
    """Collects ``trace_event`` dicts; :meth:`export` writes the Chrome
    JSON object format (``{"traceEvents": [...]}``).

    Lanes ("worker", "master", "serving", …) map to synthetic thread ids
    so phases stack in separate swimlanes in the viewer; thread-name
    metadata events are emitted at export.
    """

    def __init__(self, *, jax_annotations: bool = False, pid: int = 1):
        self.events: list[dict] = []
        self.pid = pid
        self.jax_annotations = jax_annotations
        self._lanes: dict[str, int] = {}
        self._lock = threading.Lock()
        self._annot = None
        if jax_annotations:
            from jax.profiler import TraceAnnotation  # lazy: CPU CI safe
            self._annot = TraceAnnotation

    def lane(self, name: str) -> int:
        tid = self._lanes.get(name)
        if tid is None:
            with self._lock:
                tid = self._lanes.setdefault(name, len(self._lanes) + 1)
        return tid

    def _feed_metrics(self, name: str, dur_us: float) -> None:
        reg = _metrics.active()
        if reg is not None:
            reg.counter("trace.span_seconds", name=name).inc(dur_us * 1e-6)
            reg.counter("trace.span_count", name=name).inc()

    @contextlib.contextmanager
    def span(self, name: str, lane: str = "main", **args):
        """Time a synchronous host block as one complete event."""
        annot = self._annot(name) if self._annot is not None else _NULL_CM
        t0 = now_us()
        try:
            with annot:
                yield self
        finally:
            self.complete(name, t0, now_us() - t0, lane=lane, **args)

    def complete(self, name: str, ts_us: int, dur_us: int,
                 lane: str = "main", **args) -> None:
        """Record a finished span from externally-captured timestamps —
        the async stamping entry point (zero synchronization here)."""
        ev = {"ph": "X", "name": name, "pid": self.pid,
              "tid": self.lane(lane), "ts": int(ts_us),
              "dur": max(int(dur_us), 0)}
        if args:
            ev["args"] = {k: _jsonable(v) for k, v in args.items()}
        self.events.append(ev)
        self._feed_metrics(name, dur_us)

    def export(self, path) -> Path:
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        meta = [{"ph": "M", "name": "process_name", "pid": self.pid,
                 "tid": 0, "args": {"name": "repro"}}]
        for lane_name, tid in sorted(self._lanes.items(), key=lambda kv: kv[1]):
            meta.append({"ph": "M", "name": "thread_name", "pid": self.pid,
                         "tid": tid, "args": {"name": lane_name}})
        doc = {"traceEvents": meta + self.events, "displayTimeUnit": "ms"}
        path.write_text(json.dumps(doc))
        return path


def _jsonable(v):
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    try:
        return v.item()  # numpy / 0-d jax host scalars
    except AttributeError:
        return str(v)


# ----------------------------------------------------- process-local switch

_active: Tracer | None = None


def enable_tracing(tracer: Tracer | None = None, **kw) -> Tracer:
    """Install ``tracer`` (or ``Tracer(**kw)``) as the process-local tracer."""
    global _active
    _active = tracer if tracer is not None else Tracer(**kw)
    return _active


def disable_tracing() -> Tracer | None:
    global _active
    tr, _active = _active, None
    return tr


def active_tracer() -> Tracer | None:
    return _active


@contextlib.contextmanager
def tracing(tracer: Tracer | None = None, **kw):
    """Scope a tracer around a block, restoring the previous one after."""
    global _active
    prev = _active
    tr = tracer if tracer is not None else Tracer(**kw)
    _active = tr
    try:
        yield tr
    finally:
        _active = prev


def span(name: str, lane: str = "main", **args):
    """Module-level span: delegates to the active tracer, or returns a
    shared null context when tracing is off (the free path)."""
    tr = _active
    if tr is None:
        return _NULL_CM
    return tr.span(name, lane=lane, **args)
