"""Process-local metrics registry: counters, gauges, fixed-bin histograms.

The adaptivity stack (EMA straggler telemetry → decode budgets →
wait-for/staleness policy → per-slot adaptive kernels) makes per-step
decisions all over the runtime; this module gives every layer one place to
record them.  Deliberately dependency-free (numpy only — no jax import, no
exporter daemons): a :class:`MetricsRegistry` is a plain in-process object
holding Prometheus-shaped metrics keyed on ``(name, labels)``, with
``snapshot()`` dicts for tests and a JSONL export for the ``--obs-out``
CLI surfaces and :mod:`repro.obs.report`.

Instrumentation sites follow one pattern so that observability is
OFF-BY-DEFAULT FREE::

    reg = metrics.active()
    if reg is not None:
        reg.counter("distributed.steps_total", driver="sync").inc()

With no registry enabled the cost is a module-attribute read and a None
check; nothing is allocated, nothing is traced — instrumented jitted
programs are bit-identical to uninstrumented ones because recording only
ever touches ALREADY-FETCHED host values.

Activation is process-local: :func:`enable` installs a registry,
:func:`disable` removes it, :func:`recording` scopes one around a block
(restoring whatever was active before).  Histograms use fixed bin edges
fixed at creation (numpy ``searchsorted`` buckets); the shared edge
constants below keep the same quantity comparable across layers.

Compile time: the first time a registry is enabled, one JAX monitoring
duration listener is registered (once per process, and only where jax
imports).  While a registry is active it adds JAX's compile events to
``jax.compile_seconds{stage=trace|lower|backend}`` (a backend compile
includes a persistent-cache read) and counts backend compiles in
``jax.compiles``; with none active it records nothing.  Nested events
(an inner jit traced inside an outer one) count each second once, under
the innermost stage.
"""
from __future__ import annotations

import contextlib
import json
import threading
import time
from pathlib import Path

import numpy as np

__all__ = [
    "Counter", "Gauge", "Histogram", "Info", "MetricsRegistry",
    "enable", "disable", "active", "recording",
    "ROUND_BINS", "FRACTION_BINS", "COUNT_BINS", "LAG_BINS", "LATENCY_BINS",
]

# Shared histogram edges so the same quantity buckets identically across
# layers: decode rounds / budgets / headroom; fractions in [0, 1] (rates,
# occupancy, tracking error); small counts (unresolved coords, wait-for,
# launches); arrival lags in step units; host latencies in seconds.
ROUND_BINS = (0, 1, 2, 4, 8, 16, 32, 64, 128)
FRACTION_BINS = tuple(np.round(np.linspace(0.0, 1.0, 11), 2))
COUNT_BINS = (0, 1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)
LAG_BINS = (0, 1, 2, 3, 4, 6, 8, 12, 16)
LATENCY_BINS = tuple(1e-6 * 4.0 ** i for i in range(13))  # 1 µs … ~17 s


class Counter:
    """Monotonically increasing float total."""

    kind = "counter"

    def __init__(self, name: str, labels: dict):
        self.name, self.labels = name, labels
        self.value = 0.0

    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError(f"counters only go up; got {amount}")
        self.value += float(amount)

    def payload(self) -> dict:
        return {"value": self.value}


class Gauge:
    """Last-write-wins scalar."""

    kind = "gauge"

    def __init__(self, name: str, labels: dict):
        self.name, self.labels = name, labels
        self.value = 0.0
        self.updated = False

    def set(self, value: float) -> None:
        self.value = float(value)
        self.updated = True

    def payload(self) -> dict:
        return {"value": self.value, "updated": self.updated}


class Histogram:
    """Fixed-bin histogram: ``E`` edges define ``E+1`` buckets
    ``(-inf, e0], (e0, e1], …, (e_{E-1}, inf)`` via ``searchsorted``."""

    kind = "histogram"

    def __init__(self, name: str, labels: dict, bins):
        edges = np.asarray(bins, float)
        if edges.ndim != 1 or edges.size < 2:
            raise ValueError(f"histogram {name!r} needs >= 2 bin edges")
        if not (np.diff(edges) > 0).all():
            raise ValueError(f"histogram {name!r} bin edges must increase")
        self.name, self.labels = name, labels
        self.bins = edges
        self.counts = np.zeros(edges.size + 1, np.int64)
        self.total = 0.0
        self.count = 0
        self.min = float("inf")
        self.max = float("-inf")

    def observe(self, value: float) -> None:
        self.observe_many(np.asarray([value], float))

    def observe_many(self, values) -> None:
        v = np.asarray(values, float).ravel()
        if v.size == 0:
            return
        idx = np.searchsorted(self.bins, v, side="left")
        np.add.at(self.counts, idx, 1)
        self.total += float(v.sum())
        self.count += int(v.size)
        self.min = min(self.min, float(v.min()))
        self.max = max(self.max, float(v.max()))

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else float("nan")

    def payload(self) -> dict:
        return {
            "bins": [float(e) for e in self.bins],
            "counts": [int(c) for c in self.counts],
            "sum": self.total, "count": self.count,
            "min": self.min if self.count else None,
            "max": self.max if self.count else None,
        }


class Info:
    """A structured one-shot fact (e.g. an engine's resolved dispatch, an
    estimator's :meth:`snapshot`), last write wins."""

    kind = "info"

    def __init__(self, name: str, labels: dict):
        self.name, self.labels = name, labels
        self.info: dict = {}

    def set(self, mapping: dict) -> None:
        self.info = dict(mapping)

    def payload(self) -> dict:
        return {"info": self.info}


def _render(name: str, labels: dict) -> str:
    if not labels:
        return name
    inner = ",".join(f"{k}={labels[k]}" for k in sorted(labels))
    return f"{name}{{{inner}}}"


class MetricsRegistry:
    """Get-or-create metric store keyed on ``(name, frozen labels)``.

    Label values are stringified into the key (and the rendered name), so
    ``histogram("x", driver="sync")`` and ``histogram("x",
    driver="pipeline")`` are distinct series of one metric family.
    Thread-safe creation; individual metric updates are plain Python ops
    (the driver loops are single-threaded hosts).
    """

    def __init__(self):
        self._metrics: dict[tuple, object] = {}
        self._lock = threading.Lock()

    def _get(self, cls, name: str, labels: dict, **kw):
        labels = {k: str(v) for k, v in labels.items()}
        key = (name, tuple(sorted(labels.items())))
        m = self._metrics.get(key)
        if m is None:
            with self._lock:
                m = self._metrics.get(key)
                if m is None:
                    m = cls(name, labels, **kw)
                    self._metrics[key] = m
        if not isinstance(m, cls):
            raise ValueError(f"metric {_render(name, labels)!r} already "
                             f"registered as a {m.kind}, not a {cls.kind}")
        return m

    def counter(self, name: str, /, **labels) -> Counter:
        return self._get(Counter, name, labels)

    def gauge(self, name: str, /, **labels) -> Gauge:
        return self._get(Gauge, name, labels)

    def histogram(self, name: str, /, bins=None, **labels) -> Histogram:
        labels_s = {k: str(v) for k, v in labels.items()}
        key = (name, tuple(sorted(labels_s.items())))
        existing = self._metrics.get(key)
        if existing is not None:
            if not isinstance(existing, Histogram):
                raise ValueError(
                    f"metric {_render(name, labels_s)!r} already registered "
                    f"as a {existing.kind}, not a histogram")
            if bins is not None and not np.array_equal(
                    existing.bins, np.asarray(bins, float)):
                raise ValueError(f"histogram {_render(name, labels_s)!r} "
                                 "re-registered with different bin edges")
            return existing
        if bins is None:
            raise ValueError(f"histogram {_render(name, labels_s)!r} needs "
                             "bins= at first registration")
        return self._get(Histogram, name, labels, bins=bins)

    def info(self, name: str, /, mapping: dict | None = None, **labels) -> Info:
        m = self._get(Info, name, labels)
        if mapping is not None:
            m.set(mapping)
        return m

    def get(self, name: str, /, **labels):
        """Existing metric or None (never creates)."""
        labels = {k: str(v) for k, v in labels.items()}
        return self._metrics.get((name, tuple(sorted(labels.items()))))

    def __len__(self) -> int:
        return len(self._metrics)

    def snapshot(self) -> dict[str, dict]:
        """``{rendered_name: {"kind", "name", "labels", **payload}}`` —
        plain JSON-ready dicts, fully decoupled from the live metrics."""
        out = {}
        for (name, _), m in sorted(self._metrics.items(),
                                   key=lambda kv: _render(kv[0][0],
                                                          dict(kv[0][1]))):
            out[_render(name, m.labels)] = {
                "kind": m.kind, "name": name, "labels": dict(m.labels),
                **m.payload(),
            }
        return out

    def export_jsonl(self, path) -> Path:
        """One JSON object per line: a ``meta`` header, then every metric
        (the format :mod:`repro.obs.report` consumes)."""
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        lines = [json.dumps({"kind": "meta", "schema": 1,
                             "exported_unix": time.time(),
                             "n_metrics": len(self._metrics)})]
        for entry in self.snapshot().values():
            lines.append(json.dumps(entry))
        path.write_text("\n".join(lines) + "\n")
        return path


# ------------------------------------------------------- JAX compile events

COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_listening = False
# (start, end) perf_counter intervals of the compile events already
# counted, disjoint and sorted: a nested event adds only its uncovered part
_compile_cover: list = []


def _on_jax_duration(event: str, duration: float, **_) -> None:
    stage = COMPILE_STAGES.get(event)
    reg = _active
    if stage is None or reg is None:
        return
    end = time.perf_counter()
    start = end - duration
    i = len(_compile_cover)
    while i and _compile_cover[i - 1][1] > start:
        i -= 1
    inner = _compile_cover[i:]
    covered = sum(e - max(s, start) for s, e in inner)
    _compile_cover[i:] = [(min([start] + [s for s, _ in inner]), end)]
    reg.counter("jax.compile_seconds", stage=stage).inc(
        max(duration - covered, 0.0))
    if stage == "backend":
        reg.counter("jax.compiles").inc()


def _listen_for_compiles() -> None:
    """Register :func:`_on_jax_duration` with JAX, once per process."""
    global _listening
    if _listening:
        return
    try:
        from jax import monitoring
    except ImportError:
        return
    monitoring.register_event_duration_secs_listener(_on_jax_duration)
    _listening = True


# ----------------------------------------------------- process-local switch

_active: MetricsRegistry | None = None


def enable(registry: MetricsRegistry | None = None) -> MetricsRegistry:
    """Install ``registry`` (or a fresh one) as the process-local sink that
    every instrumentation site records into.  Returns it."""
    global _active
    _listen_for_compiles()
    _active = registry if registry is not None else MetricsRegistry()
    return _active


def disable() -> MetricsRegistry | None:
    """Remove the active registry (instrumentation goes back to free
    no-ops); returns the registry that was active, if any."""
    global _active
    reg, _active = _active, None
    return reg


def active() -> MetricsRegistry | None:
    """The currently-enabled registry, or None — THE hot-path check."""
    return _active


@contextlib.contextmanager
def recording(registry: MetricsRegistry | None = None):
    """Scope a registry around a block, restoring the previous one after."""
    global _active
    _listen_for_compiles()
    prev = _active
    reg = registry if registry is not None else MetricsRegistry()
    _active = reg
    try:
        yield reg
    finally:
        _active = prev
