"""From a profiler trace to per-layer numbers.

The JAX profiler writes an ``.xplane.pb``; :func:`from_xplane` reads it
(``jax.profiler.ProfileData``) into a :class:`Trace` of plain intervals:

* ``ops``: device operations (the ``XLA Ops`` line of each TPU plane), each
  with the XLA module it ran in;
* ``modules``: XLA module executions (the ``XLA Modules`` line), one per
  launch of a jitted program, named after the jitted function
  (``jit_master_program(17)`` -> ``master_program``);
* ``spans``: host spans (``jax.profiler.TraceAnnotation`` events), the
  program's own (``worker/launch``, ``master/decode``, ...) and the
  harness's ``bench/call`` around each timed call.

:func:`reduce` turns a :class:`Trace` into a :class:`Summary` over the
window the ``bench/call`` spans cover: device busy time as the union of op
intervals (overlaps counted once), device time per module, and the idle
time, each piece of it labelled by the innermost host span open over it.
All times are nanoseconds on the trace's clock until the summary, which
gives seconds.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from pathlib import Path

__all__ = ["Interval", "Trace", "Summary", "from_xplane", "reduce",
           "union", "module_name", "op_name", "CALL_SPAN"]

CALL_SPAN = "bench/call"
# host span names: the program's obs spans and the harness's ("layer/what")
_SPAN_RE = re.compile(r"^[a-z_]+/[a-z_]+$")
_MODULE_RE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?(?:\.\d+)?$")


@dataclasses.dataclass(frozen=True)
class Interval:
    start: float      # ns
    end: float        # ns
    name: str
    module: str = ""  # ops only: the module they ran in


@dataclasses.dataclass
class Trace:
    ops: list
    modules: list
    spans: list


@dataclasses.dataclass
class Summary:
    window_s: float                # first bench/call start .. last end
    busy_s: float                  # union of device op intervals in it
    n_calls: int                   # bench/call spans in the window
    module_s: dict                 # module name -> device seconds
    top_ops: list                  # [[module/op, seconds]] by time, <= 10
    idle_by_label: list            # [[host span, idle seconds]], <= 10

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_ms_per_call(self, *names: str) -> float | None:
        """Device milliseconds per call of the named modules (None when
        the trace holds none of them)."""
        found = [self.module_s[n] for n in names if n in self.module_s]
        if not found or not self.n_calls:
            return None
        return 1e3 * sum(found) / self.n_calls


def module_name(raw: str) -> str:
    """``jit_master_program(17)`` -> ``master_program``."""
    return _MODULE_RE.match(raw).group(1)


def op_name(raw: str) -> str:
    """``%fusion.2 = f32[8]{0} fusion(...)`` -> ``fusion.2``."""
    return raw.split(" = ", 1)[0].lstrip("%")


def union(intervals) -> list:
    """Sorted, disjoint ``(start, end)`` cover of ``intervals``."""
    out: list = []
    for s, e in sorted((iv.start, iv.end) for iv in intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo: float, hi: float) -> list:
    out = []
    for iv in intervals:
        s, e = max(iv.start, lo), min(iv.end, hi)
        if e > s:
            out.append(dataclasses.replace(iv, start=s, end=e))
    return out


def _label(t: float, spans) -> str:
    """The innermost (shortest) host span open at ``t``."""
    best = None
    for sp in spans:
        if sp.start <= t < sp.end and (
                best is None or sp.end - sp.start < best.end - best.start):
            best = sp
    return best.name if best is not None else "(no span)"


def _label_gap(a: float, b: float, spans, out: dict) -> None:
    """Split the idle gap ``[a, b)`` where host spans open or close, and
    add each piece to the innermost span open over it."""
    inside = [sp for sp in spans if sp.end > a and sp.start < b]
    cuts = sorted({a, b} | {t for sp in inside for t in (sp.start, sp.end)
                           if a < t < b})
    for x, y in zip(cuts, cuts[1:]):
        out[_label(x, inside)] += y - x


def reduce(trace: Trace, call_span: str = CALL_SPAN) -> Summary:
    """Summarize the window the ``call_span`` host spans cover."""
    calls = [sp for sp in trace.spans if sp.name == call_span]
    if not calls:
        raise ValueError(f"trace holds no {call_span!r} span")
    lo = min(sp.start for sp in calls)
    hi = max(sp.end for sp in calls)
    ops = _clip(trace.ops, lo, hi)
    busy = union(ops)
    busy_ns = sum(e - s for s, e in busy)

    module_ns: dict = defaultdict(float)
    for iv in _clip(trace.modules, lo, hi):
        module_ns[iv.name] += iv.end - iv.start
    op_ns: dict = defaultdict(float)
    for iv in ops:
        op_ns[f"{iv.module}/{iv.name}" if iv.module else iv.name] += (
            iv.end - iv.start)

    spans = [sp for sp in trace.spans if sp.end > lo and sp.start < hi]
    idle_ns: dict = defaultdict(float)
    prev = lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            _label_gap(prev, s, spans, idle_ns)
        prev = max(prev, e)

    def top(d):
        return [[k, v * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9,
                   n_calls=len(calls),
                   module_s={k: v * 1e-9 for k, v in module_ns.items()},
                   top_ops=top(op_ns), idle_by_label=top(idle_ns))


def _stat(event, key: str):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def from_xplane(path) -> Trace:
    """Read one ``.xplane.pb``: TPU planes give ops and modules, host
    planes give the spans named ``layer/what`` (the program's obs spans and
    the harness's own)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops, modules, spans = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules.extend(
                        Interval(ev.start_ns, ev.end_ns, module_name(ev.name))
                        for ev in line.events)
                elif line.name == "XLA Ops":
                    for ev in line.events:
                        mod = _stat(ev, "hlo_module")
                        ops.append(Interval(
                            ev.start_ns, ev.end_ns, op_name(ev.name),
                            module_name(str(mod)) if mod else ""))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Interval(ev.start_ns, ev.end_ns, ev.name)
                             for ev in line.events
                             if _SPAN_RE.match(ev.name))
    _attach_modules(ops, modules)
    return Trace(ops=ops, modules=modules, spans=spans)


def _attach_modules(ops: list, modules: list) -> None:
    """Name the module of ops whose event carries none: the module
    execution that contains the op's start."""
    if not modules or all(op.module for op in ops):
        return
    mods = sorted(modules, key=lambda m: m.start)
    starts = [m.start for m in mods]
    for i, op in enumerate(ops):
        if op.module:
            continue
        j = bisect.bisect_right(starts, op.start) - 1
        if j >= 0 and op.start < mods[j].end:
            ops[i] = dataclasses.replace(op, module=mods[j].name)


def find_xplane(log_dir) -> Path:
    found = sorted(Path(log_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]
