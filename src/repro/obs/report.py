"""``python -m repro.obs.report out.jsonl`` — render a run summary.

Consumes the JSONL written by :meth:`MetricsRegistry.export_jsonl` (one
``meta`` header line, then one JSON object per metric) and prints the
questions the adaptivity stack exists to answer: how well the straggler
EMA tracked the observed erasure fraction, how much decode-budget headroom
the budget policy left, what the fold window recovered from late
stragglers, what serving admission looked like, and where host time went
per phase.  Sections whose metrics are absent are skipped silently, so the
same report runs on a sync-only, pipeline, serving, or dry-run export.

Optionally pass ``--trace run.trace.json`` to summarize a Chrome-trace
file directly (span count / total duration per name) when the metrics
JSONL was exported without an active registry feeding
``trace.span_seconds``.
"""
from __future__ import annotations

import argparse
import json
from collections import defaultdict
from pathlib import Path

__all__ = ["load_jsonl", "summarize", "main"]


def load_jsonl(path) -> tuple[dict, list[dict]]:
    """Returns ``(meta, entries)``; tolerates a missing meta header."""
    meta: dict = {}
    entries: list[dict] = []
    for line in Path(path).read_text().splitlines():
        line = line.strip()
        if not line:
            continue
        obj = json.loads(line)
        if obj.get("kind") == "meta":
            meta = obj
        else:
            entries.append(obj)
    return meta, entries


def _by_name(entries: list[dict]) -> dict[str, list[dict]]:
    out: dict[str, list[dict]] = defaultdict(list)
    for e in entries:
        out[e.get("name", "?")].append(e)
    return out


def _hist_mean(e: dict) -> float:
    return e["sum"] / e["count"] if e.get("count") else float("nan")


def _fmt(x, nd: int = 3) -> str:
    if x is None:
        return "-"
    if isinstance(x, float):
        return f"{x:.{nd}f}"
    return str(x)


def _label(e: dict) -> str:
    labels = e.get("labels") or {}
    return ",".join(f"{k}={labels[k]}" for k in sorted(labels)) or "-"


def summarize(meta: dict, entries: list[dict]) -> str:
    """Build the multi-section text report (what ``main`` prints)."""
    by = _by_name(entries)
    lines: list[str] = []
    add = lines.append

    add("== run ==")
    add(f"  metrics: {len(entries)}"
        + (f"  (exported_unix={meta['exported_unix']:.0f})"
           if "exported_unix" in meta else ""))
    for e in by.get("distributed.steps_total", []):
        add(f"  steps[{_label(e)}]: {int(e['value'])}")
    for e in by.get("serving.finished_total", []):
        add(f"  queries_finished[{_label(e)}]: {int(e['value'])}")

    if "engine.dispatch" in by or "decoder.resolve_total" in by:
        add("")
        add("== engine dispatch ==")
        for e in by.get("engine.dispatch", []):
            info = e.get("info", {})
            add(f"  [{_label(e)}] backend={info.get('backend')} -> "
                f"resolved={info.get('resolved_backend')} "
                f"seeded_mode={info.get('seeded_mode')} "
                f"vmem_est={info.get('vmem_bytes_estimate')}")
        for e in by.get("decoder.resolve_total", []):
            add(f"  resolve[{_label(e)}]: {int(e['value'])}")
        for e in by.get("decoder.layout_total", []):
            add(f"  layout[{_label(e)}]: {int(e['value'])}")

    strag = by.get("distributed.straggler.tracking_error", [])
    if strag or "distributed.straggler.observed" in by:
        add("")
        add("== straggler tracking ==")
        for e in by.get("distributed.straggler.observed", []):
            add(f"  observed_fraction[{_label(e)}]: "
                f"mean={_fmt(_hist_mean(e))} "
                f"min={_fmt(e.get('min'))} max={_fmt(e.get('max'))}")
        for e in by.get("distributed.straggler.rate_estimate", []):
            add(f"  ema_estimate[{_label(e)}]:    mean={_fmt(_hist_mean(e))}")
        for e in strag:
            add(f"  tracking_error[{_label(e)}]:  mean={_fmt(_hist_mean(e))} "
                f"max={_fmt(e.get('max'))}  (|rate_ema - observed|)")
        for e in by.get("telemetry.straggler_estimator", []):
            info = e.get("info", {})
            add(f"  estimator[{_label(e)}]: rate={_fmt(info.get('rate'))} "
                f"steps={info.get('steps')}")

    budget = by.get("distributed.step.budget", [])
    if budget or "distributed.step.rounds" in by:
        add("")
        add("== decode budget headroom ==")
        for e in by.get("distributed.step.rounds", []):
            add(f"  rounds_used[{_label(e)}]: mean={_fmt(_hist_mean(e))} "
                f"max={_fmt(e.get('max'), 0)}")
        for e in budget:
            add(f"  budget[{_label(e)}]:      mean={_fmt(_hist_mean(e))}")
        for e in by.get("distributed.step.budget_headroom", []):
            add(f"  headroom[{_label(e)}]:    mean={_fmt(_hist_mean(e))} "
                f"min={_fmt(e.get('min'), 0)}  (budget - rounds_used)")
        for e in by.get("distributed.step.unresolved", []):
            add(f"  unresolved[{_label(e)}]:  mean={_fmt(_hist_mean(e))} "
                f"max={_fmt(e.get('max'), 0)}")
        for e in by.get("distributed.wait_for", []):
            add(f"  wait_for[{_label(e)}]:    mean={_fmt(_hist_mean(e))}")

    folds = by.get("pipeline.folds_total", [])
    if folds or "pipeline.arrival_lag" in by:
        add("")
        add("== fold efficacy (async pipeline) ==")
        for e in folds:
            add(f"  folds[{_label(e)}]: {int(e['value'])}")
        for e in by.get("pipeline.fold_rounds_total", []):
            add(f"  fold_rounds[{_label(e)}]: {int(e['value'])}")
        for e in by.get("pipeline.resolved_late_total", []):
            add(f"  late_coords_resolved[{_label(e)}]: {int(e['value'])}")
        for e in by.get("pipeline.arrival_lag", []):
            add(f"  arrival_lag[{_label(e)}]: mean={_fmt(_hist_mean(e))} "
                f"max={_fmt(e.get('max'), 0)}")
        for e in by.get("pipeline.staleness_window", []):
            add(f"  staleness_window[{_label(e)}]: "
                f"mean={_fmt(_hist_mean(e))}")
        for e in by.get("pipeline.staleness_weight", []):
            add(f"  staleness_weight[{_label(e)}]: "
                f"mean={_fmt(_hist_mean(e))}")

    if "serving.admission_wait_s" in by or "serving.submitted_total" in by:
        add("")
        add("== serving ==")
        for e in by.get("serving.submitted_total", []):
            add(f"  submitted[{_label(e)}]: {int(e['value'])}")
        for e in by.get("serving.admission_wait_s", []):
            add(f"  admission_wait_s[{_label(e)}]: "
                f"mean={_fmt(_hist_mean(e), 6)} max={_fmt(e.get('max'), 6)}")
        for e in by.get("serving.slot_occupancy", []):
            add(f"  slot_occupancy[{_label(e)}]: mean={_fmt(_hist_mean(e))}")
        for e in by.get("serving.query.launches", []):
            add(f"  launches_per_query[{_label(e)}]: "
                f"mean={_fmt(_hist_mean(e))}")
        for e in by.get("serving.query.rounds", []):
            add(f"  rounds_per_query[{_label(e)}]: "
                f"mean={_fmt(_hist_mean(e))}")

    spans = by.get("trace.span_seconds", [])
    if spans:
        add("")
        add("== per-phase host time ==")
        counts = {_label(e): e for e in by.get("trace.span_count", [])}
        total = sum(e["value"] for e in spans) or 1.0
        for e in sorted(spans, key=lambda e: -e["value"]):
            n = counts.get(_label(e))
            add(f"  {e['labels'].get('name', _label(e)):<24} "
                f"{e['value']:.4f}s  ({100 * e['value'] / total:5.1f}%)"
                + (f"  x{int(n['value'])}" if n else ""))

    if "aot.lower_s" in by or "aot.report" in by:
        add("")
        add("== AOT ==")
        for nm in ("aot.lower_s", "aot.compile_s"):
            for e in by.get(nm, []):
                add(f"  {nm}[{_label(e)}]: {_fmt(e.get('value'))}s")

    return "\n".join(lines)


def summarize_trace(path) -> str:
    """Per-span-name totals straight from a Chrome-trace JSON file."""
    doc = json.loads(Path(path).read_text())
    agg: dict[str, list[float]] = defaultdict(lambda: [0, 0.0])
    for ev in doc.get("traceEvents", []):
        if ev.get("ph") == "X":
            agg[ev["name"]][0] += 1
            agg[ev["name"]][1] += ev.get("dur", 0) * 1e-6
    lines = [f"== trace {path} =="]
    for name, (n, secs) in sorted(agg.items(), key=lambda kv: -kv[1][1]):
        lines.append(f"  {name:<24} {secs:.4f}s  x{n}")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.obs.report",
        description="Summarize a repro obs JSONL metrics export.")
    ap.add_argument("jsonl", help="metrics JSONL written via --obs-out")
    ap.add_argument("--trace", default=None,
                    help="optional Chrome-trace JSON to summarize as well")
    args = ap.parse_args(argv)
    meta, entries = load_jsonl(args.jsonl)
    print(summarize(meta, entries))
    if args.trace:
        print()
        print(summarize_trace(args.trace))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
