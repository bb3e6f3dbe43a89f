"""One run of one benchmark cell: set-up, warm-up, the timed window, the
comparison with the plain reference, and the result line.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``; its configuration in ``configs/<config>.json``, whose
``kind`` names the data and work counts in ``kinds/<kind>.py``; its
traffic in ``traffic/<traffic>.json``, whose ``driver`` names the system
under test and its comparison in ``drivers/<kind>.<driver>.py`` and whose
straggler ``process`` names ``stragglers/<process>.py``; and each
per-layer metric's reader in ``metrics/<metric>.py``.  A new cell adds
files and a manifest entry, and edits none.
"""
from __future__ import annotations

import functools
import gc
import importlib
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from bench import trace_reduce

__all__ = ["BENCH", "ROOT", "enable_compile_cache", "load_cell",
           "load_part", "run", "run_window", "least_seconds", "peaks_for"]

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
TRACE_DIR = BENCH / "out" / "trace"
# masks drawn per run: more calls than any window of the manifest's
# length makes (the pool wraps round past it)
MASK_POOL = 8192
# the traced window of a --trace 1 run, at most
TRACE_SECONDS = 4.0


def enable_compile_cache() -> None:
    """The program's persistent compilation cache
    (:mod:`repro.launch.compile_cache`: ``JAX_COMPILATION_CACHE_DIR``
    where set, else a fixed directory in the checkout), holding every
    program however small or quick to compile, so that only the first run
    of a cell in a checkout compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable

    enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, overrides: dict | None = None):
    """``(cell entry, end_to_end, per_layer, configuration, traffic)``
    of a ``BENCHMARK.json`` cell; ``overrides`` replaces configuration
    keys (the tests' small sizes)."""
    manifest = _load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise KeyError(f"no cell {workload!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    cell = cells[workload]
    cfg = {**_load_json(BENCH / "configs" / f"{cell['config']}.json"),
           **(overrides or {})}
    traffic = _load_json(BENCH / "traffic" / f"{cell['traffic']}.json")

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return (cell, mine(manifest["end_to_end"]), mine(manifest["per_layer"]),
            cfg, traffic)


def peaks_for(device_kind: str) -> dict:
    """The chip's peaks from ``peaks.json``; an unknown kind is an error."""
    table = _load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]


def least_seconds(work: dict, peaks: dict) -> float:
    """The least time of ``{"bytes", "flops"}`` of work at the chip's
    peaks: bytes at the HBM bandwidth or operations at the bf16 peak
    (no float32 arithmetic runs faster), whichever bounds."""
    return max(work["bytes"] / peaks["hbm_bytes_per_s"],
               work["flops"] / peaks["bf16_flops_per_s"])


def run_window(cell, masks, start: int, seconds: float, span=None):
    """Drive ``cell.call`` until ``seconds`` have passed; returns
    ``(t0, ends)``, the host clock at the start and after each call."""
    ends = []
    i = start
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while True:
        mask = masks[i % len(masks)]
        if span is None:
            cell.call(i, mask)
        else:
            with span("bench/call", lane="bench", call=i):
                cell.call(i, mask)
        t = time.perf_counter()
        ends.append(t)
        i += 1
        if t >= deadline:
            return t0, ends


def _kind(cfg: dict):
    return importlib.import_module(f"bench.kinds.{cfg['kind']}")


@functools.cache
def load_part(part: str, name: str):
    """The module ``<part>/<name>.py`` under ``bench/`` (a driver, a
    straggler process or a metric reader), loaded once per process."""
    path = BENCH / part / f"{name}.py"
    if not path.exists():
        raise KeyError(f"no {part} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_{part}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Reading:
    """What a per-layer metric reader is given: the trace's summary, the
    cell's work counts and the chip's peaks."""

    def __init__(self, summary, work: dict, peaks: dict):
        self.summary, self.work, self.peaks = summary, work, peaks

    def least_ms(self, layer: str) -> float:
        return 1e3 * least_seconds(self.work[layer], self.peaks)

    @property
    def step_ms(self) -> float:
        """Time per call over the traced window."""
        return 1e3 * self.summary.window_s / self.summary.n_calls


def _say(err, **kv) -> None:
    print(json.dumps(kv), file=err, flush=True)


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True,
        overrides: dict | None = None, cell_kw: dict | None = None,
        peaks: dict | None = None, out=sys.stdout, err=sys.stderr) -> int:
    """One run of one cell: prints the result line and returns 0, or
    returns non-zero without a result (no chip, or a fault).

    The tests rehearse a run on the CPU with ``require_chip=False``, small
    sizes in ``overrides``, a fault or control through ``cell_kw`` and the
    chip's ``peaks`` given."""
    import jax

    entry, e2e, per_layer, cfg, traffic = load_cell(workload, overrides)
    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX sees {devices[0].platform}); the "
              "benchmark measures the chip only", file=err)
        return 3
    if len(devices) < entry["chips"]:
        print(f"bench: cell {workload} needs {entry['chips']} chips, JAX "
              f"sees {len(devices)}", file=err)
        return 3
    kind = _kind(cfg)
    peaks = peaks or peaks_for(devices[0].device_kind)

    def log(part, value, **info):
        _say(err, setup=part, seconds=value, **info)

    driver = load_part("drivers", f"{cfg['kind']}.{traffic['driver']}")
    cell = driver.Cell(cfg, traffic, seed, log, **(cell_kw or {}))
    process = load_part("stragglers", traffic["stragglers"]["process"])
    masks = process.worker_masks(traffic["stragglers"], cell.n_workers,
                                 MASK_POOL, seed)
    warm = traffic["warm_calls"]
    t = time.perf_counter()
    for i in range(warm):
        cell.call(i, masks[i])
    log("warm_s", time.perf_counter() - t, calls=warm)
    # what set-up left on the heap is never scanned again: a full
    # collection over the interpreter's and JAX's objects would pause the
    # window for some tens of milliseconds at a time
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    log("setup_s", setup_s)

    summary = None
    if trace:
        from jax.profiler import ProfileOptions
        from repro.obs import trace as obs_trace

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        tracer = obs_trace.enable_tracing(jax_annotations=True)
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        try:
            t0, ends = run_window(cell, masks, warm,
                                  min(seconds, TRACE_SECONDS), tracer.span)
        finally:
            jax.profiler.stop_trace()
            obs_trace.disable_tracing()
    else:
        t0, ends = run_window(cell, masks, warm, seconds)
    n = len(ends)
    calls_ms = np.diff([t0] + ends) * 1e3
    med = float(np.median(calls_ms))
    _say(err, window={"calls": n, "seconds": ends[-1] - t0,
                      "median_call_ms": med,
                      "max_call_ms": float(calls_ms.max()),
                      "calls_over_twice_median": int(
                          (calls_ms > 2 * med).sum())})
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    cell.release()
    gc.collect()

    if trace:
        summary = trace_reduce.reduce(trace_reduce.from_xplane(
            trace_reduce.find_xplane(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        reading = Reading(summary, kind.work_counts(cfg), peaks)
        metrics = {}
        for m in per_layer:
            value = load_part("metrics", m["name"]).read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e_values = {"step_ms": (ends[-1] - t0) * 1e3 / n,
                      "setup_s": setup_s}
        metrics = {m["name"]: {"value": e2e_values[m["name"]],
                               "unit": m["unit"]} for m in e2e}

    readings = cell.compare()
    limits = cfg["limits"]
    # a NaN or an overflow reads as the largest number, never as within
    compared = {k: {"value": readings[k] if math.isfinite(readings[k])
                    else sys.float_info.max, "limit": limits[k]}
                for k in limits}
    over = sum(v["value"] > v["limit"] for v in compared.values())
    correct = over == 0
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": n, "failed": over,
              "metrics": metrics,
              "device": device}
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.top_ops,
                               "idle_gaps": summary.idle_by_label}
    result["compared"] = compared
    for name, v in compared.items():
        print(f"compared {name} = {v['value']!r} (limit {v['limit']!r})",
              file=err)
    err.flush()
    print(json.dumps(result), file=out, flush=True)
    return 0
