"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

From the root of a checkout.  Set-up (data from the seed, the cached code,
the system's compiled programs) counts as ``setup_s``; the window then
drives the cell's entry for ``--seconds`` (``--trace 1``: a profiled
window of at most four seconds, reduced to the per-layer metrics), the
answers are compared with the plain reference, and the last line of
standard output is one JSON object.  Exits non-zero, with no result line,
where JAX finds no TPU or fewer chips than the cell needs.

The process runs on a fixed few of the machine's CPUs, six per chip of the
cell, set before NumPy or JAX start their thread pools, so that a run
starts few threads and they share the same CPUs in every run; PERF.md
gives what it did and did not steady on a one-chip v5e host.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# libtpu logs to /tmp/tpu_logs unless told otherwise: a run writes nothing
# outside its checkout and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")
CPUS_PER_CHIP = 6


def pin_cpus(workload: str) -> None:
    """Keep this process, and every thread it starts, on the first
    ``CPUS_PER_CHIP`` CPUs per chip of the cell that it may use."""
    with open(ROOT / "BENCHMARK.json") as f:
        cells = {w["name"]: w for w in json.load(f)["workloads"]}
    chips = cells[workload]["chips"] if workload in cells else 1
    cpus = sorted(os.sched_getaffinity(0))
    os.sched_setaffinity(0, cpus[:CPUS_PER_CHIP * chips])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    pin_cpus(args.workload)
    from bench import harness

    harness.enable_compile_cache()
    return harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
