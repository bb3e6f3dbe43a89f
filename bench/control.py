"""Readings of the numbers ``correct`` compares, over many seeds in one
process: the program's (the lower readings) or the control's, the plain
reference computed in a lower precision in the program's place (the upper
readings).

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds 3 --control program|bf16

Each seed is one full run of the cell (set-up, warm-up, a window of
``--seconds``, the comparison), printed as its result line; the benchmark's
own runs never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# libtpu logs to /tmp/tpu_logs unless told otherwise: a run writes nothing
# outside its checkout and the directories it is given
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one run each")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", default="program",
                    choices=("program", "bf16"))
    args = ap.parse_args(argv)

    from bench import harness

    harness.enable_compile_cache()

    kw = {} if args.control == "program" else {"control": args.control}
    for seed in (int(s) for s in args.seeds.split(",")):
        print(f"== {args.workload} seed {seed} control {args.control}",
              flush=True)
        rc = harness.run(args.workload, seed, args.seconds, False,
                         t_start=time.perf_counter(), cell_kw=kw)
        if rc:
            return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
