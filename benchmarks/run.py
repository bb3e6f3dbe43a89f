"""Benchmark harness — one module per paper figure plus the system-level
reports.  ``python -m benchmarks.run [--full]``.

  fig1_least_squares — paper Fig. 1 (least squares, k sweep, s ∈ {5,10})
  fig2_sparse_over   — paper Fig. 2 (overdetermined IHT sparsity sweep)
  fig3_sparse_under  — paper Fig. 3 (underdetermined IHT)
  decoder_scaling    — Section 3 decode-complexity/adaptivity claims
  roofline           — §Roofline table from the dry-run artifacts

Default mode is sized for this CPU container (fewer trials / smaller k
grids than the paper's 100-trial cluster runs); --full restores the paper's
grids.
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true",
                    help="paper-sized grids (slower)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset: fig1,fig2,fig3,decoder,roofline")
    args = ap.parse_args(argv)
    quick = not args.full

    from benchmarks import (decoder_scaling, fig1_least_squares,
                            fig2_sparse_over, fig3_sparse_under, roofline)
    suite = {
        "fig1": fig1_least_squares.main,
        "fig2": fig2_sparse_over.main,
        "fig3": fig3_sparse_under.main,
        "decoder": decoder_scaling.main,
        "roofline": roofline.main,
    }
    only = args.only.split(",") if args.only else list(suite)
    t0 = time.time()
    for name in only:
        t = time.time()
        print(f"\n================ {name} ================")
        suite[name](quick=quick)
        print(f"[{name}: {time.time()-t:.1f}s]")
    print(f"\nbenchmarks done in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
