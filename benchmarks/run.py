# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
"""Benchmark harness: ``PYTHONPATH=src python -m benchmarks.run [--only NAME]``.

  fig5a  — accuracy vs rehearsal buffer size       (paper Fig. 5a)
  fig5b  — three strategies: accuracy + runtime    (paper Fig. 5b)
  fig6   — rehearsal management breakdown/overlap  (paper Fig. 6)
  fig7   — scalability: overhead + autoscaling + restart cost (paper Fig. 7)
  fig8   — continual serving: decode throughput + drifted-slice freshness
  roofline — per (arch x shape x mesh) roofline terms from the dry-run artifacts
"""
import argparse
import sys
import traceback

from repro.utils.logging import CSVWriter
from repro.utils.platform import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: fig5a,fig5b,fig6,fig7,fig8,roofline")
    ap.add_argument("--smoke", action="store_true",
                    help="shrunk fig5a/fig6 runs for CI (still emit BENCH_*.json)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    enable_compile_cache()

    from benchmarks import (fig5a_buffer_size, fig5b_strategies, fig6_breakdown,
                            fig7_scalability, fig8_serving, roofline_table)

    benches = {
        "fig5a": fig5a_buffer_size.run,
        "fig5b": fig5b_strategies.run,
        "fig6": fig6_breakdown.run,
        "fig7": fig7_scalability.run,
        "fig8": fig8_serving.run,
        "roofline": roofline_table.run,
    }
    writer = CSVWriter()
    # emit BENCH_*.json, accept --smoke
    smoke_aware = {"fig5a", "fig5b", "fig6", "fig7", "fig8"}
    failures = 0
    for name, fn in benches.items():
        if only and name not in only:
            continue
        try:
            if name in smoke_aware:
                fn(writer, smoke=args.smoke)
            else:
                fn(writer)
        except Exception:
            failures += 1
            print(f"{name},nan,FAILED", flush=True)
            traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
