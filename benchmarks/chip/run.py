#!/usr/bin/env python3
"""On-chip benchmark of the continual trainer: one run of one cell.

    python3 benchmarks/chip/run.py --workload resnet50_async_flat \\
        --seed 12345 --seconds 45 --trace 0

Run from the root of a checkout; the cells are the ``workloads`` of
BENCHMARK.json. The last line of standard output is the result as JSON
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, and with
``--trace 1`` ``breakdown``); the numbers that decided ``correct`` are its last
key, ``checks``, and the last lines of standard error. Without the TPU chips
the cell asks for, it exits with code 2 and prints no result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from chipbench import runner

    root = os.path.dirname(os.path.dirname(HERE))
    runner.enable_cache(jax, root)
    try:
        result = runner.run(args.workload, args.seed, args.seconds, bool(args.trace),
                            root=root, t_start=T_START)
    except runner.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
