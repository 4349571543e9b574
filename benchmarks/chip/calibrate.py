#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from (not part of a run).

    python3 benchmarks/chip/calibrate.py --workload smollm135m_async_flat \\
        --seeds 11,12,13 --control-seeds 11,12,13 --seconds 2

In one process, for every seed of ``--seeds``: one short run of the cell
(set-up, the checked steps, a window of ``--seconds``, the reference) and its
numbers, the program's readings. For every seed of ``--control-seeds`` also:
the control (the reference in float8 operands in the program's place) and the
reference with a planted fault in the program's place (``half_batch``: half
the rows left out, the mean taken over the rest; ``frozen``: state returned
unchanged; ``no_replay``: the replay rows left out, in cells that rehearse),
each compared with the float32 reference as a run compares the program;
and, in cells of several workers, ``exchange_local``: a run of the program
built with the exchange between chips left out (a local sample), checked
against the reference's global sample. ``--variants`` picks among these and
``bf16_witness``, the reference with its operands and their gradients
rounded to bfloat16: how far rounding alone moves the numbers. One JSON line
per reading, then a summary line: the largest reading of the program and of
the witness (the lower readings) and the smallest of each control or fault
(the upper readings) of every number.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

VARIANTS = {"control": {"qdt": "float8_e4m3fn"}, "half_batch": {"fault": "half_batch"},
            "frozen": {"fault": "frozen"}, "no_replay": {"fault": "no_replay"},
            "bf16_witness": {"qdt": "bfloat16"}}
LOWER = ("program", "bf16_witness")
EXCHANGE_LOCAL = "exchange_local"


@contextlib.contextmanager
def exchange_left_out():
    """Within it, the mesh step is built with a local sample: each worker
    draws its representatives from its own buffer, no exchange between
    chips."""
    from repro.launch import steps

    orig = steps.build_train_step
    steps.build_train_step = lambda *a, **k: orig(*a, **dict(k, exchange="local"))
    try:
        yield
    finally:
        steps.build_train_step = orig


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--variants",
                    default="control,half_batch,frozen,no_replay,exchange_local")
    args = ap.parse_args(argv)

    import jax

    from chipbench import compare, reference, runner

    root = os.path.dirname(os.path.dirname(HERE))
    runner.enable_cache(jax, root)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    readings = {}
    t0 = T_START
    for seed in [int(s) for s in args.seeds.split(",")]:
        cap = {}
        try:
            res = runner.run(args.workload, seed, args.seconds, False, root=root,
                             t_start=t0, capture=cap)
        except runner.NoChip as e:
            print(f"chipbench: {e}", file=sys.stderr)
            return 2
        t0 = time.time()
        line = {"seed": seed, "kind": "program", "correct": res["correct"],
                "numbers": cap["numbers"], "setup_s": res["metrics"]["setup_s"]["value"],
                "samples_per_s": res["metrics"]["samples_per_s"]["value"],
                "reference_s": res["setup"]["reference_s"], "worst": cap["worst"]}
        print(json.dumps(line), flush=True)
        readings.setdefault("program", []).append(cap["numbers"])
        if seed not in controls:
            continue
        spec, ref = cap["spec"], cap["ref"]
        for kind in args.variants.split(","):
            if kind == "no_replay" and not cap["layout"]["rehearse"]:
                continue
            if kind == EXCHANGE_LOCAL:
                if cap["layout"]["group"] is None or cap["layout"]["n_workers"] < 2:
                    continue
                fault = {}
                with exchange_left_out():
                    res = runner.run(args.workload, seed, args.seconds, False,
                                     root=root, t_start=time.time(), capture=fault)
                print(json.dumps({"seed": seed, "kind": kind, "correct": res["correct"],
                                  "numbers": fault["numbers"]}), flush=True)
                readings.setdefault(kind, []).append(fault["numbers"])
                continue
            out = reference.replay(spec["config"], spec["traffic"], cap["family"],
                                   cap["layout"], seed, spec["traffic"]["checked_steps"],
                                   **VARIANTS[kind])
            numbers, _ = compare.training_numbers(out, ref)
            worst = {"grad": compare.worst_leaves(out["mu1"], ref["mu1"], cap["names"]),
                     "update": compare.worst_leaves(out["delta"], ref["delta"],
                                                    cap["names"])}
            print(json.dumps({"seed": seed, "kind": kind, "numbers": numbers,
                              "worst": worst}), flush=True)
            readings.setdefault(kind, []).append(numbers)
    summary = {}
    for kind, rows in readings.items():
        pick = max if kind in LOWER else min
        summary[kind] = {k: pick(r[k] for r in rows) for k in rows[0]}
        summary[kind]["seeds"] = len(rows)
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
