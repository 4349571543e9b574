#!/usr/bin/env python3
"""One cell's window read by what the program marks itself: device time per
named scope of the step and per collective, idle gaps by the input thread's
spans, and the ``Prefetcher`` counters of an untraced and a traced stretch.

    python3 benchmarks/chip/trace_program.py --workload resnet50_async_flat \\
        --seed 12345 --seconds 20 --out chiprun_out/scopes

Run from the root of a checkout, on the chips the cell asks for. Set-up is
``run.py``'s without its checked steps and reference: the same step, state,
stream and loop (``runner.drive``). The window runs ``--seconds`` untraced,
then the traffic's ``trace_seconds`` under the profiler. The last line of
standard output is the result as JSON; ``--out`` also keeps the step's HLO
text and the trace file (gzip, up to 16 MiB), to reduce again without the
chip. The compile cache is keyed with metadata (``runner.enable_cache``), as
``run.py`` keys it; a step text without its scopes exits 1.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

SPAN_COST_CALLS = 20000
KEEP_TRACE_BYTES = 16 << 20  # a larger compressed trace is not kept


def slow_steps(start, comps, spans, top=10, factor=1.5):
    """The window's completion intervals over ``factor`` x their median: how
    many, the seconds they lose over the median, and the slowest, each as
    [index, seconds into the window, ms, and the ms of the ``fetch`` and
    ``dispatch`` of the next step and the ``wait`` that ended it]."""
    iv = [b - a for a, b in zip([start] + comps, comps)]
    med = sorted(iv)[len(iv) // 2]
    rows = [[k, comps[k] - start, 1000 * iv[k], 1000 * spans["fetch"][k + 1],
             1000 * spans["dispatch"][k + 1], 1000 * spans["wait"][k]]
            for k in range(len(iv)) if iv[k] > factor * med]
    return {"median_ms": 1000 * med, "n": len(rows),
            "lost_s": sum(r[2] / 1000 - med for r in rows),
            "slowest": sorted(rows, key=lambda r: -r[2])[:top]}


def span_cost_us(jax):
    """Host µs of one disabled-``Tracer`` span (its TraceAnnotation included)
    with no profiler session, and with one active."""
    from repro.obs.trace import get_tracer

    def per_call():
        tracer = get_tracer()
        t0 = time.perf_counter()
        for _ in range(SPAN_COST_CALLS):
            with tracer.span("input.cost"):
                pass
        return 1e6 * (time.perf_counter() - t0) / SPAN_COST_CALLS

    off = per_call()
    out = tempfile.mkdtemp(prefix="chipbench-cost-")
    try:
        jax.profiler.start_trace(out)
        try:
            on = per_call()
        finally:
            jax.profiler.stop_trace()
    finally:
        shutil.rmtree(out, ignore_errors=True)
    return {"profiler_off": off, "profiler_on": on}


def measure(workload, seed, seconds, *, root, out=None, require_chip=True,
            spec=None, trace_seconds=None):
    """Run the cell's window; returns the result dict."""
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(root, "src"))
    from chipbench import generate, program_trace, reference, runner

    spec = spec or runner.find_cell(root, workload)
    cell, cfg, tr = spec["cell"], spec["config"], spec["traffic"]
    n = cell["chips"]
    peaks = runner.load_json(os.path.join(HERE, "peaks.json"))
    devices = (runner.check_chips(jax, n, peaks) if require_chip
               else jax.devices()[:n])
    clock = runner.CompileClock(jax)
    family = runner.load_module(os.path.join(HERE, "chipbench", "families",
                                             cfg["family"] + ".py"))
    entry_mod = runner.load_module(os.path.join(HERE, "chipbench", "entries",
                                                cfg["entry"] + ".py"))
    from repro.data import Cursor, Prefetcher

    pool = generate.stream_pool(seed, tr, n)
    ent = entry_mod.Entry(cfg, tr, family, n)
    shapes = family.param_shapes(cfg)
    trace_s = tr["trace_seconds"] if trace_seconds is None else trace_seconds
    with ent.context():
        make = jax.jit(lambda k: reference.make_params(k, shapes, family.init_leaf),
                       out_shardings=ent.param_shardings())
        ent.init_state(make(reference.weights_key(seed)), reference.lineage_key(seed),
                       generate.root_key(seed))
        pf = Prefetcher(lambda cur: pool[cur.step % len(pool)],
                        cursor=Cursor(tr["window_task"], 0), convert=jnp.asarray)
        pf.start()
        try:
            g = 0
            for _ in range(tr["checked_steps"] + tr["warmup_steps"]):
                _, batch = pf.next()
                ent.step(batch, g).block_until_ready()
                g += 1
            # the step's text, compiled outside the window (a cache read)
            hlo = program_trace.step_hlo(ent, batch, jax.random.fold_in(ent.key0, g))
            program_trace.require_scopes(hlo, rehearsal=tr["mode"] != "off")
            compiles0 = clock.count
            setup_s = time.time() - T_START
            c0 = pf.counters()
            start, comps, spans, g = runner.drive(ent, pf, g, seconds)
            c1 = pf.counters()
            tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
            try:
                jax.profiler.start_trace(tdir)
                try:
                    t_start, t_comps, _, g = runner.drive(
                        ent, pf, g, trace_s, annotate=jax.profiler.TraceAnnotation)
                finally:
                    jax.profiler.stop_trace()
                c2 = pf.counters()
                devs, host = program_trace.read_xplane(tdir)
                red = program_trace.reduce(devs, host, len(t_comps), hlo)
                if out:
                    os.makedirs(out, exist_ok=True)
                    with gzip.open(os.path.join(out, workload + ".hlo.txt.gz"), "wt") as f:
                        f.write(hlo)
                    kept = os.path.join(out, workload + ".xplane.pb.gz")
                    with open(program_trace.newest_xplane(tdir), "rb") as f, \
                            gzip.open(kept, "wb") as g_out:
                        shutil.copyfileobj(f, g_out)
                    if os.path.getsize(kept) > KEEP_TRACE_BYTES:
                        os.remove(kept)
            finally:
                shutil.rmtree(tdir, ignore_errors=True)
            compiles_in_window = clock.count - compiles0
        finally:
            pf.stop()
        cost = span_cost_us(jax)
    window_s = comps[-1] - start
    t_window_s = t_comps[-1] - t_start
    b_global = tr["batch_per_chip"] * n
    result = {
        "workload": workload, "seed": seed,
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
        "setup_s": setup_s, "compiles_in_window": compiles_in_window,
        "untraced": {"steps": len(comps), "window_s": window_s,
                     "samples_per_s": len(comps) * b_global / window_s,
                     "slow_steps": slow_steps(start, comps, spans),
                     **program_trace.input_readings(c0, c1, window_s)},
        "traced": {"steps": len(t_comps), "window_s": t_window_s,
                   "samples_per_s": len(t_comps) * b_global / t_window_s,
                   **program_trace.input_readings(c1, c2, t_window_s)},
        "span_cost_us": cost,
    }
    if red is not None:
        result["metrics"] = {
            "buffer_update_ms": program_trace.scope_ms(red, ("buffer_update",)),
            "buffer_sample_ms": program_trace.scope_ms(red, program_trace.SAMPLE_SCOPES),
            "unscoped_device_pct": program_trace.unscoped_pct(red)}
        result["collectives"] = dict(red["device_collectives"])
        result["breakdown"] = {k: red[k] for k in (
            "window_s", "busy_s", "scopes_busy_s", "idle_pct", "device_scopes",
            "device_ops_scoped", "idle_gaps", "idle_gaps_program", "idle_by_span",
            "program_span_s")}
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from chipbench import program_trace, runner

    root = os.path.dirname(os.path.dirname(HERE))
    runner.enable_cache(jax, root)
    try:
        result = measure(args.workload, args.seed, args.seconds, root=root, out=args.out)
    except runner.NoChip as e:
        print(f"trace_program: {e}", file=sys.stderr)
        return 2
    except program_trace.MissingScopes as e:
        print(f"trace_program: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
