"""One run of one cell: set-up, the measured window, the check of what the
timed path produced against the plain reference, and the result line.

Everything that belongs to one configuration, traffic mix, cell or per-layer
metric is found by name in a file of its own:

    configs/<file named in BENCHMARK.json>   sizes, precision, optimizer
    traffic/<traffic>.json                   stream, batch, buffer, pool
    limits/<workload>.json                   the limit of each number compared
    metrics/<metric>.py                      read(run) -> value or None
    chipbench/families/<family>.py           weights, FLOPs, plain reference
    chipbench/entries/<entry>.py             the program's step, built by its builders
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NoChip(RuntimeError):
    """The run needs accelerator chips that JAX does not see."""


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path):
    name = "chipbench_" + os.path.splitext(os.path.basename(path))[0].replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(per_layer, workload):
    """The per-layer metrics a cell reports: those whose ``workloads`` name
    it, and those without such a list."""
    return [m for m in per_layer if workload in m.get("workloads", [workload])]


def find_cell(root, workload):
    """The cell's entry, configuration, traffic, limits and metric lists."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return {
        "cell": cell,
        "config": load_json(os.path.join(root, conf["file"])),
        "traffic": load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json")),
        "limits": load_json(os.path.join(HERE, "limits", workload + ".json")),
        "end_to_end": bench["end_to_end"],
        "per_layer": cell_metrics(bench["per_layer"], workload),
    }


class CompileClock:
    """Backend compile seconds (persistent-cache reads included) and cache
    hits, from JAX's monitoring events."""

    def __init__(self, jax):
        self.secs, self.count, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += duration
            self.count += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def check_chips(jax, chips, peaks):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's devices are {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX sees {len(devs)}")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in peaks.json")
    return devs[:chips]


def enable_cache(jax, root):
    """JAX's persistent compilation cache: $JAX_COMPILATION_CACHE_DIR where set,
    else ``<checkout>/.jax_cache`` (a fixed path, so entries are found again).
    Its key holds the programs' metadata: without it, a step read from the
    cache carries no ``op_name``, and a traced run finds no scope."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return path


def drive(ent, pf, g, seconds, annotate=None):
    """The closed loop: dispatch step g, then wait for step g-1's loss. Runs
    until ``seconds`` have passed since it started (the previous steps must
    have completed). Returns (start, completion times, host spans, next g)."""
    from contextlib import nullcontext

    ann = annotate or (lambda name: nullcontext())
    spans = {"fetch": [], "dispatch": [], "wait": []}
    comps, prev = [], None
    start = time.perf_counter()
    while True:
        a = time.perf_counter()
        with ann("fetch"):
            _, batch = pf.next()
        b = time.perf_counter()
        with ann("dispatch"):
            loss = ent.step(batch, g)
        c = time.perf_counter()
        g += 1
        spans["fetch"].append(b - a)
        spans["dispatch"].append(c - b)
        if prev is not None:
            with ann("wait"):
                prev.block_until_ready()
            d = time.perf_counter()
            spans["wait"].append(d - c)
            comps.append(d)
        prev = loss
        if comps and comps[-1] - start >= seconds:
            break
    prev.block_until_ready()  # in flight when the window closed: not counted
    return start, comps, spans, g


def run(workload, seed, seconds, trace, *, root, t_start, require_chip=True,
        spec=None, entry_hook=None, capture=None, log=sys.stderr):
    """Run one cell; returns the result dict (the last line of stdout).
    ``spec`` (as ``find_cell`` returns it) and ``entry_hook`` (called with
    the built entry) serve the tests; ``require_chip=False`` skips the look
    for a chip. ``capture`` (a dict) receives the program's readings
    (``got``) and the reference's (``ref``) for the calibration of limits."""
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(root, "src"))
    sys.path.insert(0, HERE)
    from chipbench import compare, generate, program_trace, reference, stats

    spec = spec or find_cell(root, workload)
    cell, cfg, tr = spec["cell"], spec["config"], spec["traffic"]
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    n = cell["chips"]
    if require_chip:
        devices = check_chips(jax, n, peaks)
        peak = peaks[devices[0].device_kind]
    else:
        devices = jax.devices()[:n]
        peak = None
    clock = CompileClock(jax)
    family = load_module(os.path.join(HERE, "chipbench", "families", cfg["family"] + ".py"))
    entry_mod = load_module(os.path.join(HERE, "chipbench", "entries", cfg["entry"] + ".py"))

    from repro.data import Cursor, Prefetcher

    pool = generate.stream_pool(seed, tr, n)
    ent = entry_mod.Entry(cfg, tr, family, n)
    if entry_hook is not None:
        entry_hook(ent)
    want_shapes = family.param_shapes(cfg)
    have = ent.program_param_shapes()
    if (jax.tree_util.tree_structure(have) != jax.tree_util.tree_structure(want_shapes)
            or [x.shape for x in jax.tree_util.tree_leaves(have)]
            != [x.shape for x in jax.tree_util.tree_leaves(want_shapes)]):
        raise RuntimeError("the program's parameter tree is not the layout the "
                           f"{cfg['family']} reference reads")
    norms = jax.jit(reference.leaf_norms)
    delta_norms = jax.jit(lambda a, b: reference.leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, a, b)))
    layout = ent.layout()
    checked = tr["checked_steps"]
    got = {"loss": [], "pending": []}
    with ent.context():
        make = jax.jit(lambda k: reference.make_params(k, want_shapes, family.init_leaf),
                       out_shardings=ent.param_shardings())
        params = make(reference.weights_key(seed))
        p0 = make(reference.weights_key(seed))
        ent.init_state(params, reference.lineage_key(seed), generate.root_key(seed))
        del params
        pf = Prefetcher(lambda cur: pool[cur.step % len(pool)],
                        cursor=Cursor(tr["window_task"], 0), convert=jnp.asarray)
        pf.start()
        try:
            # the first steps go through the window's own call and feed; their
            # products are read for the check against the reference
            for g in range(checked):
                _, batch = pf.next()
                got["loss"].append(float(ent.step(batch, g)))
                if g == 0:
                    got["mu1"] = np.asarray(norms(ent.opt_mu()))
                if g == checked - 1:
                    # after the steps that train replay rows too
                    got["mu_last_tree"] = jax.device_get(
                        jax.tree_util.tree_leaves(ent.opt_mu()))
                if layout["rehearse"]:
                    fp, valid = ent.pending_reads()
                    got["pending"].append((np.asarray(fp), np.asarray(valid)))
            got["delta"] = np.asarray(delta_norms(ent.params(), p0))
            del p0
            if layout["rehearse"]:
                fp, counts, seen = ent.buffer_reads()
                got["buffer"] = {"fp": np.asarray(fp), "counts": np.asarray(counts),
                                 "seen": np.asarray(seen),
                                 "pending": got["pending"]}
            g = checked
            for _ in range(tr["warmup_steps"]):
                _, batch = pf.next()
                ent.step(batch, g).block_until_ready()
                g += 1
            compiles0 = clock.count
            setup_s = time.time() - t_start
            setup_compile_s = clock.secs
            if trace:
                trace_s = min(tr["trace_seconds"], seconds / 3)
                c0 = pf.counters()
                start, comps, spans, g = drive(ent, pf, g, seconds - trace_s)
                c1 = pf.counters()
                traced, t_steps, g = _traced(jax, ent, pf, g, trace_s)
            else:
                start, comps, spans, g = drive(ent, pf, g, seconds)
            compiles_in_window = clock.count - compiles0
            if trace:
                # off the clock: after the window, before the state is freed
                _, batch = pf.next()
                hlo = program_trace.step_hlo(ent, batch, jax.random.fold_in(ent.key0, g))
                counters = {"input": {k: c1[k] - c0[k] for k in c1},
                            "step": ent.counters() if hasattr(ent, "counters") else {}}
        finally:
            pf.stop()
        mem = [d.memory_stats() or {} for d in devices]
        memory_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    ent.free()
    del ent
    gc.collect()

    window_s = comps[-1] - start
    steps = len(comps)
    b_global = tr["batch_per_chip"] * n
    rows_per_step = b_global + (tr["reps"] * n if layout["rehearse"] else 0)
    intervals = np.diff([start] + comps)

    # --- the plain reference, once the program's state is freed ---
    t_ref = time.perf_counter()
    ref = reference.replay(cfg, tr, family, layout, seed, checked,
                           observed=[p[0] for p in got["pending"]] or None)
    numbers, n_skipped = compare.training_numbers(got, ref)
    if layout["rehearse"]:
        want = reference.expected_buffer(ref["buffer"], seed, tr)
        pend = [reference.pending_fingerprints(p, seed, tr) for p in ref["pending"]]
        numbers["buffer_mismatches"] = compare.buffer_mismatches(got["buffer"], want, pend)
    ok, checks = compare.verdict(numbers, spec["limits"])
    ref_s = time.perf_counter() - t_ref
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(want_shapes)[0]]
    worst = {"grad": compare.worst_leaves(got["mu1"], ref["mu1"], names),
             "update": compare.worst_leaves(got["delta"], ref["delta"], names)}
    if capture is not None:
        capture.update(worst=worst, got=got, ref=ref, numbers=numbers, layout=layout,
                       family=family, spec=spec, names=names)

    values = {
        "samples_per_s": steps * b_global / window_s,
        "step_ms_p90": 1000.0 * stats.percentile(intervals, 90),
        "setup_s": setup_s,
    }
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(ok), "attempted": steps, "failed": 0}
    if trace:
        trace_out = program_trace.reduce(*traced, t_steps, hlo)
        record = {"window_s": window_s, "steps": steps, "spans": spans,
                  "n_chips": n, "peaks": peak, "trace": trace_out,
                  "flops_per_step": rows_per_step * family.train_flops_per_row(cfg, tr),
                  "scopes": dict(trace_out["device_scopes"]) if trace_out else {},
                  "collectives": (dict(trace_out["device_collectives"])
                                  if trace_out else {}),
                  "counters": counters}
        metrics = {}
        for m in spec["per_layer"]:
            v = load_module(os.path.join(HERE, "metrics", m["name"] + ".py")).read(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if trace_out is not None:
            device.update(busy_s=trace_out["busy_s"], window_s=trace_out["window_s"])
            result["breakdown"] = {"device_ops": trace_out["device_ops"],
                                   "idle_gaps": trace_out["idle_gaps"],
                                   "device_scopes": trace_out["device_scopes"][:10]}
    else:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    result["metrics"] = metrics
    result["device"] = device
    result["setup"] = {"compile_s": setup_compile_s, "compiles_in_window":
                       compiles_in_window, "cache_hits": clock.hits,
                       "compile_cache": jax.config.jax_compilation_cache_dir,
                       "reference_s": ref_s,
                       "window_s": window_s, "steps": steps,
                       "step_ms_median": 1000.0 * float(np.median(intervals)),
                       "losses": got["loss"], "ref_losses": ref["loss"],
                       "leaves_left_out": n_skipped, "worst_leaves": worst,
                       "numbers": numbers}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=log)
    return result


def _traced(jax, ent, pf, g, seconds):
    """A traced stretch of the window; returns ((device ops, host spans) as
    ``program_trace.read_xplane`` reads the trace, the steps completed in
    the stretch, the next g)."""
    from chipbench import program_trace

    out = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        jax.profiler.start_trace(out)
        try:
            _, comps, _, g = drive(ent, pf, g, seconds,
                                   annotate=jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
        return program_trace.read_xplane(out), len(comps), g
    finally:
        shutil.rmtree(out, ignore_errors=True)
