"""Paper Fig. 6: rehearsal-buffer management breakdown vs Load + Train,
plus the sync-vs-pipelined exchange comparison (DESIGN.md §3).

The paper's criterion: the background work (Populate buffer + Augment batch) must be
smaller than Load + Train so the async design fully hides it. We measure each
component as its own jitted function on CPU:

  Load           — data pipeline batch production
  Train          — fwd+bwd+opt on the augmented batch (no rehearsal ops)
  Populate+Sample— Alg-1 update + global sampling (the paper's background work)
  async step     — everything fused in one XLA program (the deployed form)

derived = hideable = (Populate+Sample) / (Load+Train)  (< 1 ⇒ fully overlappable,
the paper's Fig. 6 condition). CPU has no async streams, so the fused step costs
~Train + Populate; on TPU the XLA latency-hiding scheduler overlaps the rehearsal
collectives with the backward pass (the structural evidence — independence of the
rehearsal subgraph from the grad subgraph — is checked in tests/test_dryrun_cells.py).

The sync-vs-pipelined section measures the overlap that IS observable on CPU:
the pipelined step dispatches the train program (which consumes the pending reps
sampled at t−1, so the loss has no data dependency on this step's exchange) and
the issue program separately; the issue program's device execution then overlaps
the host-side load of the next batch. The sync baseline must finish the exchange
before the loss is available, so its per-step wall-clock serialises
load + exchange + train. derived = pipelined/sync per-step ratio (< 1 ⇒ the
exchange left the critical path — the paper's headline effect).
"""
import json
import os
import time

import jax
import jax.numpy as jnp

from benchmarks.common import VisionCL
from repro.configs.base import RehearsalConfig
from repro.core import init_carry, make_cl_step, make_pipelined_halves
from repro.core import rehearsal as rb
from repro.core.distributed import sample_global


def _time(fn, *args, n=20):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e6 * (time.perf_counter() - t0) / n


def run(writer, smoke: bool = False, json_path: str = "BENCH_fig6.json"):
    n_iters = 8 if smoke else 20
    h = VisionCL()
    rcfg = RehearsalConfig(num_buckets=h.num_tasks, slots_per_bucket=64,
                           num_representatives=8, num_candidates=14, mode="async")
    key = jax.random.PRNGKey(0)
    params = jax.jit(lambda k: __import__("repro.models.resnet", fromlist=["init_cnn"])
                     .init_cnn(k, h.ccfg))(key)
    carry = init_carry(params, h.opt_init(params), h.item_spec, rcfg,
                       label_field="label")

    # Load
    t0 = time.perf_counter()
    for s in range(n_iters):
        h.stream.batch(0, h.batch_size, s)
    load_us = 1e6 * (time.perf_counter() - t0) / n_iters
    batch = {k: jnp.asarray(v) for k, v in h.stream.batch(0, h.batch_size, 0).items()}

    # Train only (no rehearsal): augmented-size batch to match the paper's b+r cost
    aug_batch = {k: jnp.concatenate([v, v[: rcfg.num_representatives]]) for k, v in
                 batch.items()}
    step_off = make_cl_step(h.loss_fn, h.opt_update, None, strategy="incremental",
                            label_field="label", donate=False)
    carry_off = init_carry(params, h.opt_init(params))
    train_us = _time(lambda c, b, k: step_off(c, b, k)[1]["loss"],
                     carry_off, aug_batch, key, n=n_iters)

    # Populate + Sample (the paper's background work), as its own jitted fn
    @jax.jit
    def populate_sample(buf, items, labels, k):
        k1, k2 = jax.random.split(k)
        buf = rb.local_update(buf, items, labels, k1, rcfg.num_candidates)
        reps, valid = sample_global(buf, k2, rcfg.num_representatives, None, "local")
        return buf, reps, valid

    pop_us = _time(lambda b, bt, k: populate_sample(b, bt, bt["task"], k)[0].counts,
                   carry.buffer, batch, key, n=n_iters)

    # Fused async step (deployed form)
    step_async = make_cl_step(h.loss_fn, h.opt_update, rcfg, strategy="rehearsal",
                              label_field="label", donate=False)
    async_us = _time(lambda c, b, k: step_async(c, b, k)[1]["loss"], carry, batch, key,
                     n=n_iters)

    hideable = pop_us / (load_us + train_us)
    writer.row("fig6/load", f"{load_us:.0f}", "")
    writer.row("fig6/train", f"{train_us:.0f}", "")
    writer.row("fig6/populate_sample", f"{pop_us:.0f}",
               f"hideable={hideable:.3f}(<1=fully_overlappable)")
    writer.row("fig6/fused_async_step", f"{async_us:.0f}",
               f"vs_train+pop={async_us / (train_us + pop_us):.2f}")

    sync_us, pipe_us = _sync_vs_pipelined(h, rcfg, params, key,
                                          n=10 if smoke else 30)
    writer.row("fig6/sync_step", f"{sync_us:.0f}", "load+exchange+train_serialised")
    writer.row("fig6/pipelined_step", f"{pipe_us:.0f}",
               f"vs_sync={pipe_us / sync_us:.3f}(<1=exchange_off_critical_path)")

    kernel_rows = _kernel_breakdown(writer, smoke=smoke)

    payload = {"bench": "fig6", "smoke": smoke, "rows": {
        "load_us": round(load_us, 1), "train_us": round(train_us, 1),
        "populate_sample_us": round(pop_us, 1), "hideable": round(hideable, 4),
        "fused_async_us": round(async_us, 1), "sync_us": round(sync_us, 1),
        "pipelined_us": round(pipe_us, 1),
        "pipelined_vs_sync": round(pipe_us / sync_us, 4),
        **kernel_rows}}
    with open(json_path, "w") as f:
        json.dump(payload, f, indent=2)
    writer.row("fig6/json", "0", os.path.abspath(json_path))

    # --- telemetry cost + chaos validation (DESIGN.md §11) -----------------
    off_us, on_us, overhead = _obs_overhead(h, rcfg, params, key,
                                            n=10 if smoke else 30)
    writer.row("fig6/obs_off_pipelined_step", f"{off_us:.0f}", "")
    writer.row("fig6/obs_on_pipelined_step", f"{on_us:.0f}",
               f"obs_overhead={overhead:.3f}(gate<=1.03)")
    chaos = _chaos_obs(h, params, key, smoke=smoke)
    writer.row("fig6/obs_chaos", f"{chaos['restore_s'] * 1e6:.0f}",
               f"restarts={chaos['restarts']},reshard_s={chaos['reshard_s']:.3f}")
    obs_payload = {"bench": "obs", "smoke": smoke, "rows": {
        "obs_off_us": round(off_us, 1), "obs_on_us": round(on_us, 1),
        "obs_overhead": round(overhead, 4),
        "chaos_restarts": chaos["restarts"],
        "chaos_reshard_s": round(chaos["reshard_s"], 4),
        "chaos_restore_s": round(chaos["restore_s"], 4),
        "chaos_trace_events": chaos["trace_events"],
        "chaos_event_lines": chaos["event_lines"]}}
    obs_json = os.path.join(os.path.dirname(json_path) or ".", "BENCH_obs.json")
    with open(obs_json, "w") as f:
        json.dump(obs_payload, f, indent=2)
    writer.row("obs/json", "0", os.path.abspath(obs_json))


def _count_ops(jaxpr) -> int:
    """Primitive count of a jaxpr with call-like primitives expanded — except
    ``pallas_call``, which counts as ONE op (a single fused kernel launch).
    This is the interpret-comparable cost model of DESIGN.md §14: each op is
    (at least) one HBM round-trip for its operands, so fewer ops over the same
    tensors == fewer full-width passes."""
    n = 0
    for eqn in jaxpr.eqns:
        inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if eqn.primitive.name != "pallas_call" and inner is not None:
            n += _count_ops(getattr(inner, "jaxpr", inner))
        else:
            n += 1
    return n


def _kernel_breakdown(writer, smoke: bool = False):
    """Tiered hot-path kernels (DESIGN.md §14): fused dequant-on-gather /
    encode-on-scatter vs their unfused two-pass forms, plus the full tiered
    step both ways.

    Two measurements per pair: wall-clock (informational on CPU — interpret
    mode serialises the per-row DMA emulation, so the TPU win does not show
    here) and the *op count* of the traced computation (``_count_ops``), the
    deterministic interpret-comparable metric the acceptance gate pins: the
    fused form must need ≤ 1.0x the ops of the two-pass form, because it IS
    the two-pass pipeline minus the intermediate materialisation."""
    from repro.buffer import tiered as tiered_mod
    from repro.kernels import ops

    n = 5 if smoke else 15
    r_rows, l = (256, 128) if smoke else (1024, 512)
    s_rows, c_rows = 32, 24
    key = jax.random.PRNGKey(42)
    # int8 tables in the lane-dense record layout [R, W, 128] (core.compression)
    w = l // 128
    q_table = jax.random.randint(key, (r_rows, w, 128), -127, 128, dtype=jnp.int8)
    scales = jax.random.uniform(jax.random.fold_in(key, 1), (r_rows, 1),
                                minval=1e-3, maxval=2.0)
    rows_s = jax.random.randint(jax.random.fold_in(key, 2), (s_rows,), 0, r_rows)
    x = jax.random.normal(jax.random.fold_in(key, 3), (c_rows, w, 128))
    rows_c = jax.random.randint(jax.random.fold_in(key, 4), (c_rows,), -1, r_rows)

    # --- gather+dequant: two-pass (gather int8 -> full-width dequant) vs fused
    @jax.jit
    def gather_unfused(qt, st, rows):
        idx = jnp.clip(rows, 0, qt.shape[0] - 1)
        q = qt[idx]
        return ops.dequantize(q.reshape(q.shape[0], -1), st[idx]).reshape(q.shape)

    gather_fused = ops.gather_dequant
    g_un_us = _time(gather_unfused, q_table, scales, rows_s, n=n)
    g_fu_us = _time(gather_fused, q_table, scales, rows_s, n=n)
    g_un_ops = _count_ops(jax.make_jaxpr(gather_unfused)(q_table, scales, rows_s).jaxpr)
    g_fu_ops = _count_ops(jax.make_jaxpr(gather_fused)(q_table, scales, rows_s).jaxpr)
    g_ratio = g_fu_ops / g_un_ops

    # --- encode+scatter: two-pass (quantize -> scatter both tables) vs fused
    @jax.jit
    def scatter_unfused(qt, st, xv, rows):
        q, s = ops.quantize(xv.reshape(xv.shape[0], -1))
        safe = jnp.where(rows >= 0, rows, qt.shape[0])
        return (qt.at[safe].set(q.reshape(xv.shape), mode="drop"),
                st.at[safe].set(s, mode="drop"))

    scatter_fused = ops.encode_scatter
    s_un_us = _time(lambda *a: scatter_unfused(*a)[0], q_table, scales, x, rows_c, n=n)
    s_fu_us = _time(lambda *a: scatter_fused(*a)[0], q_table, scales, x, rows_c, n=n)
    s_un_ops = _count_ops(jax.make_jaxpr(scatter_unfused)(q_table, scales, x, rows_c).jaxpr)
    s_fu_ops = _count_ops(jax.make_jaxpr(scatter_fused)(q_table, scales, x, rows_c).jaxpr)
    s_ratio = s_fu_ops / s_un_ops

    # the acceptance pin: fusion must never need MORE passes than two-pass
    for name, ratio in (("gather+dequant", g_ratio), ("encode+scatter", s_ratio)):
        if ratio > 1.0:
            raise RuntimeError(
                f"fused {name} needs {ratio:.2f}x the ops of its unfused "
                f"two-pass form — fusion is supposed to REMOVE the "
                f"intermediate pass (DESIGN.md §14)")

    # --- full tiered step, XLA chain vs fused dispatch (bit-identical results)
    spec = {"x": jax.ShapeDtypeStruct((l,), jnp.float32),
            "labels": jax.ShapeDtypeStruct((), jnp.int32),
            "task": jax.ShapeDtypeStruct((), jnp.int32)}
    state = tiered_mod.init_tiered(spec, num_buckets=4, hot_slots=8,
                                   cold_slots=32, stage_rows=c_rows)
    items = {"x": x, "labels": jnp.zeros((c_rows,), jnp.int32),
             "task": jnp.zeros((c_rows,), jnp.int32)}
    labels = jax.random.randint(jax.random.fold_in(key, 5), (c_rows,), 0, 4)
    step_xla = jax.jit(lambda st, k: tiered_mod.tiered_update(
        st, items, labels, k, c_rows))
    step_fused = jax.jit(lambda st, k: tiered_mod.tiered_update(
        st, items, labels, k, c_rows, fused=True))
    # warm the cold tier so the flush actually encodes
    for i in range(3):
        state = step_xla(state, jax.random.PRNGKey(i))
    t_xla_us = _time(lambda st, k: step_xla(st, k).cold.counts, state, key, n=n)
    t_fu_us = _time(lambda st, k: step_fused(st, k).cold.counts, state, key, n=n)

    writer.row("fig6/kernel_gather_unfused", f"{g_un_us:.0f}", f"ops={g_un_ops}")
    writer.row("fig6/kernel_gather_fused", f"{g_fu_us:.0f}",
               f"ops={g_fu_ops},vs_unfused={g_ratio:.3f}(gate<=1.0)")
    writer.row("fig6/kernel_scatter_unfused", f"{s_un_us:.0f}", f"ops={s_un_ops}")
    writer.row("fig6/kernel_scatter_fused", f"{s_fu_us:.0f}",
               f"ops={s_fu_ops},vs_unfused={s_ratio:.3f}(gate<=1.0)")
    writer.row("fig6/kernel_tiered_step_xla", f"{t_xla_us:.0f}", "")
    writer.row("fig6/kernel_tiered_step_fused", f"{t_fu_us:.0f}",
               f"vs_xla={t_fu_us / t_xla_us:.3f}(informational_on_cpu)")
    return {
        "kernel_gather_unfused_us": round(g_un_us, 1),
        "kernel_gather_fused_us": round(g_fu_us, 1),
        "kernel_gather_ops_vs_unfused": round(g_ratio, 4),
        "kernel_scatter_unfused_us": round(s_un_us, 1),
        "kernel_scatter_fused_us": round(s_fu_us, 1),
        "kernel_scatter_ops_vs_unfused": round(s_ratio, 4),
        "kernel_tiered_step_xla_us": round(t_xla_us, 1),
        "kernel_tiered_step_fused_us": round(t_fu_us, 1),
    }


def _sync_vs_pipelined(h, rcfg, params, key, n=30):
    """Per-step wall-clock (including host-side load) of the blocking sync step vs
    the split-dispatch pipelined step on identical configs and data."""
    rcfg_sync = RehearsalConfig(num_buckets=rcfg.num_buckets,
                                slots_per_bucket=rcfg.slots_per_bucket,
                                num_representatives=rcfg.num_representatives,
                                num_candidates=rcfg.num_candidates, mode="sync")

    def load(s):
        return {k: jnp.asarray(v) for k, v in
                h.stream.batch(0, h.batch_size, s).items()}

    # --- sync: the exchange gates the loss, every component on the critical path
    step_sync = make_cl_step(h.loss_fn, h.opt_update, rcfg_sync,
                             strategy="rehearsal", exchange="local",
                             label_field="label", donate=False)
    carry = init_carry(params, h.opt_init(params), h.item_spec, rcfg_sync,
                       label_field="label")
    carry, m = step_sync(carry, load(0), key)  # compile
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for s in range(n):
        batch = load(s)
        carry, m = step_sync(carry, batch, jax.random.fold_in(key, s))
        float(m["loss"])  # block: waits for update + exchange + train
    sync_us = 1e6 * (time.perf_counter() - t0) / n

    # --- pipelined: loss depends only on the train program; the issue program
    # (Alg-1 + sample) executes while the host loads the next batch
    train_half, issue_half = make_pipelined_halves(
        h.loss_fn, h.opt_update, rcfg_sync, exchange="local", label_field="label")
    c0 = init_carry(params, h.opt_init(params), h.item_spec, rcfg_sync,
                    label_field="label")
    p, opt, buf, pipe = c0.params, c0.opt, c0.buffer, c0.pipe
    batch = load(0)
    p, opt, m = train_half(p, opt, pipe, batch)  # compile both programs
    # warm-up key off the timing loop's fold_in(key, 0..n-1) lineage
    buf, pipe = issue_half(buf, pipe, batch, jax.random.fold_in(key, n))
    jax.block_until_ready((m["loss"], buf.counts))
    batch = load(0)
    t0 = time.perf_counter()
    for s in range(n):
        p, opt, m = train_half(p, opt, pipe, batch)
        buf, pipe = issue_half(buf, pipe, batch, jax.random.fold_in(key, s))
        batch = load(s + 1)  # host load overlaps the queued issue program
        float(m["loss"])  # blocks on the train program only
    pipe_us = 1e6 * (time.perf_counter() - t0) / n
    return sync_us, pipe_us


def _obs_overhead(h, rcfg, params, key, n=30, trials=3):
    """Paired pipelined-step timing with telemetry off vs on.

    The same split-dispatch loop as ``_sync_vs_pipelined``'s pipelined arm,
    built twice — ``make_pipelined_halves(obs=None)`` vs
    ``obs=ObsConfig(enabled=True)`` — and timed in interleaved off/on pairs so
    host drift hits both arms equally; best-of-``trials`` per arm, where each
    trial reports its *per-step minimum* (the quietest step is the floor —
    shared-box noise spikes are ms-scale while the true obs cost is µs-scale,
    so means drown the signal). The ratio of minima is the obs latency cost,
    and this function IS the gate: the telemetry contract says jit-safe gauges
    ride existing outputs for (almost) free, so anything past 1.03x fails the
    benchmark rather than shipping a silent slowdown."""
    from repro.configs.base import ObsConfig

    def build(obs):
        return make_pipelined_halves(h.loss_fn, h.opt_update, rcfg,
                                     exchange="local", label_field="label",
                                     obs=obs)

    halves_off = build(None)
    halves_on = build(ObsConfig(enabled=True))

    def load(s):
        return {k: jnp.asarray(v) for k, v in
                h.stream.batch(0, h.batch_size, s).items()}

    def timed(halves):
        train_half, issue_half = halves
        c0 = init_carry(params, h.opt_init(params), h.item_spec, rcfg,
                        label_field="label")
        p, opt, buf, pipe = c0.params, c0.opt, c0.buffer, c0.pipe
        batch = load(0)
        p, opt, m = train_half(p, opt, pipe, batch)  # compile (cached later)
        buf, pipe = issue_half(buf, pipe, batch, key)
        jax.block_until_ready((m["loss"], buf.counts))
        batch = load(0)
        best = float("inf")
        for s in range(n):
            t0 = time.perf_counter()
            p, opt, m = train_half(p, opt, pipe, batch)
            # _obs_overhead *times* real train steps; the RNG here drives the
            # measured workload, not telemetry (RPL041 name-heuristic misfire)
            buf, pipe = issue_half(buf, pipe, batch, jax.random.fold_in(key, s))  # replint: disable=RPL041
            batch = load(s + 1)
            float(m["loss"])
            best = min(best, time.perf_counter() - t0)
        return 1e6 * best

    off, on = [], []
    for _ in range(trials):
        off.append(timed(halves_off))
        on.append(timed(halves_on))
    off_us, on_us = min(off), min(on)
    ratio = on_us / off_us
    if ratio > 1.03:
        raise RuntimeError(
            f"obs overhead gate: pipelined step with telemetry is {ratio:.3f}x "
            f"the obs-off step (best-of-{trials}, {on_us:.0f}us vs "
            f"{off_us:.0f}us); budget is 1.03x — see DESIGN.md §11")
    return off_us, on_us, ratio


def _chaos_obs(h, params, key, out_dir="obs_fig6", smoke=False):
    """Chaos run under full telemetry; validates the emitted artifacts.

    The tiered fused step (``make_cl_step``) steps inside a ``ResilientLoop``
    whose failure hook kills step 2 once (≥1 restart event + restore span),
    then a 2-worker tiered carry is scaled down through ``scale_carry`` (≥1
    reshard event + span). The compiled step's HLO must carry the named
    scope of each of its stages (``repro.obs.scopes``), the resulting
    ``trace.json`` must validate against the Chrome trace-event schema and
    ``events.jsonl`` must carry the restart and reshard kinds — the
    acceptance contract for the telemetry layer, enforced here so CI reruns
    it on every benchmark pass."""
    import shutil

    from repro import obs as obs_mod
    from repro.checkpoint import CheckpointManager
    from repro.configs.base import ObsConfig, RehearsalConfig
    from repro.obs import read_events, validate_trace
    from repro.obs.scopes import scopes_in_hlo
    from repro.runtime.autoscale import scale_carry
    from repro.runtime.fault_tolerance import InjectedFailure, ResilientLoop

    steps = 4 if smoke else 6
    shutil.rmtree(out_dir, ignore_errors=True)
    obs_mod.configure(out_dir)
    try:
        rcfg = RehearsalConfig(num_buckets=h.num_tasks, slots_per_bucket=8,
                               num_representatives=4, num_candidates=8,
                               mode="async", tiering="host", hot_slots=8,
                               cold_slots=16)
        step = make_cl_step(h.loss_fn, h.opt_update, rcfg, strategy="rehearsal",
                            exchange="local", label_field="label", donate=False,
                            obs=ObsConfig(enabled=True))
        carry = init_carry(params, h.opt_init(params), h.item_spec, rcfg,
                           label_field="label")
        loop = ResilientLoop(
            step_fn=step,
            ckpt=CheckpointManager(os.path.join(out_dir, "ckpt")),
            checkpoint_every=2, max_restarts=2, backoff_base=0.0)
        fired = []

        def chaos(step):
            if step == 2 and not fired:
                fired.append(step)
                raise InjectedFailure("chaos: injected node failure")

        def batch_fn(s):
            return {k: jnp.asarray(v) for k, v in
                    h.stream.batch(0, h.batch_size, s).items()}

        # the stages are named inside the one fused program (no sanitizer
        # wrapper around this build: it is lowered, never run)
        hlo = make_cl_step(
            h.loss_fn, h.opt_update, rcfg, strategy="rehearsal",
            exchange="local", label_field="label", donate=False,
            sanitize=False).lower(carry, batch_fn(0), key).compile().as_text()
        # one device, local draw: every stage but the exchange
        missing = ({"train", "optimizer", "buffer_update", "buffer_sample",
                    "augment"} - scopes_in_hlo(hlo))
        if missing:
            raise RuntimeError(f"compiled chaos step missing step scopes: "
                               f"{sorted(missing)}")

        carry, _, restarts = loop.run(carry, batch_fn, key, steps,
                                      failure_hook=chaos)

        # elastic excursion on a 2-worker tiered carry: reshard span + event
        dist = init_carry(params, h.opt_init(params), h.item_spec, rcfg,
                          label_field="label", n_dp=2)
        _, reshard_s = scale_carry(dist, 1)

        tracer, bus = obs_mod.get_tracer(), obs_mod.get_event_bus()
        for kind in ("restart", "reshard", "checkpoint_save",
                     "checkpoint_restore"):
            if kind not in bus.kinds():
                raise RuntimeError(f"chaos event log missing kind {kind!r}")
        if restarts < 1:
            raise RuntimeError("chaos run recorded no restart")
        trace_events = len(tracer.events())
        event_lines = len(bus.events)
    finally:
        obs_mod.shutdown()  # writes trace.json, closes events.jsonl

    with open(os.path.join(out_dir, "trace.json")) as f:
        problems = validate_trace(json.load(f))
    if problems:
        raise RuntimeError(f"trace.json failed schema validation: {problems}")
    on_disk = read_events(os.path.join(out_dir, "events.jsonl"))
    kinds = {e["kind"] for e in on_disk}
    if not {"restart", "reshard"} <= kinds:
        raise RuntimeError(f"events.jsonl missing restart/reshard: {kinds}")
    return {"restarts": int(restarts), "reshard_s": float(reshard_s),
            "restore_s": float(loop.stats["restore_seconds"]),
            "trace_events": trace_events, "event_lines": event_lines}


if __name__ == "__main__":
    import argparse

    from repro.utils.logging import CSVWriter

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json", default="BENCH_fig6.json")
    args = ap.parse_args()
    run(CSVWriter(), smoke=args.smoke, json_path=args.json)
