#!/usr/bin/env python3
"""Smoke run of the continual trainer on a TPU, through its normal entry points.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: the sharded LM path only

One process drives the chip(s). Phases on one chip:

  kernels     the fused tiered kernels at the ImageNet row width, checked
              against kernels/ref.py and the quantize kernel.
  cnn_async   ResNet-50 at 224 px, 1000 classes, class-incremental 4 x 250,
              b=56 r=7 c=14, flat 4 x 1024-slot buffer, ``ContinualTrainer``.
  cnn_tiered  the same run with a tiered buffer (hot 256 / cold 768 slots
              per bucket), once with the fused kernels and once without: the
              sampled-representative checksums and fills must be identical.
              It takes enough steps per task (24 x 14 candidates > 256) for
              the hot tier to overflow, so both kernels move real rows. The
              carry backend keeps both tiers in HBM, the table the fused
              kernels address, and must report that placement.
  buffer_host the distributed rehearsal buffer (``make_sharded_update``) at
              the ImageNet record width with 4 x 768 cold slots in
              pinned_host memory, against the same updates with the cold tier
              in HBM: identical representatives, counts and cold records.
  lm_async    ``repro.launch.train.main`` on SmolLM-135M (full config),
              seq 2048, global batch 8, flat and tiered; the tiered cold tier
              must be in pinned_host memory.
  lm_parity   tiered carry backend (cold tier in HBM) against the pjit backend
              (cold tier in pinned_host) at a small size: identical
              fingerprints.

``--chips 4`` runs ``lm_async`` on a 4x1 mesh with the full exchange, flat and
tiered, and the same global batch on a 1x1 mesh of the first chip: every
buffer leaf must be sharded over four chips, every worker's buffer must fill,
and the step-0 loss must match the one-chip run. A pair of runs without
rehearsal (plain data parallelism on 4x1 and 1x1) must also agree on the loss
after the first update, which depends on the gradient of the whole batch.

The last line of standard output is the result as JSON. Without a TPU the
script exits nonzero before any phase. Steps/s figures are smoke readings, not
benchmarks.
"""
import argparse
import functools
import json
import math
import os
import sys
import time


CNN_STEPS = 3  # cnn_async steps per task
# cnn_tiered steps per task: 24 x 14 candidates overflow the 256 hot slots
TIERED_STEPS = 24
# Relative gap allowed between a 4x1 and a 1x1 loss: room for bfloat16
# reduction order (the step-0 gap read on a v5e is about 4e-6), far below
# what training on one worker's shard or a wrong reduction would move.
LOSS_RTOL = 1e-4


def _fail(msg):
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(2)


def check(cond, msg):
    if not cond:
        raise RuntimeError(f"check failed: {msg}")


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

    def mark(self):
        return self.secs, self.count, self.hits


def run_phase(clock, name, fn):
    s0, n0, h0 = clock.mark()
    t0 = time.perf_counter()
    info = fn()
    s1, n1, h1 = clock.mark()
    line = {"phase": name, "ok": True,
            "wall_s": round(time.perf_counter() - t0, 2),
            "compile_s": round(s1 - s0, 2), "compiles": n1 - n0,
            "cache_hits": h1 - h0}
    line.update(info)
    print(json.dumps(line), flush=True)


def steps_per_s(res, steps_per_task):
    """Smoke reading over the tasks after the first (the first compiles)."""
    later = res.task_runtimes[1:]
    return round(steps_per_task * len(later) / sum(later), 3) if later else None


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def phase_kernels():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    w, rows_n = 224 * 224 * 3 // 128, 64
    key = jax.random.PRNGKey(0)
    q = jax.random.randint(key, (rows_n, w, 128), -127, 128, dtype=jnp.int8)
    scales = jax.random.uniform(jax.random.fold_in(key, 1), (rows_n, 1),
                                minval=1e-3, maxval=2.0)
    samp = jax.random.randint(jax.random.fold_in(key, 2), (7,), 0, rows_n)
    got = ops.gather_dequant(q, scales, samp)
    want = ref.gather_dequant_rows_ref(q, scales, samp)
    check(np.array_equal(np.asarray(got), np.asarray(want)),
          "gather_dequant differs from the gather-then-dequantize oracle")

    x = jax.random.normal(jax.random.fold_in(key, 3), (14, w, 128)) * 3
    dest = jnp.arange(14, dtype=jnp.int32) * 3  # distinct rows
    gq, gs = ops.encode_scatter(q, scales, x, dest)
    qq, qs = ops.quantize(x.reshape(14, -1))
    check(np.array_equal(np.asarray(gq)[np.asarray(dest)],
                         np.asarray(qq).reshape(14, w, 128)),
          "encode_scatter rows differ from the quantize kernel")
    check(np.array_equal(np.asarray(gs)[np.asarray(dest)], np.asarray(qs)),
          "encode_scatter scales differ from the quantize kernel")
    untouched = np.setdiff1d(np.arange(rows_n), np.asarray(dest))
    check(np.array_equal(np.asarray(gq)[untouched], np.asarray(q)[untouched]),
          "encode_scatter touched rows it was not given")
    return {"row_width": w * 128, "table_rows": rows_n}


def _cnn_run(scenario, steps, **rkw):
    from repro.configs import resnet50_cl
    from repro.configs.base import (RehearsalConfig, RunConfig,
                                    ScenarioConfig, TrainConfig)
    from repro.scenario import ContinualTrainer

    run = RunConfig(
        model=resnet50_cl.full(),
        train=TrainConfig(compute_dtype="bfloat16"),
        rehearsal=RehearsalConfig(num_buckets=4, num_representatives=7,
                                  num_candidates=14, mode="async",
                                  label_field="label", task_field="task", **rkw),
        scenario=ScenarioConfig(num_tasks=4, classes_per_task=250,
                                image_size=224, steps_per_epoch=steps,
                                batch_size=56, auto_defaults=False))
    return ContinualTrainer(run, scenario).fit()


def _check_cnn(res, name):
    import numpy as np

    losses = [h["loss"] for h in res.history]
    check(all(math.isfinite(v) for v in losses), f"{name}: non-finite loss")
    fill0 = [h["buffer_fill"] for h in res.history if h["task"] == 0][-1]
    check(fill0 > 0, f"{name}: empty buffer after task 0")
    acc = res.accuracy_matrix
    check(acc.shape == (4, 4) and np.all(np.isfinite(acc))
          and np.all((acc >= 0) & (acc <= 1)), f"{name}: bad Eq.-1 matrix {acc}")
    return losses, fill0


def make_cnn_scenario():
    from repro.configs.base import ScenarioConfig
    from repro.scenario import ClassIncremental

    return ClassIncremental(ScenarioConfig(num_tasks=4, classes_per_task=250,
                                           image_size=224))


def phase_cnn_async(scenario, steps):
    res = _cnn_run(scenario, steps, slots_per_bucket=1024)
    losses, fill0 = _check_cnn(res, "cnn_async")
    return {"model": "resnet50 224px 1000 classes", "tasks": 4,
            "steps_per_task": steps, "batch": 56, "reps": 7, "candidates": 14,
            "buffer": "flat 4x1024", "losses": losses, "fill_after_task0": fill0,
            "final_accuracy": res.final_accuracy,
            "steps_per_s_smoke": steps_per_s(res, steps)}


def phase_cnn_tiered(scenario, steps):
    prints = {}
    for fused in (True, False):
        res = _cnn_run(scenario, steps, tiering="host", hot_slots=256,
                       cold_slots=768, fused_kernels=fused)
        _check_cnn(res, f"cnn_tiered fused={fused}")
        prints[fused] = [(h["rep_checksum"], h["buffer_fill"]) for h in res.history]
        cold = int(res.buffer.cold.counts.sum())
        check(cold > 0, f"cnn_tiered fused={fused}: nothing was demoted")
        placement = res.step_meta["cold_placement"]
        check(placement == "device",
              f"cnn_tiered fused={fused}: carry backend reports {placement}")
        del res
    check(prints[True] == prints[False],
          f"fused and unfused fingerprints differ: {prints[True]} vs {prints[False]}")
    return {"buffer": "tiered hot 256 / cold 768 per bucket, both in HBM",
            "tasks": 4, "steps_per_task": steps, "fingerprints": prints[True],
            "cold_fill": cold, "cold_placement": placement,
            "fused_equals_unfused": True}


def run_buffer_host(image_size=224, tasks=4, steps=10, hot=16, cold=768):
    """``steps`` updates per task of the distributed tiered buffer on a 1x1
    mesh (cold records in the platform's cold memory) and of the same store
    on one device with the cold tier in HBM; every sampled representative,
    the counts and the cold records must agree bit for bit. 16 hot slots
    per bucket make the hot tier overflow within a task."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.buffer import tiered
    from repro.configs.base import RehearsalConfig
    from repro.core import distributed as dist
    from repro.launch.mesh import make_mesh
    from repro.parallel.sharding import dp_axes

    b, classes = 14, 250
    rcfg = RehearsalConfig(num_buckets=tasks, num_representatives=7,
                           num_candidates=b, mode="async", tiering="host",
                           hot_slots=hot, cold_slots=cold,
                           label_field="label", task_field="task")
    item_s = {"images": jax.ShapeDtypeStruct((image_size, image_size, 3),
                                             jnp.float32),
              "label": jax.ShapeDtypeStruct((), jnp.int32),
              "task": jax.ShapeDtypeStruct((), jnp.int32)}
    mesh = make_mesh((1, 1), ("data", "model"))
    dp = dp_axes(mesh)
    init = lambda: dist.init_distributed_from_config(item_s, rcfg, 1)
    state_sh = tiered.cold_shardings(jax.eval_shape(init), mesh, dp)
    placement = tiered.resolve_cold_placement(mesh.devices.flat)
    host = jax.jit(init, out_shardings=state_sh)()
    ref = jax.jit(lambda: jax.tree_util.tree_map(lambda x: x[0], init()))()

    sharded = dist.make_sharded_update(mesh, dp, rcfg, exchange="local")
    host_step = jax.jit(sharded, out_shardings=(state_sh, None, None),
                        donate_argnums=0)

    @functools.partial(jax.jit, donate_argnums=0)
    def ref_step(state, items, labels, key):
        # the per-worker body of make_sharded_update, on one device
        new, pending = dist.issue_sample(state, items, labels,
                                         jax.random.fold_in(key, 0), rcfg)
        reps, valid = dist.consume_reps(pending, "label")
        return new, reps, valid

    @jax.jit
    def batch(key, task):
        k_im, k_lab = jax.random.split(key)
        labels = task * classes + jax.random.randint(k_lab, (b,), 0, classes)
        return {"images": jax.random.normal(k_im, (b,) + item_s["images"].shape),
                "label": labels, "task": jnp.full((b,), task, jnp.int32)}

    root = jax.random.PRNGKey(0)
    with jax.set_mesh(mesh):
        for t in range(tasks * steps):
            task = t // steps
            key = jax.random.fold_in(root, t)
            items = batch(jax.random.fold_in(key, 1), task)
            host, reps, valid = host_step(host, items, items["task"], key)
            ref, rreps, rvalid = ref_step(ref, items, items["task"], key)
            same = (np.array_equal(np.asarray(valid[0]), np.asarray(rvalid))
                    and all(np.array_equal(np.asarray(x[0]), np.asarray(y))
                            for x, y in zip(jax.tree_util.tree_leaves(reps),
                                            jax.tree_util.tree_leaves(rreps))))
            check(same, f"buffer_host: step {t} representatives differ")
    for x, y in zip(jax.tree_util.tree_leaves(host), jax.tree_util.tree_leaves(ref)):
        check(np.array_equal(np.asarray(x)[0], np.asarray(y)),
              f"buffer_host: a {x.shape} leaf differs after {tasks * steps} steps")
    kinds = sorted({x.sharding.memory_kind
                    for x in jax.tree_util.tree_leaves(host.cold.data)
                    if tiered.host_resident(x.shape[3:])})
    cold_fill = int(np.asarray(host.cold.counts).sum())
    check(cold_fill > 0, "buffer_host: nothing was demoted")
    return {"record": list(item_s["images"].shape), "hot_slots": hot,
            "cold_slots": cold, "buckets": tasks, "steps": tasks * steps,
            "cold_placement": placement, "cold_record_memory": kinds,
            "cold_fill": cold_fill, "host_equals_hbm": True}


def phase_buffer_host():
    out = run_buffer_host()
    check(out["cold_placement"] == "pinned_host",
          f"buffer_host: cold placement {out['cold_placement']}")
    check(out["cold_record_memory"] == ["pinned_host"],
          f"buffer_host: cold records in {out['cold_record_memory']}")
    return out


def _lm_argv(mesh, tiering, mode="async", tasks=2):
    argv = ["--arch", "smollm-135m", "--mesh", mesh, "--mode", mode,
            "--tasks", str(tasks), "--steps-per-task", "3", "--seq-len", "2048",
            "--global-batch", "8", "--tiering", tiering, "--exchange", "full",
            "--log-every", "0"]
    if tiering != "off":
        # 4 hot slots per bucket: even 2 rows per worker and step overflow
        # them within a task, so rows reach the host-memory cold tier
        argv += ["--hot-slots", "4"]
    return argv


def _check_lm(res, name):
    import jax.numpy as jnp

    losses = [h["loss"] for h in res.history]
    check(all(math.isfinite(v) for v in losses), f"{name}: non-finite loss")
    check(res.history[-1]["buffer_fill"] > 0, f"{name}: empty buffer")
    out = {"losses": losses, "fill": res.history[-1]["buffer_fill"],
           "steps_per_s_smoke": steps_per_s(res, 3)}
    if res.step_meta["tiering"] != "off":
        placement = res.step_meta["cold_placement"]
        check(placement == "pinned_host", f"{name}: cold placement {placement}")
        kind = res.buffer.cold.data["tokens"]["raw"].sharding.memory_kind
        check(kind == "pinned_host", f"{name}: cold token rows in {kind}")
        cold = int(jnp.sum(res.buffer.cold.counts))
        check(cold > 0, f"{name}: nothing was demoted to the cold tier")
        out.update(cold_placement=placement, cold_fill=cold)
    return out


def phase_lm_async():
    from repro.launch import train

    out = {"model": "smollm-135m full", "seq_len": 2048, "global_batch": 8,
           "mesh": "1x1"}
    for tiering in ("off", "host"):
        res = train.main(_lm_argv("1x1", tiering))
        out["flat" if tiering == "off" else "tiered"] = _check_lm(
            res, f"lm_async tiering={tiering}")
        del res
    return out


def phase_lm_parity():
    from repro.configs import get_reduced
    from repro.configs.base import (RehearsalConfig, RunConfig,
                                    ScenarioConfig, ShapeConfig, TrainConfig)
    from repro.launch.mesh import make_mesh
    from repro.scenario import ContinualTrainer, TokenClassIncremental

    run = RunConfig(
        model=get_reduced("smollm-135m"), shape=ShapeConfig("parity", 128, 8, "train"),
        train=TrainConfig(optimizer="adamw", peak_lr=1e-3, warmup_steps=5,
                          linear_scaling=False, compute_dtype="float32"),
        rehearsal=RehearsalConfig(num_buckets=2, slots_per_bucket=4,
                                  tiering="host", hot_slots=4, cold_slots=8,
                                  num_representatives=3, num_candidates=8,
                                  mode="async"),
        scenario=ScenarioConfig(name="class_incremental", modality="tokens",
                                num_tasks=2, steps_per_epoch=6, batch_size=8,
                                vocab_size=128, seq_len=128, auto_defaults=False))
    sc = TokenClassIncremental(run.scenario)
    mesh = make_mesh((1, 1), ("data", "model"))
    pjit_res = ContinualTrainer(run, sc, mesh=mesh, exchange="local").fit()
    carry_res = ContinualTrainer(run, sc).fit()
    pj = [(h["rep_checksum"], h["buffer_fill"]) for h in pjit_res.history]
    ca = [(h["rep_checksum"], h["buffer_fill"]) for h in carry_res.history]
    check(pjit_res.step_meta["cold_placement"] == "pinned_host",
          "lm_parity: pjit cold tier not in pinned_host")
    check(pj == ca, f"lm_parity: pjit {pj} != carry {ca}")
    check(max(f for _, f in pj) > 2 * 4, "lm_parity: hot tier never overflowed")
    return {"fingerprints": pj, "pjit_equals_carry": True}


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------


def phase_lm_four_chips():
    import jax
    import numpy as np

    from repro.launch import train

    def close(a, b):
        return abs(a - b) <= LOSS_RTOL * abs(b)

    # no rehearsal: plain data parallelism at every step, so the loss after
    # the first update (step 1) depends on the gradient of the whole batch
    plain = {}
    for mesh in ("1x1", "4x1"):
        res = train.main(_lm_argv(mesh, "off", mode="off", tasks=1))
        plain[mesh] = [h["loss"] for h in res.history[:2]]
        del res
    for step in (0, 1):
        check(close(plain["4x1"][step], plain["1x1"][step]),
              f"no rehearsal: step-{step} loss {plain['4x1'][step]} on 4x1 vs "
              f"{plain['1x1'][step]} on 1x1")
    # the rehearsal runs' reference: the same stream (the task count sets each
    # task's vocabulary range) on one chip
    one = train.main(_lm_argv("1x1", "off"))
    loss1 = one.history[0]["loss"]
    del one
    out = {"model": "smollm-135m full", "seq_len": 2048, "global_batch": 8,
           "mesh": "4x1", "exchange": "full", "loss_rtol": LOSS_RTOL,
           "plain_losses_1x1": plain["1x1"], "plain_losses_4x1": plain["4x1"],
           "step0_loss_1x1": loss1}
    for tiering in ("off", "host"):
        name = f"lm_4x1 tiering={tiering}"
        res = train.main(_lm_argv("4x1", tiering))
        info = _check_lm(res, name)
        check(res.step_meta["n_dp"] == 4, f"{name}: n_dp={res.step_meta['n_dp']}")
        for leaf in jax.tree_util.tree_leaves(res.buffer):
            devs = {s.device for s in leaf.addressable_shards}
            check(len(devs) == 4, f"{name}: a buffer leaf {leaf.shape} sits on "
                                  f"{len(devs)} device(s)")
        buf = res.buffer
        counts = (np.asarray(buf.hot.counts) + np.asarray(buf.cold.counts)
                  if tiering == "host" else np.asarray(buf.counts))
        per_worker = counts.reshape(4, -1).sum(axis=1).tolist()
        check(all(c > 0 for c in per_worker), f"{name}: fills {per_worker}")
        loss4 = res.history[0]["loss"]
        # step 0 trains on no representatives: plain data parallelism, equal
        # to the one-chip step up to bfloat16 reduction order
        check(close(loss4, loss1), f"{name}: step-0 loss {loss4} vs one chip {loss1}")
        info.update(worker_fill=per_worker, step0_loss=loss4)
        out["flat" if tiering == "off" else "tiered"] = info
        del res, buf
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded LM path on four chips")
    ap.add_argument("--only", default="",
                    help="comma list of one-chip phases to run (default: all)")
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        _fail(f"no TPU: JAX's backend is {jax.default_backend()!r}")
    devices = jax.devices()
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} but JAX sees {len(devices)} device(s)")

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "src"))
    from repro.utils.platform import enable_compile_cache

    cache_dir = enable_compile_cache()
    clock = CompileClock(jax)
    print(json.dumps({"device_kind": devices[0].device_kind,
                      "devices": len(devices), "compile_cache": cache_dir}),
          flush=True)

    if args.chips == 4:
        run_phase(clock, "lm_async_4chips", phase_lm_four_chips)
    else:
        scenario = []  # the CNN stream, shared by the CNN phases

        def cnn(phase, steps):
            def run():
                if not scenario:
                    scenario.append(make_cnn_scenario())
                return phase(scenario[0], steps)
            return run

        phases = {
            "kernels": phase_kernels,
            "cnn_async": cnn(phase_cnn_async, CNN_STEPS),
            "cnn_tiered": cnn(phase_cnn_tiered, TIERED_STEPS),
            "buffer_host": phase_buffer_host,
            "lm_async": phase_lm_async,
            "lm_parity": phase_lm_parity,
        }
        only = [p for p in args.only.split(",") if p] or list(phases)
        unknown = set(only) - set(phases)
        if unknown:
            _fail(f"unknown phases {sorted(unknown)}; choose from {list(phases)}")
        for name in only:
            if not name.startswith("cnn"):
                scenario.clear()  # release the CNN eval sets first
            run_phase(clock, name, phases[name])

    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
