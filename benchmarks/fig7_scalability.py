"""Paper Fig. 7: scalability + elasticity — overhead, autoscaling, restart cost.

Physical strong-scaling is unmeasurable on one CPU core, so this benchmark
verifies the paper's scale-invariant claims that ARE measurable here (fake
devices, one subprocess so XLA_FLAGS is set before the first jax import):

  (a) the rehearsal overhead fraction (rehearsal step time / plain step time)
      does not grow with N — the paper's shrinking-gap observation;
  (b) an autoscaling excursion (TrafficSignal → Autoscaler → scale_carry,
      grow 2→4 and shrink 4→2 live) preserves every stored representative up
      to aggregate capacity, and accuracy@N stays in family with a flat
      2-worker fleet — the §VII elasticity claim under an operational driver;
      reshard latency is reported for both directions;
  (c) restart cost: a ResilientLoop run with one injected failure — time spent
      in checkpoint restore vs total wall clock (the preemption-recovery cost
      the runtime adds);
  (d) from the compiled dry-run artifacts: per-chip rehearsal-exchange
      collective bytes are O(r·item) and stay flat from 256 to 512 chips (the
      all_to_all volume argument of DESIGN.md §2).

Emits ``BENCH_fig7.json`` ({"bench", "smoke", "rows"}) for the perf
trajectory; ``--smoke`` shrinks step counts for CI.
"""
import json
import os
import subprocess
import sys

CHILD = """
import json, os, tempfile, time
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import Mesh

from benchmarks.common import VisionCL
from repro.configs.base import RehearsalConfig
from repro.checkpoint.manager import CheckpointManager
from repro.core import init_carry, make_cl_step
from repro.models.resnet import init_cnn
from repro.runtime import (Autoscaler, InjectedFailure, ResilientLoop,
                           TrafficSignal)
from repro.runtime.autoscale import scale_carry

SMOKE = os.environ.get("REPRO_FIG7_SMOKE") == "1"
payload = {}


def submesh(n):
    # explicit device subset: the child owns 4 fake devices, meshes use n <= 4
    return Mesh(np.array(jax.devices()[:n]).reshape(n, 1), ("data", "model"))


# ---- (a) rehearsal overhead fraction vs N ---------------------------------
h = VisionCL()
params = init_cnn(jax.random.PRNGKey(0), h.ccfg)


def timed(n_dp, strategy, mode, steps):
    rc = RehearsalConfig(num_buckets=h.num_tasks, slots_per_bucket=64,
                         num_representatives=8, num_candidates=14, mode=mode)
    step = make_cl_step(h.loss_fn, h.opt_update, rc, strategy=strategy,
                        mesh=submesh(n_dp) if n_dp > 1 else None,
                        dp_axis="data", label_field="label", donate=False)
    carry = init_carry(params, h.opt_init(params), h.item_spec, rc,
                       n_dp=n_dp, label_field="label")
    bs = h.batch_size * n_dp  # weak scaling: global batch grows with N
    batch = {k: jnp.asarray(v) for k, v in h.stream.batch(0, bs, 0).items()}
    key = jax.random.PRNGKey(0)
    carry, m = step(carry, batch, key)  # compile
    t0 = time.perf_counter()
    for s in range(steps):
        carry, m = step(carry, batch, jax.random.fold_in(key, s))
    jax.block_until_ready(m["loss"])
    return (time.perf_counter() - t0) / steps


steps = 3 if SMOKE else 10
overhead = {}
for n_dp in ((1, 4) if SMOKE else (1, 2, 4)):
    t_plain = timed(n_dp, "incremental", "off", steps)
    t_reh = timed(n_dp, "rehearsal", "async", steps)
    overhead[str(n_dp)] = {"t_plain": t_plain, "t_reh": t_reh,
                           "overhead": (t_reh - t_plain) / t_plain}
payload["overhead"] = overhead

# ---- (b) autoscaling excursion 2 -> 4 -> 2 --------------------------------
ha = VisionCL(num_tasks=3, classes_per_task=3, image_size=8, batch_size=8,
              epochs_per_task=1, steps_per_epoch=(6 if SMOKE else 12))
pa = init_cnn(jax.random.PRNGKey(1), ha.ccfg)
rca = RehearsalConfig(num_buckets=ha.num_tasks, slots_per_bucket=32,
                      num_representatives=8, num_candidates=14, mode="async",
                      policy="reservoir", label_field="label")
_steps = {}


def step_for(n):
    if n not in _steps:
        _steps[n] = make_cl_step(ha.loss_fn, ha.opt_update, rca,
                                 strategy="rehearsal", mesh=submesh(n),
                                 dp_axis="data", label_field="label",
                                 donate=False)
    return _steps[n]


def run_fleet(elastic):
    n = 2
    carry = init_carry(pa, ha.opt_init(pa), ha.item_spec, rca, n_dp=n,
                       label_field="label")
    per_task = ha.epochs_per_task * ha.steps_per_epoch
    half = max(2, per_task // 2)
    # square traffic: low keeps 2 workers in the hysteresis band, high forces
    # a grow to 4; the next low half-period shrinks back (anti-thrash checked)
    signal = TrafficSignal("square", period=2 * half, low=1.4, high=3.9)
    scaler = Autoscaler(min_workers=2, max_workers=4, cooldown_steps=2)
    key = jax.random.PRNGKey(7)
    reshard, trace, gstep = [], [], 0
    for task in range(ha.num_tasks):
        cur = 0
        for _ in range(per_task):
            if elastic:
                target = scaler.observe(gstep, signal.load(gstep), n)
                if target is not None:
                    per_bucket = np.asarray(carry.buffer.counts).sum(axis=0)
                    before = int(per_bucket.sum())
                    # capacity binds per bucket: each pooled bucket keeps at
                    # most target * slots_per_bucket records after the re-deal
                    expect = int(np.minimum(
                        per_bucket, target * rca.slots_per_bucket).sum())
                    carry, secs = scale_carry(carry, target, policy=rca.policy)
                    after = int(np.asarray(carry.buffer.counts).sum())
                    assert after == expect, (before, after, expect)
                    reshard.append({"step": gstep, "from": n, "to": target,
                                    "seconds": secs, "records_before": before,
                                    "records_after": after})
                    n = target
            trace.append(n)
            bs = ha.batch_size * n
            batch = {k: jnp.asarray(v)
                     for k, v in ha.stream.batch(task, bs, cur).items()}
            cur += bs
            carry, m = step_for(n)(carry, batch, jax.random.fold_in(key, gstep))
            gstep += 1
    accs = [ha.eval_fn(carry.params, t) for t in range(ha.num_tasks)]
    return accs, reshard, trace


accs_static, _, _ = run_fleet(False)
accs_elastic, reshard, trace = run_fleet(True)
payload["autoscale"] = {
    "acc_static": accs_static, "acc_elastic": accs_elastic,
    "acc_static_avg": sum(accs_static) / len(accs_static),
    "acc_elastic_avg": sum(accs_elastic) / len(accs_elastic),
    "reshard": reshard,
    "workers_min": min(trace), "workers_max": max(trace),
}

# ---- (c) restart cost: ResilientLoop + one injected failure ---------------
step_r = make_cl_step(ha.loss_fn, ha.opt_update, rca, strategy="rehearsal",
                      label_field="label", donate=False)
carry_r = init_carry(pa, ha.opt_init(pa), ha.item_spec, rca, n_dp=1,
                     label_field="label")
n_steps = 8 if SMOKE else 16
fail_at, fired = n_steps // 2, []


def chaos(step):
    if step == fail_at and not fired:
        fired.append(step)
        raise InjectedFailure(f"injected at step {step}")


def batch_fn(s):
    return {k: jnp.asarray(v) for k, v in
            ha.stream.batch(0, ha.batch_size, s * ha.batch_size).items()}


loop = ResilientLoop(
    step_fn=step_r,
    ckpt=CheckpointManager(tempfile.mkdtemp(prefix="fig7_ckpt_"),
                           async_save=False),
    checkpoint_every=3, max_restarts=2)
t0 = time.perf_counter()
carry_r, hist, restarts = loop.run(carry_r, batch_fn, jax.random.PRNGKey(3),
                                   n_steps, failure_hook=chaos)
payload["restart"] = {"restarts": restarts, "steps": n_steps,
                      "restore_seconds": loop.stats["restore_seconds"],
                      "wall_seconds": time.perf_counter() - t0}

print("PAYLOAD " + json.dumps(payload))
"""


def run(writer, smoke: bool = False, json_path: str = "BENCH_fig7.json"):
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    # a fake-device CPU figure by design: the child never competes with the
    # parent for an accelerator
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(here, "src") + ":" + here
    env["REPRO_FIG7_SMOKE"] = "1" if smoke else "0"
    p = subprocess.run([sys.executable, "-c", CHILD], capture_output=True,
                       text=True, timeout=1800, env=env)
    line = [l for l in p.stdout.splitlines() if l.startswith("PAYLOAD ")]
    if not line:
        raise RuntimeError(f"fig7 child printed no payload (rc={p.returncode}):\n"
                           f"{p.stderr[-2000:]}")
    payload = json.loads(line[0][len("PAYLOAD "):])

    rows = {}
    # (a) overhead fraction vs worker count
    for n, rec in sorted(payload.get("overhead", {}).items(),
                         key=lambda kv: int(kv[0])):
        rows[f"overhead_n{n}"] = round(rec["overhead"], 4)
        writer.row(f"fig7/overhead_n{n}", f"{1e6 * rec['t_reh']:.0f}",
                   f"rehearsal_overhead={rec['overhead']:+.2%}")

    # (b) autoscaled accuracy + reshard latency
    au = payload.get("autoscale")
    if au:
        rows["acc_static"] = round(au["acc_static_avg"], 4)
        rows["acc_elastic"] = round(au["acc_elastic_avg"], 4)
        writer.row("fig7/acc_elastic", f"{au['acc_elastic_avg']:.4f}",
                   f"static_2worker={au['acc_static_avg']:.4f} "
                   f"fleet={au['workers_min']}->{au['workers_max']}"
                   f"->{au['workers_min']}")
        grows = [r["seconds"] for r in au["reshard"] if r["to"] > r["from"]]
        shrinks = [r["seconds"] for r in au["reshard"] if r["to"] < r["from"]]
        # the child asserts after == min(before, aggregate capacity) per event;
        # a grow never truncates, so it must carry every record across
        preserved = all(r["records_after"] == r["records_before"]
                        for r in au["reshard"] if r["to"] > r["from"])
        if grows:
            rows["reshard_grow_s"] = round(max(grows), 4)
            writer.row("fig7/reshard_grow_s", f"{1e6 * max(grows):.0f}",
                       f"events={len(grows)} buffers_preserved={preserved}")
        if shrinks:
            rows["reshard_shrink_s"] = round(max(shrinks), 4)
            writer.row("fig7/reshard_shrink_s", f"{1e6 * max(shrinks):.0f}",
                       f"events={len(shrinks)} pooled_to_aggregate_capacity")

    # (c) restart cost
    rs = payload.get("restart")
    if rs:
        rows["restore_s"] = round(rs["restore_seconds"], 4)
        writer.row("fig7/restore_s", f"{1e6 * rs['restore_seconds']:.0f}",
                   f"restarts={rs['restarts']} wall={rs['wall_seconds']:.1f}s "
                   f"over {rs['steps']} steps")

    # (d) exchange volume vs chips, from the dry-run artifacts
    ddir = os.path.join(here, "benchmarks", "results", "dryrun")
    for mesh_name in ("single", "multi"):
        path = os.path.join(ddir, f"smollm-135m__train_4k__{mesh_name}__scaled.json")
        if not os.path.exists(path):
            path = os.path.join(ddir, f"smollm-135m__train_4k__{mesh_name}.json")
        if os.path.exists(path):
            rec = json.load(open(path))
            a2a = rec["per_collective"].get("all-to-all", {"bytes": 0})
            rows[f"exchange_bytes_{mesh_name}"] = a2a["bytes"]
            writer.row(f"fig7/exchange_bytes_{mesh_name}",
                       "0", f"all_to_all_bytes_per_chip={a2a['bytes']:.3e}")

    with open(json_path, "w") as f:
        json.dump({"bench": "fig7", "smoke": smoke, "rows": rows}, f, indent=2)
    writer.row("fig7/json", "0", os.path.abspath(json_path))


if __name__ == "__main__":
    import argparse

    from repro.utils.logging import CSVWriter

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--json", default="BENCH_fig7.json")
    args = ap.parse_args()
    run(CSVWriter(), smoke=args.smoke, json_path=args.json)
