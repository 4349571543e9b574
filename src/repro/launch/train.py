"""Production training entry: continual LM training with distributed rehearsal.

One code path from laptop to pod, now routed through the scenario-first API:
the CLI builds a ``RunConfig`` (+ ``ScenarioConfig``) and a token
class-incremental scenario, and ``ContinualTrainer``'s pjit backend does what
this file used to hand-wire — ``build_train_step``, state materialisation,
prefetching, checkpointing, per-task eval (DESIGN.md §7). ``--mesh 1x1`` runs
the same program on one device that ``--mesh 16x16`` runs on a pod. The
compute dtype follows the platform: bfloat16 on the TPU, float32 on the CPU.

Example (CPU, reduced arch):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m --reduced \\
      --steps-per-task 50 --tasks 2 --seq-len 128 --global-batch 8
"""
from __future__ import annotations

import argparse
import time

from repro.configs import get_config, get_reduced
from repro.configs.base import (
    RehearsalConfig,
    ResilienceConfig,
    RunConfig,
    ScenarioConfig,
    ShapeConfig,
    StrategyConfig,
    TrainConfig,
)
from repro.launch.mesh import make_mesh
from repro.scenario import ContinualTrainer, TokenClassIncremental
from repro.scenario.trainer import materialize_state  # noqa: F401  (back-compat)
from repro.utils.logging import get_logger
from repro.utils.platform import compute_dtype_of, enable_compile_cache

log = get_logger("repro.train")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 16x16")
    ap.add_argument("--tasks", type=int, default=2)
    ap.add_argument("--steps-per-task", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--mode", default="async", choices=["async", "sync", "off"])
    ap.add_argument("--strategy", default="",
                    help="training strategy (rehearsal | der | der_pp | "
                         "grasp_embed | incremental); default: rehearsal, or "
                         "incremental when --mode off")
    ap.add_argument("--der-alpha", type=float, default=0.5,
                    help="DER: weight of the logit-MSE distillation term")
    ap.add_argument("--der-beta", type=float, default=0.5,
                    help="DER++: weight of the replay-row CE term")
    ap.add_argument("--der-top-k", type=int, default=0,
                    help="store top-k (value,index) logit pairs instead of the "
                         "dense vocab row (0 = dense; 8-16x buffer saving)")
    ap.add_argument("--exchange", default="full",
                    choices=["full", "pod_local", "local"])
    ap.add_argument("--policy", default="reservoir",
                    help="buffer policy (reservoir|fifo|class_balanced|grasp)")
    ap.add_argument("--tiering", default="off", choices=["off", "host", "on"],
                    help="two-tier buffer: cold records spill to host as int8")
    ap.add_argument("--hot-slots", type=int, default=0,
                    help="tiered: hot (HBM) slots/bucket; 0 = slots_per_bucket")
    ap.add_argument("--cold-slots", type=int, default=0,
                    help="tiered: cold (host int8) slots/bucket; 0 = 3x hot")
    ap.add_argument("--slots-per-bucket", type=int, default=16)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--resilience", action="store_true",
                    help="wrap the step loop in runtime.ResilientLoop "
                         "(checkpointed restart; needs --ckpt-dir)")
    ap.add_argument("--resilience-checkpoint-every", type=int, default=25)
    ap.add_argument("--max-restarts", type=int, default=3)
    ap.add_argument("--backoff-base", type=float, default=0.0,
                    help="restart r sleeps min(backoff-max, base * 2^(r-1)) s")
    ap.add_argument("--backoff-max", type=float, default=30.0)
    ap.add_argument("--step-timeout", type=float, default=0.0,
                    help="wall-clock step budget (s); overruns flag the next "
                         "exchange as straggling (bounded-staleness reuse)")
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    strategy = args.strategy or (
        "rehearsal" if args.mode != "off" else "incremental")
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))
    shape = ShapeConfig("train_cli", args.seq_len, args.global_batch, "train")
    vocab_active = min(cfg.vocab_size, 2048)
    run = RunConfig(
        model=cfg,
        shape=shape,
        # remat="full": at real sequence lengths the per-layer attention
        # scores that the "dots" policy saves outgrow a chip's HBM
        train=TrainConfig(optimizer=args.optimizer, peak_lr=args.lr,
                          warmup_steps=20, linear_scaling=False,
                          remat="full"),
        rehearsal=RehearsalConfig(num_buckets=max(args.tasks, 2), mode=args.mode,
                                  slots_per_bucket=args.slots_per_bucket,
                                  policy=args.policy, tiering=args.tiering,
                                  hot_slots=args.hot_slots,
                                  cold_slots=args.cold_slots),
        strategy=StrategyConfig(alpha=args.der_alpha, beta=args.der_beta,
                                top_k=args.der_top_k),
        scenario=ScenarioConfig(
            name="class_incremental", modality="tokens",
            strategy=strategy,
            num_tasks=args.tasks, epochs_per_task=1,
            steps_per_epoch=args.steps_per_task, batch_size=args.global_batch,
            seed=args.seed, vocab_size=vocab_active, seq_len=args.seq_len,
            auto_defaults=False),  # the CLI's rehearsal flags are authoritative
        resilience=ResilienceConfig(
            checkpoint_every=args.resilience_checkpoint_every,
            max_restarts=args.max_restarts, backoff_base=args.backoff_base,
            backoff_max=args.backoff_max,
            step_timeout=args.step_timeout) if args.resilience else None,
    )
    scenario = TokenClassIncremental(run.scenario)

    log.info("arch=%s params=%.1fM mesh=%s mode=%s strategy=%s dtype=%s "
             "compile_cache=%s", cfg.name, cfg.param_count() / 1e6,
             dict(mesh.shape), args.mode, strategy,
             compute_dtype_of(run.train.compute_dtype),
             cache_dir)
    if strategy in ("der", "der_pp") and args.der_top_k:
        log.info("der: storing top-%d logit (val,idx) pairs per position "
                 "(alpha=%.2f beta=%.2f)", args.der_top_k, args.der_alpha,
                 args.der_beta)
    if run.rehearsal.tiered:
        from repro.launch.mesh import memory_kinds
        log.info("tiered buffer: hot=%d cold=%d slots/bucket; mesh memory "
                 "kinds: %s", run.rehearsal.resolved_hot_slots,
                 run.rehearsal.resolved_cold_slots, sorted(memory_kinds(mesh)))
    trainer = ContinualTrainer(run, scenario, mesh=mesh, exchange=args.exchange,
                               ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                               log_every=args.log_every)
    t_start = time.time()
    res = trainer.fit()
    for task in range(args.tasks):
        for j in range(task + 1):
            log.info("eval after task %d on task %d: loss=%.4f", task, j,
                     res.accuracy_matrix[task, j])
    steps = args.tasks * args.steps_per_task
    if res.resilience_stats is not None:
        log.info("resilience: restarts=%d stale_steps=%d restore=%.3fs",
                 res.restarts, int(res.resilience_stats.get("stale_steps", 0)),
                 res.resilience_stats.get("restore_seconds", 0.0))
    log.info("done: %d steps in %.1fs", steps, time.time() - t_start)
    return res


if __name__ == "__main__":
    main()
