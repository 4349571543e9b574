"""Serving entry: prefill a prompt batch, then batched greedy decode with KV caches.

Same mesh-parameterised path as training: ``--mesh 1x1`` on CPU, ``16x16`` on a pod.

``--online`` switches to the continual-serving loop (``repro.serving``,
DESIGN.md §12): requests come from the task-free ``drift_stream`` scenario,
each round's traffic is admitted into the rehearsal buffer, and asynchronous
train steps keep the served weights current. Without ``--online`` the decode
path is bit-identical to the historical script for the same arguments.
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_reduced
from repro.launch.mesh import make_mesh
from repro.models import StackCtx, build_model
from repro.parallel import make_shard_fn
from repro.utils.compat import set_mesh
from repro.utils.logging import get_logger
from repro.utils.platform import enable_compile_cache

log = get_logger("repro.serve")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dtype", default="float32",
                    choices=("float32", "bfloat16"),
                    help="serving compute/cache dtype (StackCtx), matching "
                         "launch/train.py's compute_dtype plumbing")
    ap.add_argument("--online", action="store_true",
                    help="continually learn from the served traffic "
                         "(drift_stream scenario + rehearsal buffer)")
    ap.add_argument("--rounds", type=int, default=8,
                    help="--online: serve rounds (one request batch each)")
    ap.add_argument("--train-every", type=int, default=1,
                    help="--online: train steps interleaved per round")
    ap.add_argument("--phases", type=int, default=3,
                    help="--online: anchor distributions the traffic drifts "
                         "across")
    ap.add_argument("--ckpt-dir", default="",
                    help="--online: arms ResilientLoop restart checkpoints")
    ap.add_argument("--obs", default="", metavar="DIR",
                    help="write trace.json + events.jsonl under DIR")
    ap.add_argument("--metrics-port", type=int, default=-1, metavar="PORT",
                    help="serve Prometheus text gauges at /metrics on PORT "
                         "(0 = OS-assigned; default: no endpoint)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    from repro import obs as obs_mod
    registry = server = None
    if args.obs:
        obs_mod.configure(args.obs)
    if args.metrics_port >= 0:
        registry = obs_mod.MetricsRegistry()
        server, port = obs_mod.start_metrics_server(registry,
                                                    port=args.metrics_port)
        log.info("prometheus /metrics on http://127.0.0.1:%d/metrics", port)

    # The metrics server and obs sinks must come down on EVERY exit path —
    # an exception mid-decode used to leak the listener thread and drop the
    # buffered trace/events on the floor.
    try:
        if args.online:
            _serve_online(args, registry)
        else:
            _serve_once(args, registry)
    finally:
        if args.obs:
            obs_mod.flush()
        if server is not None:
            server.shutdown()


def _serve_once(args, registry):
    """One prefill + greedy generation pass (the historical serve path)."""
    from repro.serving import DecodeEngine

    cfg = get_reduced(args.arch) if args.reduced else get_config(args.arch)
    d, m = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d, m), ("data", "model"))
    max_len = args.prompt_len + args.gen_len
    dtype = jnp.float32 if args.dtype == "float32" else jnp.bfloat16
    model = build_model(cfg)
    ctx = StackCtx(cfg=cfg, shard=make_shard_fn(mesh), compute_dtype=dtype,
                   remat="none")
    key = jax.random.PRNGKey(args.seed)

    with set_mesh(mesh):
        params = model.init(key, max_seq=max_len)
        prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                     cfg.vocab_size)
        engine = DecodeEngine(model, ctx, cache_dtype=dtype)
        res = engine.generate(params, prompts, args.gen_len)

    gen = res.tokens
    log.info("arch=%s batch=%d prefill(%d tok)=%.2fs decode(%d tok)=%.2fs "
             "(%.1f tok/s/seq)", cfg.name, args.batch, args.prompt_len,
             res.prefill_seconds, gen.shape[1], res.decode_seconds,
             res.tokens_per_second)
    if registry is not None:
        registry.set("repro_serve_prefill_seconds", res.prefill_seconds,
                     help="wall-clock seconds to prefill the prompt batch")
        registry.set("repro_serve_decode_tokens_per_second",
                     res.tokens_per_second,
                     help="greedy-decode throughput per sequence")
        registry.set("repro_serve_batch_size", args.batch)
    print("generated token ids (first sequence):", np.asarray(gen[0]))


def _serve_online(args, registry):
    """Continual serving: drift_stream traffic in, fresh weights out."""
    from repro.configs.base import (OnlineConfig, RunConfig, ScenarioConfig,
                                    TrainConfig)
    from repro.serving import OnlineLearner

    if args.mesh != "1x1":
        log.info("--online trains on the single-device carry backend; "
                 "--mesh %s ignored", args.mesh)
    seq_len = args.prompt_len + args.gen_len - 1
    run = RunConfig(
        model=None,  # reduced 2-layer token LM (build_token_lm default)
        train=TrainConfig(optimizer="adamw", peak_lr=3e-3, warmup_steps=4,
                          linear_scaling=False, compute_dtype="float32"),
        scenario=ScenarioConfig(
            name="drift_stream", modality="tokens", num_tasks=args.phases,
            epochs_per_task=1,
            steps_per_epoch=max(2, args.rounds // max(args.phases, 1)),
            batch_size=args.batch, seed=args.seed, vocab_size=128,
            seq_len=seq_len),
        online=OnlineConfig(enabled=True, rounds=args.rounds,
                            requests_per_round=args.batch,
                            prompt_len=args.prompt_len,
                            train_every=args.train_every))
    dtype = jnp.float32 if args.dtype == "float32" else jnp.bfloat16
    learner = OnlineLearner(run, ckpt_dir=args.ckpt_dir, serve_dtype=dtype,
                            registry=registry)
    result = learner.run()
    log.info("online: rounds=%d decode=%.1f tok/s/seq admission=%.2f "
             "freshness=%d restarts=%d acc=%s", args.rounds,
             result.decode_tokens_per_second, result.admission_rate,
             int(result.freshness_rounds), result.restarts,
             [round(a, 3) for a in result.accuracy])
    print("generated token ids (first sequence, final round):",
          np.asarray(result.last_tokens[0]))


if __name__ == "__main__":
    main()
