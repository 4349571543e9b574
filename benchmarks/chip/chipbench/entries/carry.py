"""The carry backend's step, as ``ContinualTrainer`` builds it without a mesh:
the scenario's loss, ``make_optimizer``, ``strategy.make_cl_step`` and a
``TrainCarry`` in the layout ``init_carry`` gives it. One device."""
from __future__ import annotations

import contextlib
import types

import jax
import jax.numpy as jnp

from chipbench import generate
from chipbench.entries import numeric_leaves


class Entry:
    def __init__(self, cfg, tr, family, n_chips):
        from repro.configs.base import (RehearsalConfig, RunConfig,
                                        ScenarioConfig, TrainConfig)
        from repro.optim import make_optimizer
        from repro.scenario import ClassIncremental
        from repro.strategy import make_cl_step

        if n_chips != 1:
            raise ValueError("the carry backend runs on one device")
        if tr["records"] != "images":
            raise ValueError("the carry entry drives the image scenario")
        tc = cfg["train"]
        self.rehearse = tr["mode"] != "off"
        strategy = "rehearsal" if self.rehearse else "incremental"
        self.rcfg = RehearsalConfig(
            num_buckets=tr["buckets"], slots_per_bucket=tr.get("slots_per_bucket", 16),
            num_representatives=tr["reps"] or 1, num_candidates=tr["candidates"] or 1,
            mode=tr["mode"], policy=tr["policy"], label_field="label",
            task_field="task")
        run = RunConfig(
            model=family.program_model(cfg),
            train=TrainConfig(optimizer=tc["optimizer"], peak_lr=tc["peak_lr"],
                              warmup_steps=tc["warmup_steps"],
                              weight_decay=tc["weight_decay"],
                              momentum=tc.get("momentum", 0.9),
                              grad_clip=tc["grad_clip"],
                              linear_scaling=tc["linear_scaling"],
                              max_scaled_lr=tc["max_scaled_lr"]),
            rehearsal=self.rcfg,
            scenario=ScenarioConfig(
                num_tasks=tr["num_tasks"], classes_per_task=tr["classes_per_task"],
                image_size=tr["image_size"], noise=tr["noise"],
                batch_size=tr["batch_per_chip"], strategy=strategy,
                auto_defaults=False))
        # the program's scenario, told the stream's shape; its batches come
        # from the benchmark's generator
        stream = types.SimpleNamespace(
            cfg=types.SimpleNamespace(num_tasks=tr["num_tasks"],
                                      image_size=tr["image_size"], channels=3),
            num_classes=tr["num_tasks"] * tr["classes_per_task"])
        scenario = ClassIncremental(run.scenario, stream=stream)
        problem = scenario.build_problem(run)
        self.opt_init, opt_update = make_optimizer(run.train)
        self.step_fn = make_cl_step(
            problem.loss_fn, opt_update, self.rcfg, strategy=strategy,
            exchange="full", label_field="label", task_field="task",
            donate=True, strategy_cfg=run.strategy,
            forward_outputs=problem.forward_outputs, aux_spec={}, obs=run.obs)
        self.init_params = problem.init_params_fn
        self.tr = tr
        self.carry = None

    # -- what the harness needs to know ------------------------------------
    def program_param_shapes(self):
        return jax.eval_shape(self.init_params, jax.random.PRNGKey(0))

    def param_shardings(self):
        return None

    def layout(self):
        return {"n_workers": 1, "group": None, "rehearse": self.rehearse,
                "slots": self.rcfg.slots_per_bucket, "initial_reps": "sample"}

    def context(self):
        return contextlib.nullcontext()

    # -- state ---------------------------------------------------------------
    def init_state(self, params, key0, seed_key):
        from repro.buffer import api as buffer_api
        from repro.buffer.state import BufferState, mask_invalid
        from repro.strategy import PipelinedRehearsalCarry, TrainCarry

        opt = jax.jit(self.opt_init)(params)
        buffer = pipe = None
        if self.rehearse:
            tr, s = self.tr, self.rcfg.slots_per_bucket

            # keys are arguments, never constants of a program: one compiled
            # program serves every seed
            def full(seed_key):
                data = generate.prefill_records(seed_key, tr, 1, s, tr["prefill_chunk"])
                counts = jnp.full((tr["buckets"],), s, jnp.int32)
                return BufferState({k: v[0] for k, v in data.items()}, counts,
                                   counts, ())

            buffer = jax.jit(full)(seed_key)
            # the pending slot as init_carry fills it: a sample of the buffer
            # drawn with the lineage's root key
            reps, valid = jax.jit(lambda b, k: buffer_api.buffer_sample(
                b, k, self.rcfg.num_representatives, self.rcfg))(buffer, key0)
            reps = jax.jit(lambda r, v: mask_invalid(r, v, "label"))(reps, valid)
            # its own copy: the carry is donated at every step
            pipe = PipelinedRehearsalCarry(reps, valid, jnp.copy(key0))
        self.carry = TrainCarry(params, opt, buffer, pipe, None)
        self.key0 = key0

    def step(self, batch, g):
        kstep = jax.random.fold_in(self.key0, g)
        self.carry, metrics = self.step_fn(self.carry, batch, kstep)
        self.metrics = metrics
        return metrics["loss"]

    def counters(self):
        """The numeric outputs of the last step's metrics, by name."""
        return numeric_leaves(self.metrics)

    def params(self):
        return self.carry.params

    def opt_mu(self):
        return self.carry.opt.mu

    def buffer_reads(self):
        """(fingerprints [1,K,S,F], counts [1,K], seen [1,K])."""
        buf = self.carry.buffer
        fp = jax.jit(lambda d: generate.fingerprint(d, 2))(buf.data)
        return fp[None], buf.counts[None], buf.seen[None]

    def pending_reads(self):
        """(fingerprints [1,r,F], valid [1,r]) of the pending representatives."""
        pipe = self.carry.pipe
        fp = jax.jit(lambda r: generate.fingerprint(r, 1))(pipe.reps)
        return fp[None], pipe.valid[None]

    def free(self):
        self.carry = None
