"""The mesh backend's step, as ``ContinualTrainer`` (and ``launch/train.py``)
build it: ``launch.steps.build_train_step`` on a (chips x 1) data-parallel
mesh, with the state in the layout and shardings ``materialize_state`` gives
it. The buffer is written full; the pending representatives start invalid, as
``materialize_state`` leaves them."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import generate
from chipbench.entries import numeric_leaves


class Entry:
    def __init__(self, cfg, tr, family, n_chips):
        from repro.configs.base import (RehearsalConfig, RunConfig,
                                        ScenarioConfig, ShapeConfig, TrainConfig)
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import build_train_step
        from repro.models import build_model
        from repro.optim import make_optimizer
        from repro.utils.compat import set_mesh

        if tr["records"] != "tokens":
            raise ValueError("the mesh entry drives the token scenario")
        tc = cfg["train"]
        self.rehearse = tr["mode"] != "off"
        strategy = "rehearsal" if self.rehearse else "incremental"
        self.mesh = make_mesh((n_chips, 1), ("data", "model"))
        self._set_mesh = set_mesh
        gb = tr["batch_per_chip"] * n_chips
        self.rcfg = RehearsalConfig(
            num_buckets=tr["buckets"], mode=tr["mode"],
            slots_per_bucket=tr.get("slots_per_bucket", 16), policy=tr["policy"],
            num_representatives=tr["reps"] or 1,
            num_candidates=tr["candidates"] or 1)
        self.model_cfg = family.program_model(cfg)
        run = RunConfig(
            model=self.model_cfg,
            shape=ShapeConfig("bench", tr["seq_len"], gb, "train"),
            train=TrainConfig(optimizer=tc["optimizer"], peak_lr=tc["peak_lr"],
                              warmup_steps=tc["warmup_steps"],
                              weight_decay=tc["weight_decay"],
                              grad_clip=tc["grad_clip"],
                              linear_scaling=tc["linear_scaling"],
                              max_scaled_lr=tc["max_scaled_lr"],
                              remat=tc["remat"]),
            rehearsal=self.rcfg,
            scenario=ScenarioConfig(
                name="class_incremental", modality="tokens", strategy=strategy,
                num_tasks=tr["num_tasks"], batch_size=gb,
                vocab_size=tr["vocab_active"], seq_len=tr["seq_len"],
                auto_defaults=False))
        with set_mesh(self.mesh):
            self.built = build_train_step(run, self.mesh, exchange=tr["exchange"],
                                          buffer_budget_bytes=None, donate=True)
        self.n_workers = self.built.meta["n_dp"]
        self.opt_init, _ = make_optimizer(run.train, n_workers=self.n_workers)
        self.tr = tr
        self._model = build_model(self.model_cfg)
        self.state = None

    def program_param_shapes(self):
        return jax.eval_shape(lambda k: self._model.init(k, self.tr["seq_len"]),
                              jax.random.PRNGKey(0))

    def param_shardings(self):
        return self.built.shardings[0]

    def layout(self):
        return {"n_workers": self.n_workers,
                "group": self.n_workers if self.tr["exchange"] == "full" else None,
                "rehearse": self.rehearse, "slots": self.rcfg.slots_per_bucket,
                "initial_reps": "invalid"}

    def context(self):
        return self._set_mesh(self.mesh)

    def init_state(self, params, key0, seed_key):
        from repro.core import rehearsal as rb

        sh = self.built.shardings
        opt = jax.jit(self.opt_init, out_shardings=sh[1])(params)
        self.key0 = key0
        self.issue_key = key0
        if not self.rehearse:
            self.state = [params, opt]
            return
        tr, s, n = self.tr, self.rcfg.slots_per_bucket, self.n_workers
        reps_s, valid_s = self.built.args[3], self.built.args[4]

        def full(seed_key):  # the key is an argument: one program for every seed
            data = generate.prefill_records(seed_key, tr, n, s, tr["prefill_chunk"])
            counts = jnp.full((n, tr["buckets"]), s, jnp.int32)
            return rb.BufferState(data, counts, jnp.full_like(counts, s), ())

        buffer = jax.jit(full, out_shardings=sh[2])(seed_key)

        def init_reps():
            def leaf(path, x):
                z = jnp.zeros(x.shape, x.dtype)
                return z - 1 if path[-1].key == self.rcfg.label_field else z
            return jax.tree_util.tree_map_with_path(leaf, reps_s)

        reps = jax.jit(init_reps, out_shardings=sh[3])()
        valid = jax.jit(lambda: jnp.zeros(valid_s.shape, bool), out_shardings=sh[4])()
        self.state = [params, opt, buffer, reps, valid]

    def step(self, batch, g):
        kstep = jax.random.fold_in(self.key0, g)
        if not self.rehearse:
            p, o, metrics = self.built.fn(*self.state, batch, kstep)
            self.state = [p, o]
        else:
            *state, metrics = self.built.fn(*self.state, batch, self.issue_key)
            self.state = state
            self.issue_key = kstep
        self.metrics = metrics
        return metrics["loss"]

    def counters(self):
        """The numeric outputs of the last step's metrics, by name."""
        return numeric_leaves(self.metrics)

    def params(self):
        return self.state[0]

    def opt_mu(self):
        return self.state[1].mu

    def buffer_reads(self):
        buf = self.state[2]
        fp = jax.jit(lambda d: generate.fingerprint(d, 3))(buf.data)
        return fp, buf.counts, buf.seen

    def pending_reads(self):
        fp = jax.jit(lambda r: generate.fingerprint(r, 2))(self.state[3])
        return fp, self.state[4]

    def free(self):
        self.state = None
