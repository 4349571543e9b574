"""ContinualTrainer: the one entry path for continual training.

``ContinualTrainer(run, scenario)`` composes everything the three historical
entry paths (``core.cl_loop.run_continual``, the hand-wired pjit loop in
``launch.train``, ``benchmarks.common.Harness``) each re-plumbed by hand:

    RunConfig + Scenario
        │
        ├─ scenario.apply_defaults(run.rehearsal)   # policy/bucketing defaults
        ├─ scenario.build_problem(run)              # init_params / loss / eval
        ├─ make_cl_step  (carry backend)  ──or──  build_train_step (pjit backend)
        ├─ init_carry / materialize_state           # buffer + pipeline slot init
        ├─ Prefetcher                               # background Load stage
        ├─ CheckpointManager                        # per-task / every-N-steps
        └─ accuracy-matrix evaluation               # paper Eq. (1)

The carry backend reproduces ``run_continual`` bit-for-bit on the
class-incremental scenario (the pinned parity contract,
tests/test_scenario.py); ``run_continual`` itself is now a deprecated shim
over this class. The pjit backend absorbs ``launch.train``'s
``materialize_state`` wiring and serves the mesh-parameterised LM path.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.buffer.api import resolve_field
from repro.configs.base import RunConfig
from repro.data import Cursor, Prefetcher
from repro.scenario.base import Scenario, get_scenario

# Escape-hatch keys honoured by ``ContinualTrainer(..., overrides=...)`` — the
# documented bridge for the run_continual shim and bespoke harnesses. Anything
# not overridden is composed from (RunConfig, Scenario).
OVERRIDE_KEYS = frozenset({
    "batch_fn", "cumulative_batch_fn", "eval_fn", "init_params_fn",
    "init_opt_fn", "step_fn", "loss_fn", "item_spec", "rcfg", "label_field",
    "checkpoint_cb", "forward_outputs", "failure_hook",
})


def _log():
    from repro.utils.logging import get_logger
    return get_logger("repro.trainer")


class ContinualTrainer:
    """Scenario-first continual-training facade (DESIGN.md §7).

    Args:
      run: the ``RunConfig``; ``run.scenario`` holds the schedule (tasks,
        epochs, steps, batch size, seed, strategy) and names the scenario when
        ``scenario`` is not passed explicitly.
      scenario: a ``Scenario`` instance, a registry name, or None (resolve
        from ``run.scenario``).
      mesh: when given, train through the pjit step builder
        (``launch.steps.build_train_step``) instead of the carry-based
        ``make_cl_step`` — the production LM path.
      exchange: rehearsal exchange mode (full | pod_local | local).
      ckpt_dir / ckpt_every: checkpointing; the carry backend saves per task,
        the pjit backend every ``ckpt_every`` steps (0 = per task only).
      prefetch: stage batches on a background thread (identical values — the
        streams are pure functions of the cursor).
      resilience: a ``ResilienceConfig`` (or None; ``run.resilience`` is the
        config-file spelling) wraps each task's step loop in a
        ``runtime.ResilientLoop``: periodic full-carry checkpoints under
        ``ckpt_dir/resilient`` + cursor rewind give bit-exact restart after a
        transient failure, and the wall-clock ``step_timeout`` feeds the
        bounded-staleness straggler path. Requires ``ckpt_dir``. Works on both
        backends; the ``failure_hook`` override is the chaos injection point.
      overrides: escape hatches (see OVERRIDE_KEYS) replacing individual
        composed pieces; used by the deprecated ``run_continual`` shim.
    """

    def __init__(self, run: RunConfig, scenario=None, *, mesh=None,
                 exchange: str = "full", strategy: Optional[str] = None,
                 ckpt_dir: str = "", ckpt_every: int = 0, prefetch: bool = True,
                 log_every: int = 0, donate: bool = True,
                 step_form: str = "fused", resilience=None,
                 overrides: Optional[Dict[str, Any]] = None):
        from repro.strategy import STRATEGIES, get_strategy

        ov = dict(overrides or {})
        unknown = set(ov) - OVERRIDE_KEYS
        if unknown:
            raise TypeError(f"unknown trainer overrides: {sorted(unknown)}")
        self.run = run
        self.mesh = mesh
        self.exchange = exchange
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = ckpt_every
        self.prefetch = prefetch
        self.log_every = log_every
        self.donate = donate
        self._checkpoint_cb = ov.get("checkpoint_cb")
        self._failure_hook = ov.get("failure_hook")
        self.resilience = resilience if resilience is not None else run.resilience
        if self.resilience is not None and not ckpt_dir:
            raise ValueError("resilience= needs ckpt_dir: the ResilientLoop's "
                             "restart path restores from ckpt_dir/resilient")

        sc = run.scenario
        self.scenario: Optional[Scenario] = None
        if isinstance(scenario, str):
            # a registry name selects the scenario KIND; its stream parameters
            # still come from run.scenario (else shape and schedule desync)
            self.scenario = get_scenario(dataclasses.replace(sc, name=scenario))
        elif scenario is not None:
            self.scenario = get_scenario(scenario)
        elif not {"batch_fn", "eval_fn", "item_spec"} <= set(ov):
            self.scenario = get_scenario(sc)

        self.strategy = strategy or sc.strategy
        if self.strategy not in STRATEGIES:
            raise ValueError(
                f"unknown strategy {self.strategy!r}; expected one of "
                f"{sorted(STRATEGIES)}")
        # the resolved Strategy drives loss shape, buffer usage, and any
        # record aux fields; the name stays for logging/result records
        self.strat = get_strategy(self.strategy)
        self.scfg = run.strategy  # StrategyConfig (alpha/beta/top_k)
        self.num_tasks = (self.scenario.num_tasks if self.scenario is not None
                          else sc.num_tasks)
        self.epochs_per_task = sc.epochs_per_task
        self.steps_per_epoch = sc.steps_per_epoch
        self.batch_size = sc.batch_size
        self.seed = sc.seed

        # --- rehearsal config: explicit override > scenario defaults > run ---
        if "rcfg" in ov:
            rcfg = ov["rcfg"]
        else:
            rcfg = run.rehearsal
            if self.scenario is not None and sc.auto_defaults:
                rcfg = self.scenario.apply_defaults(rcfg)
                if not self.strat.uses_buffer and rcfg is not None:
                    # non-buffer strategies never touch the buffer — skip
                    # allocating one (explicit rcfg overrides opt out of this)
                    rcfg = dataclasses.replace(rcfg, mode="off")
                elif (getattr(self.strat, "recommended_policy", None)
                      and rcfg is not None
                      and rcfg.policy == type(rcfg)().policy):
                    # e.g. grasp_embed pairs with the grasp policy when the
                    # config's policy sits at its dataclass default — the same
                    # convention as Scenario.apply_defaults (an explicit
                    # non-default policy always wins; auto_defaults=False
                    # turns all pairing off)
                    rcfg = dataclasses.replace(
                        rcfg, policy=self.strat.recommended_policy)
        self.rcfg = rcfg
        self.label_field = resolve_field(
            ov.get("label_field",
                   self.scenario.label_field if self.scenario else None),
            rcfg, "label_field", "label")

        # --- problem (model coupling) ---
        need_problem = not {"init_params_fn", "eval_fn"} <= set(ov) or \
            ("step_fn" not in ov and "loss_fn" not in ov)
        problem = (self.scenario.build_problem(run)
                   if need_problem and self.scenario is not None else None)
        self.init_params_fn = ov.get(
            "init_params_fn", problem.init_params_fn if problem else None)
        self.loss_fn = ov.get("loss_fn", problem.loss_fn if problem else None)
        self.eval_fn = ov.get("eval_fn", problem.eval_fn if problem else None)
        self.forward_outputs = ov.get(
            "forward_outputs",
            problem.forward_outputs if problem else None)
        self.item_spec = ov.get(
            "item_spec", self.scenario.item_spec if self.scenario else None)
        # tap strategies (DER/grasp_embed) extend the record layout with aux
        # fields derived from the model-outputs tap — the buffer, exchange,
        # tiering, checkpoint and reshard layers all see the extended spec
        self.aux_spec = self._strategy_aux_spec()
        if self.aux_spec:
            self.item_spec = dict(self.item_spec, **self.aux_spec)
        self._batch_fn = ov.get(
            "batch_fn", self.scenario.batch if self.scenario else None)
        self._cumulative_batch_fn = ov.get(
            "cumulative_batch_fn",
            self.scenario.cumulative_batch if self.scenario else None)

        if "init_opt_fn" in ov:
            self.init_opt_fn, self._opt_update = ov["init_opt_fn"], None
        else:
            from repro.optim import make_optimizer
            self.init_opt_fn, self._opt_update = make_optimizer(run.train)

        self._validate_bucketing()
        from repro.runtime.sanitizer import sanitize_enabled
        # one sanitizer per trainer: the fused, stale and split-half wrappers
        # must share a single slot clock (DESIGN.md §13)
        self._sanitize = sanitize_enabled(run)
        self._step_fn = ov.get("step_fn")
        self._halves = None
        task_field = self.scenario.buffer_task_field if self.scenario else None
        if step_form not in ("fused", "split"):
            raise ValueError(f"unknown step_form {step_form!r}")
        if step_form == "split":
            # two separately-dispatched XLA programs (DESIGN.md §3): the issue
            # half's device execution overlaps the host-side load of the next
            # batch — the CPU-visible analogue of the paper's Argobots threads
            from repro.strategy import make_pipelined_halves
            if (self.mesh is not None or self.strategy != "rehearsal"
                    or rcfg is None or not rcfg.is_pipelined):
                raise ValueError("step_form='split' needs the single-device "
                                 "pipelined rehearsal path (mode='async')")
            if self._opt_update is None:
                raise TypeError("step_form='split' composes its own step; it "
                                "cannot be combined with an init_opt_fn override")
            self._halves = make_pipelined_halves(
                self.loss_fn, self._opt_update, rcfg, exchange=exchange,
                label_field=self.label_field, task_field=task_field,
                obs=run.obs, sanitize=self._sanitize)
        elif self._step_fn is None and self.mesh is None:
            from repro.strategy import make_cl_step
            if self._opt_update is None:
                raise TypeError("step_fn or a full make_optimizer pair is required")
            self._step_fn = make_cl_step(
                self.loss_fn, self._opt_update, rcfg, strategy=self.strat,
                exchange=exchange, label_field=self.label_field,
                task_field=task_field, donate=donate,
                strategy_cfg=self.scfg, forward_outputs=self.forward_outputs,
                aux_spec=self.aux_spec, obs=run.obs, sanitize=self._sanitize)

        if self.resilience is not None and self._halves is not None:
            raise ValueError("resilience= needs step_form='fused': the split "
                             "form's two half-programs have no single step the "
                             "ResilientLoop can retry atomically")
        # The bounded-staleness reuse path: only the plain pipelined rehearsal
        # step has a carried pending sample to re-consume (tap strategies need
        # the fresh forward's aux values; the pjit path samples in-program) —
        # elsewhere a straggling exchange falls back to blocking, never to a
        # wrong program.
        self._stale_step_fn = None
        if (self.resilience is not None and self.mesh is None
                and "step_fn" not in ov and self._opt_update is not None
                and self.strat.uses_buffer and not self.strat.needs_outputs
                and rcfg is not None and rcfg.enabled and rcfg.is_pipelined):
            from repro.strategy import make_stale_step
            # share the fused step's sanitizer: stale re-consumes of the
            # pending slot are legal on the SAME clock, double fresh
            # consumes are not
            shared_san = getattr(self._step_fn, "_sanitizer", None)
            self._stale_step_fn = make_stale_step(
                self.loss_fn, self._opt_update, rcfg,
                label_field=self.label_field, donate=donate, obs=run.obs,
                sanitize=shared_san if shared_san is not None
                else self._sanitize)

    # ------------------------------------------------------------------ util
    def _strategy_aux_spec(self) -> Dict[str, Any]:
        """The strategy's per-record aux field specs (``{}`` for the built-in
        trio): eval_shape the model-outputs tap on a one-row batch and hand
        the per-record shapes to ``Strategy.record_fields``."""
        from repro.strategy import outputs_row_spec

        strat, rcfg = self.strat, self.rcfg
        if not (strat.needs_outputs and strat.uses_buffer
                and rcfg is not None and getattr(rcfg, "enabled", False)):
            return {}
        if self.forward_outputs is None or self.init_params_fn is None \
                or self.item_spec is None:
            raise TypeError(
                f"strategy {self.strategy!r} needs the model-outputs tap; the "
                f"scenario's Problem must provide forward_outputs (or pass it "
                f"via overrides)")
        params_s = jax.eval_shape(self.init_params_fn, jax.random.PRNGKey(0))
        batch_s = {k: jax.ShapeDtypeStruct((1,) + tuple(v.shape), v.dtype)
                   for k, v in self.item_spec.items()}
        row_spec = outputs_row_spec(self.forward_outputs, params_s, batch_s)
        return dict(strat.record_fields(self.item_spec, row_spec, self.scfg))

    def _validate_bucketing(self):
        """A task_field-free scenario must not be bucketed by a field its
        batches do not carry — fail at construction, not mid-jit."""
        rcfg, spec = self.rcfg, self.item_spec
        if (self.scenario is not None and rcfg is not None
                and getattr(rcfg, "enabled", False) and spec is not None):
            bucket = self.scenario.buffer_task_field
            if bucket not in spec:
                # the scenario's schema is authoritative for the bucket field
                # (rcfg.task_field is overridden on this path), so the fix is
                # in the scenario, not the rehearsal config
                raise ValueError(
                    f"scenario {self.scenario.name!r} declares bucket field "
                    f"{bucket!r} (task_field={self.scenario.task_field!r}) but "
                    f"its records only carry {sorted(spec)}; fix the "
                    f"scenario's task_field/label_field (task_field=None "
                    f"buckets by the label field)")

    def _source(self, task: int) -> Callable[[int], Dict[str, np.ndarray]]:
        """cursor -> raw batch for the given task segment, strategy-aware."""
        if self.strat.cumulative_data:
            if self._cumulative_batch_fn is None:
                raise NotImplementedError(
                    f"{self.strategy} needs a cumulative batch source")
            return lambda cur, _t=task: self._cumulative_batch_fn(
                _t, self.batch_size, cur)
        return lambda cur, _t=task: self._batch_fn(_t, self.batch_size, cur)

    @staticmethod
    def _history_entry(task: int, step: int, metrics) -> Dict[str, float]:
        """One history record; rehearsal runs also carry the buffer fingerprints
        (rep_checksum / buffer_fill) so the two backends can be compared
        step-for-step (the tiered pjit parity contract)."""
        entry = {"task": task, "step": step, "loss": float(metrics["loss"])}
        for k in ("rep_checksum", "buffer_fill"):
            if k in metrics:
                entry[k] = float(metrics[k])
        for k, v in metrics.items():  # obs/* gauges ride along when enabled
            if k.startswith("obs/"):
                entry[k] = float(v)
        return entry

    def _resilient_loop(self, step_fn, stale_step_fn=None):
        """Build the per-fit ``ResilientLoop`` from ``self.resilience``: its
        checkpoints live under ``ckpt_dir/resilient`` (global-step ids — the
        trainer's own per-task saves use task ids, so the two streams must not
        share a directory), and the straggler policy is freshly seeded so
        repeated fits draw the same simulated-delay sequence."""
        from repro.checkpoint import CheckpointManager
        from repro.runtime.fault_tolerance import (InjectedFailure,
                                                   ResilientLoop,
                                                   StragglerPolicy)
        res = self.resilience
        rmgr = CheckpointManager(os.path.join(self.ckpt_dir, "resilient"))
        straggler = None
        if res.straggler_delay_prob > 0.0 or res.step_timeout > 0.0:
            straggler = StragglerPolicy(res.straggler_delay_prob,
                                        res.max_staleness, seed=self.seed)
        return ResilientLoop(
            step_fn=step_fn, ckpt=rmgr,
            checkpoint_every=res.checkpoint_every,
            max_restarts=res.max_restarts,
            retry_on=None if res.retry_transient else (InjectedFailure,),
            backoff_base=res.backoff_base, backoff_max=res.backoff_max,
            step_timeout=res.step_timeout, straggler=straggler,
            stale_step_fn=stale_step_fn)

    def _loop_history(self, task: int, n_steps: int, loop_hist, history):
        """Fold a ResilientLoop metrics history into the trainer's history at
        the trainer's cadence (every n//4 steps, same as the inline loop)."""
        for s, m in enumerate(loop_hist):
            if s % max(1, n_steps // 4) == 0:
                history.append(self._history_entry(task, s, m))

    def _checkpoint_task(self, task: int, carry, global_step: int, manager):
        if self._checkpoint_cb is not None:
            self._checkpoint_cb(task, carry)
        elif manager is not None:
            # the FULL carry: buffer (data + counts + policy aux, incl. the
            # tiered staging slot) and the in-flight pipeline state — restore
            # must not rebuild FIFO cursors / GRASP distances / stage_valid
            # from init (the checkpoint-roundtrip contract, tests/test_system)
            manager.save(task, {"params": carry.params, "opt": carry.opt,
                                "buffer": carry.buffer, "pipe": carry.pipe},
                         {"task": task, "global_step": global_step})

    # ------------------------------------------------------------------- fit
    def fit(self):
        """Train through every task; returns ``CLRunResult`` (Eq.-1 metric
        matrix, per-task runtimes, loss history).

        With ``run.obs.enabled`` the fit also (a) configures the process-global
        tracer/event bus when ``run.obs.dir`` names an output directory —
        ``trace.json`` + ``events.jsonl`` land there at the end of the fit —
        and (b) folds the ``obs/*`` gauges carried by the history into
        ``result.obs`` ({last, mean, max, n} per key)."""
        ocfg = getattr(self.run, "obs", None)
        obs_active = ocfg is not None and ocfg.enabled
        if obs_active and ocfg.dir:
            from repro import obs as obs_mod
            obs_mod.configure(ocfg.dir)
        try:
            if self.mesh is not None:
                result = self._fit_pjit()
            else:
                result = self._fit_carry()
        finally:
            if obs_active and ocfg.dir:
                obs_mod.flush()
        if obs_active:
            from repro.obs import MetricsWriter
            w = MetricsWriter()
            for i, entry in enumerate(result.history):
                w.add(entry, step=i)
            if w.series:
                result.obs = w.summary()
        return result

    def _fit_carry(self):
        from repro.core.cl_loop import CLRunResult
        from repro.strategy import init_carry

        if None in (self.init_params_fn, self.eval_fn, self._batch_fn) or \
                (self._step_fn is None and self._halves is None):
            raise TypeError("trainer is missing a scenario or explicit overrides")
        manager = None
        if self.ckpt_dir and self._checkpoint_cb is None:
            from repro.checkpoint import CheckpointManager
            manager = CheckpointManager(self.ckpt_dir)

        from repro.obs import get_tracer
        tracer = get_tracer()  # disabled-by-default no-op unless obs configured

        key = jax.random.PRNGKey(self.seed)
        params = self.init_params_fn(key)
        carry = init_carry(params, self.init_opt_fn(params), self.item_spec,
                           self.rcfg, label_field=self.label_field,
                           seed=self.seed)

        rloop = None
        if self.resilience is not None:
            if self._step_fn is None:
                raise TypeError("resilience= needs a fused step_fn")
            rloop = self._resilient_loop(self._step_fn, self._stale_step_fn)

        T = self.num_tasks
        acc = np.zeros((T, T))
        runtimes, history = [], []
        res_stats: Dict[str, float] = {}
        global_step = 0
        for task in range(T):
            if self.strat.fresh_params_per_task:
                # fresh model, cumulative data, proportionally more steps (the
                # quadratic-runtime regime) — same re-init keys as run_continual
                k = jax.random.fold_in(key, 1000 + task)
                params = self.init_params_fn(k)
                carry = init_carry(params, self.init_opt_fn(params),
                                   self.item_spec, self.rcfg,
                                   label_field=self.label_field, seed=self.seed)
                n_steps = self.epochs_per_task * self.steps_per_epoch * (task + 1)
            else:
                n_steps = self.epochs_per_task * self.steps_per_epoch

            source = self._source(task)
            if rloop is not None:
                # resilient: batches come straight off the cursor-pure stream
                # (the Prefetcher's read-ahead can't be rewound on restore) and
                # the ResilientLoop owns stepping, checkpoints and chaos
                def batch_fn(cur, _src=source):
                    return {k_: jnp.asarray(v) for k_, v in _src(cur).items()}

                t0 = time.perf_counter()
                carry, loop_hist, _ = rloop.run(
                    carry, batch_fn, key, n_steps, start_step=global_step,
                    failure_hook=self._failure_hook)
                self._loop_history(task, n_steps, loop_hist, history)
                global_step += n_steps
                for k_, v in rloop.stats.items():
                    res_stats[k_] = res_stats.get(k_, 0.0) + v
                jax.block_until_ready(carry.params)
                runtimes.append(time.perf_counter() - t0)
                with tracer.span("eval", cat="trainer", task=task):
                    for j in range(task + 1):
                        acc[task, j] = self.eval_fn(carry.params, j)
                self._checkpoint_task(task, carry, global_step, manager)
                continue
            pf = None
            if self.prefetch:
                pf = Prefetcher(lambda cur, _src=source: _src(cur.step),
                                cursor=Cursor(task, global_step),
                                convert=jnp.asarray, limit=n_steps).start()
            t0 = time.perf_counter()
            try:
                for s in range(n_steps):
                    if pf is not None:
                        _, batch = pf.next()
                    else:
                        batch = {k_: jnp.asarray(v)
                                 for k_, v in source(global_step).items()}
                    kstep = jax.random.fold_in(key, global_step)
                    if self._halves is not None:
                        # dispatch train THEN issue: the issue program's device
                        # execution overlaps the prefetcher's next host load
                        train_half, issue_half = self._halves
                        prev_pipe = carry.pipe
                        params, opt, metrics = train_half(
                            carry.params, carry.opt, carry.pipe, batch)
                        buffer, pipe = issue_half(carry.buffer, carry.pipe,
                                                  batch, kstep)
                        carry = type(carry)(params, opt, buffer, pipe, carry.ef)
                        if s % max(1, n_steps // 4) == 0:
                            # fingerprints the fused step emits, computed only
                            # on the steps history records — the split form
                            # exists for overlap; keep its hot loop dispatch-free
                            from repro.buffer.api import buffer_fill
                            from repro.strategy import rep_checksum
                            metrics = dict(
                                metrics,
                                rep_checksum=rep_checksum(
                                    prev_pipe.reps, prev_pipe.valid,
                                    self.label_field),
                                buffer_fill=jnp.asarray(
                                    buffer_fill(buffer), jnp.float32))
                    else:
                        carry, metrics = self._step_fn(carry, batch, kstep)
                    global_step += 1
                    if self.log_every and global_step % self.log_every == 0:
                        _log().info("task=%d step=%d loss=%.4f", task,
                                    global_step, float(metrics["loss"]))
                    if s % max(1, n_steps // 4) == 0:
                        history.append(self._history_entry(task, s, metrics))
            finally:
                if pf is not None:
                    pf.stop()
            jax.block_until_ready(carry.params)
            runtimes.append(time.perf_counter() - t0)

            with tracer.span("eval", cat="trainer", task=task):
                for j in range(task + 1):
                    acc[task, j] = self.eval_fn(carry.params, j)
            self._checkpoint_task(task, carry, global_step, manager)

        if manager is not None:
            manager.wait()
        final = float(np.mean(acc[T - 1, :T]))
        return CLRunResult(strategy=self.strategy, accuracy_matrix=acc,
                           task_runtimes=runtimes, final_accuracy=final,
                           history=history,
                           restarts=int(res_stats.get("restarts", 0)),
                           resilience_stats=res_stats or None,
                           step_meta=self._carry_meta(),
                           buffer=carry.buffer)

    def _carry_meta(self):
        """The carry backend's counterpart of ``BuiltStep.meta`` for a tiered
        buffer: it runs on one device with both tiers in device memory (the
        table the fused kernels address), whatever the platform."""
        if self.rcfg is None or not self.rcfg.tiered:
            return None
        return {"tiering": self.rcfg.tiering, "cold_placement": "device"}

    # ------------------------------------------------------------------ pjit
    def _fit_pjit(self):
        from repro.core.cl_loop import CLRunResult
        from repro.launch.steps import build_train_step
        from repro.utils.compat import set_mesh
        from repro.utils.logging import get_logger

        if self.scenario is None:
            raise TypeError("the pjit backend requires a scenario")
        if self.strat.fresh_params_per_task or self.strat.cumulative_data:
            raise NotImplementedError(
                "the pjit backend does not implement from_scratch semantics "
                "(per-task re-init + cumulative sampling); use the carry "
                "backend (mesh=None)")
        # the effective rehearsal config (scenario defaults applied in
        # __init__) drives the step builder too — both backends must bucket
        # and mask identically for the same RunConfig; the builder reads the
        # strategy name off run.scenario, so pin it to the trainer's choice
        run, mesh = self.run, self.mesh
        if self.rcfg is not None:
            run = dataclasses.replace(run, rehearsal=self.rcfg)
        run = dataclasses.replace(
            run, scenario=dataclasses.replace(run.scenario,
                                              strategy=self.strategy))
        if not self.strat.uses_buffer and run.rehearsal.mode != "off":
            raise ValueError("pjit backend: non-buffer strategies run with "
                             "rehearsal.mode='off'")
        log = get_logger("repro.trainer")
        from repro.obs import get_tracer
        tracer = get_tracer()  # disabled-by-default no-op unless obs configured
        manager = None
        if self.ckpt_dir:
            from repro.checkpoint import CheckpointManager
            manager = CheckpointManager(self.ckpt_dir)

        T = self.num_tasks
        bs = run.shape.global_batch  # pjit: the sharded global batch
        if self.batch_size != bs:
            raise ValueError(
                f"pjit backend trains at shape.global_batch={bs} but "
                f"scenario.batch_size={self.batch_size}; set them equal so the "
                f"declared scenario schedule is the one that actually runs")
        acc = np.zeros((T, T))
        runtimes, history = [], []
        res_stats: Dict[str, float] = {}
        with set_mesh(mesh):
            # buffer_budget_bytes=None: rcfg.slots_per_bucket is authoritative,
            # so both backends allocate the same buffer for the same RunConfig.
            # State (incl. the TieredState) is donated: the buffer update is
            # in-place on device, no host round-trip on the step; checkpoints
            # snapshot to numpy before the next call, so donation is safe.
            built = build_train_step(run, mesh, exchange=self.exchange,
                                     buffer_budget_bytes=None,
                                     donate=self.donate)
            key = jax.random.PRNGKey(self.seed)
            params, opt, buffer, reps, valid = materialize_state(
                built, run, mesh, key)
            # RNG lineage matches the carry backend's PipelinedRehearsalCarry:
            # the key handed to step t's issue half is step t-1's step key,
            # rooted at PRNGKey(seed) — so for the same RunConfig both backends
            # draw the identical sample sequence (the tiered parity contract).
            issue_key = key
            global_step = 0

            rloop = None
            if self.resilience is not None:
                # adapt the positional pjit step to the ResilientLoop's
                # (carry, batch, key) contract: the carry is the full state
                # tuple INCLUDING issue_key, so a restore rewinds the sampling
                # lineage with the arrays (bit-exact restart, same as the
                # carry backend's PipelinedRehearsalCarry.key)
                if built.meta["mode"] == "off":
                    def rstep(state, batch, kstep):
                        p, o, m = built.fn(state[0], state[1], batch, kstep)
                        return (p, o), m
                else:
                    def rstep(state, batch, kstep):
                        p, o, b, r, v, m = built.fn(*state[:5], batch, state[5])
                        return (p, o, b, r, v, kstep), m
                # surface the built step's sanitizer so ResilientLoop rewinds
                # its slot clock on checkpoint restore
                rstep._sanitizer = getattr(built.fn, "_sanitizer", None)
                rloop = self._resilient_loop(rstep)

            def snapshot(step_id, task):
                state = {"params": params, "opt": opt}
                if built.meta["mode"] != "off":
                    state.update(buffer=buffer, reps=reps, valid=valid,
                                 issue_key=issue_key)
                manager.save(step_id, state,
                             {"task": task, "global_step": global_step})

            for task in range(T):
                def fetch(cur, _t=task):
                    return self.scenario.batch(_t, bs, cur.step)

                n_steps = self.epochs_per_task * self.steps_per_epoch
                if rloop is not None:
                    def batch_fn(cur, _t=task):
                        return {k_: jnp.asarray(v) for k_, v in
                                self.scenario.batch(_t, bs, cur).items()}

                    t0 = time.perf_counter()
                    if built.meta["mode"] == "off":
                        state = (params, opt)
                    else:
                        state = (params, opt, buffer, reps, valid, issue_key)
                    state, loop_hist, _ = rloop.run(
                        state, batch_fn, key, n_steps, start_step=global_step,
                        failure_hook=self._failure_hook)
                    if built.meta["mode"] == "off":
                        params, opt = state
                    else:
                        params, opt, buffer, reps, valid, issue_key = state
                    self._loop_history(task, n_steps, loop_hist, history)
                    global_step += n_steps
                    for k_, v in rloop.stats.items():
                        res_stats[k_] = res_stats.get(k_, 0.0) + v
                    jax.block_until_ready(params)
                    runtimes.append(time.perf_counter() - t0)
                    with tracer.span("eval", cat="trainer", task=task):
                        for j in range(task + 1):
                            acc[task, j] = self.eval_fn(params, j)
                    if manager is not None:
                        snapshot(global_step, task)
                    continue
                pf = Prefetcher(fetch, cursor=Cursor(task, global_step),
                                convert=jnp.asarray, limit=n_steps)
                if self.prefetch:
                    pf.start()
                t0 = time.perf_counter()
                try:
                    for s in range(n_steps):
                        _, batch = pf.next()
                        kstep = jax.random.fold_in(key, global_step)
                        if built.meta["mode"] == "off":
                            params, opt, metrics = built.fn(params, opt, batch,
                                                            kstep)
                        else:
                            params, opt, buffer, reps, valid, metrics = built.fn(
                                params, opt, buffer, reps, valid, batch,
                                issue_key)
                            issue_key = kstep
                        global_step += 1
                        if self.log_every and global_step % self.log_every == 0:
                            log.info("task=%d step=%d loss=%.4f", task,
                                     global_step, float(metrics["loss"]))
                        if s % max(1, n_steps // 4) == 0:
                            history.append(self._history_entry(task, s, metrics))
                        if (manager is not None and self.ckpt_every
                                and global_step % self.ckpt_every == 0):
                            snapshot(global_step, task)
                finally:
                    pf.stop()
                jax.block_until_ready(params)
                runtimes.append(time.perf_counter() - t0)
                with tracer.span("eval", cat="trainer", task=task):
                    for j in range(task + 1):
                        acc[task, j] = self.eval_fn(params, j)
                if manager is not None and not (
                        self.ckpt_every and global_step % self.ckpt_every == 0):
                    # end-of-task snapshot (skip if the in-loop save just did)
                    snapshot(global_step, task)
        if manager is not None:
            manager.wait()
        final = float(np.mean(acc[T - 1, :T]))
        return CLRunResult(strategy=self.strategy, accuracy_matrix=acc,
                           task_runtimes=runtimes, final_accuracy=final,
                           history=history,
                           restarts=int(res_stats.get("restarts", 0)),
                           resilience_stats=res_stats or None,
                           step_meta=built.meta, buffer=buffer)


# ---------------------------------------------------------------------------
# pjit state materialisation (absorbed from launch.train)
# ---------------------------------------------------------------------------


def materialize_state(built, run, mesh, key, exchange: str = "full"):
    """Turn a BuiltStep's abstract args into real (sharded) arrays."""
    from repro.core import distributed as dist
    from repro.core import rehearsal as rb
    from repro.models import build_model
    from repro.optim import make_optimizer

    cfg, shape, rcfg = run.model, run.shape, run.rehearsal
    model = build_model(cfg)
    params_sh, opt_sh = built.shardings[0], built.shardings[1]
    params = jax.jit(lambda k: model.init(k, shape.seq_len),
                     out_shardings=params_sh)(key)
    opt_init, _ = make_optimizer(run.train, n_workers=built.meta["n_dp"])
    opt = jax.jit(opt_init, out_shardings=opt_sh)(params)
    if built.meta["mode"] == "off":
        return params, opt, None, None, None
    n_dp = built.meta["n_dp"]
    buffer_struct, reps_struct, valid_struct = (
        built.args[2], built.args[3], built.args[4])
    # proper policy init (e.g. GRASP's +inf distance sentinels), not plain zeros
    item_s = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape[2:], s.dtype), reps_struct)
    if built.meta.get("tiering", "off") != "off":
        # tiered: the config is authoritative for hot/cold/stage sizes (mirrors
        # build_train_step); out_shardings place the cold tier's records in
        # the platform's cold memory (tiered.cold_shardings)
        buffer = jax.jit(
            lambda: dist.init_distributed_from_config(item_s, rcfg, n_dp),
            out_shardings=built.shardings[2])()
    else:
        buffer = rb.BufferState(*jax.jit(
            lambda: tuple(dist.init_distributed_buffer(
                item_s, rcfg.num_buckets, built.meta["slots_per_bucket"], n_dp,
                rcfg.policy)),
            out_shardings=tuple(built.shardings[2]))())

    def init_reps():
        def leaf(path, s):
            name = path[-1].key if hasattr(path[-1], "key") else ""
            z = jnp.zeros(s.shape, s.dtype)
            # invalid until the first issue: labels masked -> zero loss
            return z - 1 if name in (rcfg.label_field, "label") else z

        return jax.tree_util.tree_map_with_path(leaf, reps_struct)

    reps = jax.jit(init_reps, out_shardings=built.shardings[3])()
    valid = jax.jit(lambda: jnp.zeros(valid_struct.shape, bool),
                    out_shardings=built.shardings[4])()
    return params, opt, buffer, reps, valid
