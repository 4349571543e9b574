"""Training-step factories, parameterised by a registered ``Strategy``.

Migrated bit-for-bit from ``core/strategies.py`` (now a re-export shim): with
one of the built-in trio (incremental / from_scratch / rehearsal) the factory
emits the exact pre-refactor program — same RNG lineage, same op order — the
pinned-trace parity contract (tests/test_buffer_policies.py).

Strategies that need the model-outputs tap (``Strategy.needs_outputs``: DER,
DER++, grasp_embed) take a second path through the same factory:

      reps   <- pipe (sampled + exchanged at t-1)              # double buffer
      aug    <- batch ⊕ zero-aux  ++  reps (with stored aux)
      outs   <- forward(params, aug)        # logits + penultimate, ONCE
      grads  <- d/dparams strategy_loss(outs, aug)
      store  <- on_store(batch, outs[:b])   # aux values for the new rows
      buffer <- Alg-1(buffer, store); reps' <- global_sample(buffer')
      params <- opt(params, grads)

    The buffer update depends on the *forward* outputs but not on the
    gradients, so the rehearsal collectives still overlap the backward pass
    (DESIGN.md §3/§9). Tap strategies therefore require the pipelined path
    (``mode='async'``): the synchronous form would need this step's sampled
    representatives before the forward that produces the aux values to store.

Steps come in two flavours: single-device (CPU experiments) and manual-DP via
``shard_map`` over a data axis, with optional int8 error-feedback gradient
compression. The large-model pjit path lives in ``repro.launch.steps``.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.buffer import api as buffer_api
from repro.buffer import state as rb
from repro.obs.scopes import scope
from repro.optim.grad_compress import compressed_psum, plain_psum
from repro.strategy.base import STRATEGIES, resolve_strategy
from repro.utils.compat import shard_map


class PipelinedRehearsalCarry(NamedTuple):
    """The double buffer threaded through the train loop (DESIGN.md §3):

    ``reps``/``valid`` — the pending representatives, sampled + exchanged at step
    t−1, that the pipelined step consumes at step t (its stale-by-one slot);
    ``key`` — the RNG lineage: the PRNG key the *next* step's issue half will use
    (established one step ahead so sync and pipelined runs draw the identical key
    sequence, and so the lineage survives checkpoint/restart inside the carry).
    """

    reps: Any  # record pytree [r, ...] ([N_dp, r, ...] in manual-DP carries)
    valid: Any  # bool[r]
    key: Any  # PRNG key, replicated


class TrainCarry(NamedTuple):
    params: Any
    opt: Any
    buffer: Any  # BufferState | TieredState | None
    pipe: Optional[PipelinedRehearsalCarry]  # in-flight sample + RNG lineage
    ef: Any  # error-feedback state (int8 compression) or None

    # Back-compat views of the double buffer (pre-pipeline field names).
    @property
    def reps(self):
        return None if self.pipe is None else self.pipe.reps

    @property
    def reps_valid(self):
        return None if self.pipe is None else self.pipe.valid


def _add_worker_axis(tree, n_dp):
    return jax.tree_util.tree_map(lambda x: jnp.broadcast_to(x, (n_dp,) + x.shape), tree)


def init_carry(params, opt_state, item_spec=None, rcfg=None, ef=None, n_dp: int = 1,
               label_field: Optional[str] = None, seed: int = 0):
    """Fresh carry. With rehearsal on, the buffer (flat or tiered, per the config)
    starts empty and the in-flight representatives start invalid — the first
    iteration trains un-augmented, exactly the paper's bootstrap (§IV-D). ``seed``
    roots the sampling RNG lineage; ``label_field=None`` inherits
    ``rcfg.label_field``. ``item_spec`` must already include any strategy aux
    fields (``Strategy.record_fields``) — the trainer extends it before calling.
    """
    buffer = pipe = None
    if rcfg is not None and rcfg.enabled:
        label_field = buffer_api.resolve_field(label_field, rcfg, "label_field", "label")
        buffer = buffer_api.init_from_config(item_spec, rcfg)
        key0 = jax.random.PRNGKey(seed)
        reps, valid = buffer_api.buffer_sample(buffer, key0, rcfg.num_representatives,
                                              rcfg)
        reps = rb.mask_invalid(reps, valid, label_field)
        if n_dp > 1:
            buffer = _add_worker_axis(buffer, n_dp)
            reps = _add_worker_axis(reps, n_dp)
            valid = _add_worker_axis(valid, n_dp)
        pipe = PipelinedRehearsalCarry(reps, valid, key0)
    return TrainCarry(params, opt_state, buffer, pipe, ef)


def carry_specs(carry: TrainCarry, dp_axis: Optional[str]) -> TrainCarry:
    """Spec prefix-tree for shard_map / jit: params+opt replicated, buffer/reps
    per-worker (leading worker axis sharded over the data axis), RNG key replicated."""
    rep = P()
    per_worker = P(dp_axis) if dp_axis else P()
    pipe = None
    if carry.pipe is not None:
        pipe = PipelinedRehearsalCarry(reps=per_worker, valid=per_worker, key=rep)
    return TrainCarry(
        params=rep,
        opt=rep,
        buffer=None if carry.buffer is None else per_worker,
        pipe=pipe,
        ef=None if carry.ef is None else rep,
    )


def rep_checksum(reps, valid, label_field: str):
    """Order-invariant fingerprint of the consumed representatives (parity tests;
    also emitted by the pjit train step so the two backends can be compared)."""
    labels = reps.get(label_field, reps.get("label")) if isinstance(reps, dict) else None
    if labels is None:
        labels = jax.tree_util.tree_leaves(reps)[0]
    mask = valid.reshape(valid.shape + (1,) * (labels.ndim - valid.ndim))
    return jnp.sum(jnp.asarray(labels, jnp.float32) * mask)


def batch_rows(outputs, b: int):
    """The first ``b`` rows of each batched leaf of an outputs-tap dict (the
    incoming mini-batch's rows of the augmented forward); scalar leaves (the
    MoE aux) are dropped — ``on_store`` only reads per-row values."""
    return {k: v[:b] for k, v in outputs.items()
            if getattr(v, "ndim", 0) and v.shape[0] >= b}


def make_cl_step(
    loss_fn: Callable,
    opt_update: Callable,
    rcfg,
    *,
    strategy="rehearsal",
    mesh=None,
    dp_axis: str = "data",
    exchange: str = "full",
    compress: str = "none",
    label_field: Optional[str] = None,
    task_field: Optional[str] = None,
    donate: bool = True,
    strategy_cfg=None,
    forward_outputs: Optional[Callable] = None,
    aux_spec=None,
    obs=None,
    sanitize=None,
):
    """Build ``step(carry, batch, key) -> (carry, metrics)`` (jitted).

    ``loss_fn(params, batch) -> (loss, metrics_dict)``;
    ``opt_update(grads, opt_state, params) -> (params, opt_state, metrics_dict)``.
    With ``mesh``, the whole step runs in shard_map over ``dp_axis``: batch sharded,
    params replicated, gradients explicitly psum'd (optionally int8-compressed).
    ``label_field``/``task_field`` default to the ``RehearsalConfig`` field names.

    ``strategy`` is a registry name or ``Strategy`` instance. Tap strategies
    (DER/DER++/grasp_embed) additionally need ``forward_outputs(params, batch)
    -> {'logits', 'embed', ...}`` (the model-outputs tap), ``aux_spec`` (their
    per-record aux field specs, from ``Strategy.record_fields``) and a
    ``StrategyConfig`` in ``strategy_cfg``.

    ``obs`` (an ``ObsConfig``, DESIGN.md §11) merges the jit-safe ``obs/*``
    step metrics into the output dict — pure reads of state the step already
    computes, consuming no RNG: fingerprints and carry layout are bit-identical
    with obs on or off. ``None``/disabled compiles the exact pre-obs program.

    ``sanitize`` arms the pipeline race sanitizer (DESIGN.md §13): True, an
    existing ``PipelineRaceSanitizer`` to share its slot clock, or None to
    follow ``REPRO_SANITIZE``. Host-side bookkeeping only — the compiled
    program and its outputs are bit-identical sanitize on/off.
    """
    try:
        strat = resolve_strategy(strategy)
    except KeyError:
        raise ValueError(
            f"unknown strategy {strategy!r}; expected one of "
            f"{sorted(STRATEGIES)}") from None
    from repro.core import distributed as dist

    rehearse = strat.uses_buffer and rcfg is not None and rcfg.enabled
    pipelined = rehearse and rcfg.is_pipelined
    tap = rehearse and strat.needs_outputs
    if strat.needs_outputs and strat.uses_buffer and not rehearse:
        # without this, a der/grasp_embed run with mode='off' would silently
        # train plain incremental while reporting the strategy's name
        raise ValueError(
            f"strategy {strat.name!r} stores aux fields in the rehearsal "
            f"buffer; rehearsal.mode='off' (or no RehearsalConfig) would "
            f"silently degrade it to 'incremental' — set mode='async'")
    label_field = buffer_api.resolve_field(label_field, rcfg, "label_field", "label")
    task_field = buffer_api.resolve_field(task_field, rcfg, "task_field", "task")
    if tap:
        if forward_outputs is None:
            raise TypeError(
                f"strategy {strat.name!r} needs the model-outputs tap: pass "
                f"forward_outputs (and aux_spec from Strategy.record_fields)")
        if not pipelined:
            raise ValueError(
                f"strategy {strat.name!r} requires the pipelined rehearsal "
                f"path (rehearsal.mode='async'): the sync form would need the "
                f"sampled representatives before the forward that produces "
                f"the aux values to store")
        aux_spec = aux_spec or {}
        tap_loss = strat.build_loss(loss_fn, forward_outputs, strategy_cfg,
                                    label_field=label_field)
    obs_on = obs is not None and obs.enabled and obs.step_metrics
    obs_aux_bytes = None
    if obs_on and tap and aux_spec:
        from repro.obs.metrics import aux_row_bytes

        obs_aux_bytes = aux_row_bytes(aux_spec)

    def worker(carry: TrainCarry, batch, key, axis, n_workers):
        buf, pipe = carry.buffer, carry.pipe
        metrics = {}
        obs_valid = obs_rows = None
        if tap:
            idx = jax.lax.axis_index(axis) if axis is not None else 0
            k_issue = jax.random.fold_in(pipe.key, idx)
            ex_axis = None if exchange == "local" else axis
            b = jax.tree_util.tree_leaves(batch)[0].shape[0]
            # the augmented batch concatenates treewise, so the incoming rows
            # carry zero aux placeholders (masked out of the loss via
            # is_replay — only *valid* replay rows distill)
            batch_z = dict(batch, **strat.placeholder_fields(aux_spec, b))
            train_reps, train_valid = dist.consume_reps(
                dist.PendingSample(pipe.reps, pipe.valid), label_field
            )
            train_batch = rb.augment_batch(batch_z, train_reps, train_valid,
                                           label_field)
            train_batch = dict(train_batch, is_replay=jnp.concatenate(
                [jnp.zeros((b,), jnp.float32),
                 train_valid.astype(jnp.float32)]))
            with scope("train"):
                (loss, (aux_metrics, outs)), grads = jax.value_and_grad(
                    tap_loss, has_aux=True)(carry.params, train_batch)
            # store the new rows with their aux values (this step's outputs);
            # no dependency on the gradient subgraph — the exchange still
            # overlaps the backward pass
            store = strat.on_store(batch, batch_rows(outs, b), strategy_cfg)
            buf, pending = dist.issue_sample(
                buf, store, batch[task_field], k_issue, rcfg, ex_axis, exchange
            )
            pipe = PipelinedRehearsalCarry(pending.reps, pending.valid, key)
            metrics["buffer_fill"] = buffer_api.buffer_fill(buf).astype(jnp.float32)
            metrics["rep_checksum"] = rep_checksum(train_reps, train_valid,
                                                   label_field)
            obs_valid, obs_rows = train_valid, b
        else:
            if rehearse:
                idx = jax.lax.axis_index(axis) if axis is not None else 0
                # RNG lineage: this step's issue half draws with the key established
                # at step t-1 (carried), never with this step's own key — so sync
                # and pipelined runs consume the identical key sequence.
                k_issue = jax.random.fold_in(pipe.key, idx)
                ex_axis = None if exchange == "local" else axis
                new_buf, pending = dist.issue_sample(
                    buf, batch, batch[task_field], k_issue, rcfg, ex_axis, exchange
                )
                if pipelined:  # consume the reps sampled at t-1 (double buffer)
                    train_reps, train_valid = dist.consume_reps(
                        dist.PendingSample(pipe.reps, pipe.valid), label_field
                    )
                else:  # sync: this step's freshly issued sample, blocking
                    train_reps, train_valid = dist.consume_reps(pending, label_field)
                train_batch = rb.augment_batch(batch, train_reps, train_valid,
                                               label_field)
                buf = new_buf
                pipe = PipelinedRehearsalCarry(pending.reps, pending.valid, key)
                metrics["buffer_fill"] = buffer_api.buffer_fill(buf).astype(jnp.float32)
                metrics["rep_checksum"] = rep_checksum(train_reps, train_valid,
                                                       label_field)
                obs_valid = train_valid
                obs_rows = jax.tree_util.tree_leaves(batch)[0].shape[0]
            else:
                train_batch = batch

            with scope("train"):
                (loss, aux_metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(carry.params, train_batch)
        ef = carry.ef
        if axis is not None:
            if compress == "int8":
                grads, ef = compressed_psum(grads, axis, ef, n_workers)
            else:
                grads = plain_psum(grads, axis, n_workers)
            loss = jax.lax.pmean(loss, axis)
        with scope("optimizer"):
            params, opt, opt_metrics = opt_update(grads, carry.opt, carry.params)
        metrics.update(loss=loss, **aux_metrics, **opt_metrics)
        if obs_on:
            from repro.obs.metrics import step_metrics as obs_step_metrics

            # pure reads of state already in hand: no RNG, no new carry
            # leaves — the obs-off/obs-on fingerprint parity contract
            metrics.update(obs_step_metrics(
                buffer=buf if rehearse else None,
                rcfg=rcfg if rehearse else None,
                valid=obs_valid, new_rows=obs_rows,
                grads=grads, params=params,
                staleness=(1.0 if pipelined else 0.0) if rehearse else None,
                aux_bytes=obs_aux_bytes, cfg=obs))
        if axis is not None:
            metrics = jax.tree_util.tree_map(
                lambda m: jax.lax.pmean(jnp.asarray(m, jnp.float32), axis), metrics
            )
        return TrainCarry(params, opt, buf, pipe, ef), metrics

    from repro.runtime.sanitizer import resolve_sanitizer, wrap_fused_step

    san = resolve_sanitizer(sanitize, "cl_step")

    if mesh is None:
        @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
        def step(carry, batch, key):
            return worker(carry, batch, key, None, 1)

        if san is not None:
            step = wrap_fused_step(step, san, pipelined=pipelined,
                                   donate=donate)
        return step

    n_workers = mesh.shape[dp_axis]

    def body(carry, batch, key):
        # strip the worker axis from per-worker carry fields (key stays replicated)
        def squeeze(t):
            return None if t is None else jax.tree_util.tree_map(lambda x: x[0], t)

        local = TrainCarry(
            carry.params, carry.opt,
            squeeze(carry.buffer),
            None if carry.pipe is None else PipelinedRehearsalCarry(
                squeeze(carry.pipe.reps), squeeze(carry.pipe.valid), carry.pipe.key),
            carry.ef,
        )
        new_c, metrics = worker(local, batch, key, dp_axis, n_workers)

        def unsqueeze(t):
            return None if t is None else jax.tree_util.tree_map(lambda x: x[None], t)

        out = TrainCarry(
            new_c.params, new_c.opt,
            unsqueeze(new_c.buffer),
            None if new_c.pipe is None else PipelinedRehearsalCarry(
                unsqueeze(new_c.pipe.reps), unsqueeze(new_c.pipe.valid), new_c.pipe.key),
            new_c.ef,
        )
        return out, metrics

    compiled = {}

    def step(carry, batch, key):
        if "fn" not in compiled:
            cspecs = carry_specs(carry, dp_axis)
            fn = shard_map(
                body, mesh=mesh,
                in_specs=(cspecs, P(dp_axis), P()),
                out_specs=(cspecs, P()),
                check_vma=False,
            )
            compiled["fn"] = jax.jit(fn, donate_argnums=(0,) if donate else ())
        return compiled["fn"](carry, batch, key)

    if san is not None:
        step = wrap_fused_step(step, san, pipelined=pipelined, donate=donate,
                               label="sharded step")
    return step


def make_stale_step(
    loss_fn: Callable,
    opt_update: Callable,
    rcfg,
    *,
    label_field: Optional[str] = None,
    donate: bool = False,
    obs=None,
    sanitize=None,
):
    """The bounded-staleness step (single device): same optimizer step as the
    pipelined ``make_cl_step``, but the rehearsal exchange is presumed late —
    consume the carried in-flight representatives *again*, and leave buffer and
    pipe untouched (no push, no sample, no collective). This is the
    ``StragglerPolicy`` reuse path the runtime dispatches when a step blows its
    wall-clock budget: training never waits on the rehearsal service; the same
    pending slot just serves one extra step (staleness +1).

    Skipping the push is deliberate, not merely cheap: Alg-1's reservoir
    accounting and the sampling RNG lineage both advance per *exchange*, so an
    exchange-free step keeps (buffer, pipe) bit-identical and the next fresh
    step re-joins the normal lineage as if the slow step had merely taken long.

    Signature-compatible with ``make_cl_step``'s output —
    ``step(carry, batch, key) -> (carry, metrics)`` with ``stale_step=1.0`` in
    the metrics. Plain rehearsal only (tap strategies fall back to blocking).
    """
    from repro.core import distributed as dist

    label_field = buffer_api.resolve_field(label_field, rcfg, "label_field", "label")
    obs_on = obs is not None and obs.enabled and obs.step_metrics

    @functools.partial(jax.jit, donate_argnums=(0,) if donate else ())
    def step(carry: TrainCarry, batch, key):
        pipe = carry.pipe
        train_reps, train_valid = dist.consume_reps(
            dist.PendingSample(pipe.reps, pipe.valid), label_field
        )
        train_batch = rb.augment_batch(batch, train_reps, train_valid, label_field)
        with scope("train"):
            (loss, aux_metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                carry.params, train_batch
            )
        with scope("optimizer"):
            params, opt, opt_metrics = opt_update(grads, carry.opt, carry.params)
        metrics = dict(
            aux_metrics, **opt_metrics, loss=loss, stale_step=jnp.float32(1.0),
            buffer_fill=buffer_api.buffer_fill(carry.buffer).astype(jnp.float32),
            rep_checksum=rep_checksum(train_reps, train_valid, label_field),
        )
        if obs_on:
            from repro.obs.metrics import step_metrics as obs_step_metrics

            # structural staleness is still 1 (one-step-stale slot); the
            # EXTRA reuse staleness is per-event (StragglerPolicy -> EventBus)
            metrics.update(obs_step_metrics(
                buffer=carry.buffer, rcfg=rcfg, valid=train_valid,
                new_rows=jax.tree_util.tree_leaves(batch)[0].shape[0],
                grads=grads, params=params, staleness=1.0, cfg=obs))
        # buffer/pipe pass through untouched — the pending sample stays pending
        return TrainCarry(params, opt, carry.buffer, pipe, carry.ef), metrics

    from repro.runtime.sanitizer import resolve_sanitizer, wrap_stale_step

    # pass the fresh step's sanitizer instance so stale re-consumes share the
    # same slot clock (a stale consume is legal; a double fresh consume isn't)
    san = resolve_sanitizer(sanitize, "stale_step")
    if san is not None:
        step = wrap_stale_step(step, san)
    return step


def make_pipelined_halves(
    loss_fn: Callable,
    opt_update: Callable,
    rcfg,
    *,
    exchange: str = "local",
    label_field: Optional[str] = None,
    task_field: Optional[str] = None,
    obs=None,
    sanitize=None,
):
    """The pipelined step as TWO separately-dispatched XLA programs (single device):

      ``train_half(params, opt, pipe, batch)``  — augment with the carried pending
          reps and take the optimizer step (no dependency on this step's exchange);
      ``issue_half(buffer, pipe, batch, key)``  — Alg-1 push + the global sample
          producing step t+1's representatives.

    Dispatch order ``train_half; issue_half; <host loads next batch>; block(loss)``
    lets the issue program's device execution overlap the host-side data loading of
    the next step — the CPU-visible analogue of the paper's background Argobots
    threads (benchmarks/fig6_breakdown.py measures exactly this; DESIGN.md §3).
    The fused single-program form (``make_cl_step``) is the deployed TPU path where
    XLA's latency-hiding scheduler provides the overlap instead.

    Plain rehearsal only: tap strategies (DER/grasp_embed) need the fused form —
    their issue half consumes the train half's forward outputs.

    ``obs`` merges the grad/param-norm + replay ``obs/*`` metrics into the
    train half's output (buffer gauges need the buffer and belong to the fused
    form); the issue half's signature is unchanged.
    """
    from repro.core import distributed as dist

    label_field = buffer_api.resolve_field(label_field, rcfg, "label_field", "label")
    task_field = buffer_api.resolve_field(task_field, rcfg, "task_field", "task")
    obs_on = obs is not None and obs.enabled and obs.step_metrics

    @jax.jit
    def train_half(params, opt, pipe, batch):
        train_reps, train_valid = dist.consume_reps(
            dist.PendingSample(pipe.reps, pipe.valid), label_field
        )
        train_batch = rb.augment_batch(batch, train_reps, train_valid, label_field)
        with scope("train"):
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, train_batch)
        with scope("optimizer"):
            params, opt, om = opt_update(grads, opt, params)
        metrics = dict(aux, **om, loss=loss)
        if obs_on:
            from repro.obs.metrics import step_metrics as obs_step_metrics

            metrics.update(obs_step_metrics(
                valid=train_valid,
                new_rows=jax.tree_util.tree_leaves(batch)[0].shape[0],
                grads=grads, params=params, staleness=1.0, cfg=obs))
        return params, opt, metrics

    @jax.jit
    def issue_half(buffer, pipe, batch, key):
        k_issue = jax.random.fold_in(pipe.key, 0)  # single worker: idx 0, as fused
        new_buf, pending = dist.issue_sample(
            buffer, batch, batch[task_field], k_issue, rcfg, None, exchange
        )
        return new_buf, PipelinedRehearsalCarry(pending.reps, pending.valid, key)

    from repro.runtime.sanitizer import resolve_sanitizer, wrap_halves

    san = resolve_sanitizer(sanitize, "pipelined_halves")
    if san is not None:
        train_half, issue_half = wrap_halves(train_half, issue_half, san)
    return train_half, issue_half
