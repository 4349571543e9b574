"""pjit step builders: train (with fused pipelined/sync rehearsal), prefill, decode.

The train step is the paper's Fig. 4 pipeline compiled into ONE XLA program
(DESIGN.md §3; ``rehearsal.mode='async'`` or ``rehearsal.pipelined=True``
selects it, ``mode='sync'`` the blocking baseline):

  pipelined (default, the paper's contribution):
      grads  <- loss(params, batch ⊕ inflight_reps)         # reps sampled at t-1
      buffer <- Alg-1(buffer, batch)                        # no dep on grads
      reps'  <- global_sample(buffer')                      # all_to_all, no dep on grads
      params <- opt(params, grads)
    The rehearsal collectives share no data dependency with the backward pass, so
    XLA's latency-hiding scheduler overlaps them with compute — the in-graph
    equivalent of the paper's background Argobots threads.

  sync (the paper's blocking baseline, Fig. 6):
      buffer, reps' <- update+sample(buffer, batch)
      grads <- loss(params, batch ⊕ reps')                  # exchange on critical path

All functions here are mesh-parameterised and return (fn, in_state, shardings) ready
for ``jax.jit(...).lower(...).compile()`` — the dry-run contract.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.buffer import api as buffer_api
from repro.buffer import tiered as tiered_mod
from repro.configs.base import RunConfig, ShapeConfig
from repro.core import distributed as dist
from repro.core import rehearsal as rb
from repro.strategy import outputs_row_spec, rep_checksum, resolve_strategy
from repro.models import StackCtx, build_model
from repro.obs.scopes import scope
from repro.optim import make_optimizer
from repro.parallel import (
    batch_shardings,
    buffer_shardings,
    cache_shardings,
    dp_axes,
    make_shard_fn,
    params_shardings,
)
from repro.parallel.sharding import make_moe_apply
from repro.utils.platform import compute_dtype_of
from repro.utils.trees import tree_cast

MAX_SLOTS = 1024


def _cast_struct(tree_s, dtype):
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, dtype)
        if jnp.issubdtype(l.dtype, jnp.floating) else l, tree_s)


def slots_for_budget(item_spec, num_buckets: int, budget_bytes: int) -> int:
    """Paper §VII: per-worker buffer memory S_max is a fixed budget; slots = S_max/K."""
    item_bytes = 0
    for leaf in jax.tree_util.tree_leaves(item_spec):
        item_bytes += int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
    return max(1, min(MAX_SLOTS, budget_bytes // max(1, num_buckets * item_bytes)))


def _rep_sharding(reps_struct, mesh):
    dp = dp_axes(mesh)

    def one(leaf):
        return NamedSharding(mesh, P(dp, *([None] * (len(leaf.shape) - 1))))

    return jax.tree_util.tree_map(one, reps_struct)


@dataclass
class BuiltStep:
    """Everything needed to run — or dry-run — one step function."""

    fn: Any  # jitted
    args: Tuple  # ShapeDtypeStructs (dry-run) in the fn's argument order
    shardings: Tuple  # in_shardings matching args
    meta: Dict[str, Any]


def shard_host_batch(batch, shardings):
    """Assemble per-process host batches into global sharded arrays.

    Single-process (the CPU/test path): a no-op — jit moves host arrays onto
    the mesh itself. Multi-process (``jax.distributed``): each process holds
    only its LOCAL slice of the global batch, and jit cannot be handed host
    arrays for a sharding that spans non-addressable devices, so every leaf
    goes through ``make_array_from_process_local_data`` (each process
    contributes its slice; the global shape is inferred from the sharding's
    process count along the batch axis). Feed the result straight to
    ``BuiltStep.fn``.
    """
    import jax.experimental.multihost_utils  # noqa: F401  (registers helpers)

    if jax.process_count() == 1:
        return batch
    return jax.tree_util.tree_map(
        lambda sh, x: jax.make_array_from_process_local_data(sh, np.asarray(x)),
        shardings, batch)


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


def build_train_step(
    run: RunConfig,
    mesh,
    *,
    rehearsal_mode: Optional[str] = None,  # None -> run.rehearsal.mode
    exchange: str = "full",
    buffer_budget_bytes: Optional[int] = 64 << 20,
    donate: bool = True,
    strategy=None,  # None -> run.scenario.strategy; name or Strategy
) -> BuiltStep:
    cfg, shape, tcfg, rcfg = run.model, run.shape, run.train, run.rehearsal
    strat = resolve_strategy(strategy if strategy is not None
                             else run.scenario.strategy)
    scfg = run.strategy
    ocfg = getattr(run, "obs", None)
    # obs/* gauges ride the existing replicated metrics dict; fingerprints
    # (rep_checksum / buffer_fill / loss) are computed exactly as before, so
    # toggling obs cannot change them (the bit-exactness contract, DESIGN §11)
    obs_on = ocfg is not None and ocfg.enabled and ocfg.step_metrics
    if obs_on:
        from repro.obs.metrics import step_metrics as obs_step_metrics
    mode = rehearsal_mode if rehearsal_mode is not None else rcfg.mode
    # one-step-stale double buffering (DESIGN.md §3): async mode, or forced via
    # the ``rehearsal.pipelined`` flag (sync mode stays available for parity runs)
    pipelined = dataclasses.replace(rcfg, mode=mode).is_pipelined
    model = build_model(cfg)
    dp = dp_axes(mesh)
    n_dp = int(np.prod([mesh.shape[a] for a in dp]))
    compute_dtype = compute_dtype_of(tcfg.compute_dtype)
    from repro.models.attention import ATTN_IMPL
    ATTN_IMPL["mode"] = tcfg.attn_impl
    ctx = StackCtx(cfg=cfg, shard=make_shard_fn(mesh, tcfg.sequence_parallel),
                   compute_dtype=compute_dtype,
                   remat=tcfg.remat, scan_layers=tcfg.scan_layers, dp_shards=n_dp,
                   moe_apply=make_moe_apply(mesh, cfg) if cfg.is_moe else None)
    opt_init, opt_update = make_optimizer(tcfg, n_workers=n_dp)

    # --- abstract state (no allocation) ---
    key0 = jax.random.PRNGKey(0)
    params_s = jax.eval_shape(lambda k: model.init(k, shape.seq_len), key0)
    if tcfg.param_dtype == "bfloat16":  # bf16 storage: halves the grad all-reduce
        params_s = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, jnp.bfloat16)
            if jnp.issubdtype(l.dtype, jnp.floating) else l, params_s)
    opt_s = jax.eval_shape(opt_init, params_s)
    batch_s = model.input_specs(shape)
    item_s = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), batch_s
    )
    use_rehearsal = mode != "off" and strat.uses_buffer
    if strat.fresh_params_per_task or strat.cumulative_data:
        raise NotImplementedError(
            f"strategy {strat.name!r} needs per-task re-init / cumulative "
            f"sampling, which the pjit step builder does not implement; use "
            f"the carry backend (mesh=None)")
    if not strat.uses_buffer and mode != "off":
        # mirror the trainer: a non-buffer strategy with rehearsal on would
        # compile a plain step while meta reports rehearsal semantics
        raise ValueError(
            f"strategy {strat.name!r} never touches the buffer; build with "
            f"rehearsal.mode='off'")
    if strat.needs_outputs and strat.uses_buffer and not use_rehearsal:
        # without this, a der/grasp_embed run with mode='off' would silently
        # train plain incremental while meta still reports the strategy name
        raise ValueError(
            f"strategy {strat.name!r} stores aux fields in the rehearsal "
            f"buffer; rehearsal.mode='off' would silently degrade it to "
            f"'incremental' — set mode='async'")
    r = rcfg.num_representatives
    task_field = rcfg.task_field
    # Tap strategies (DER/DER++/grasp_embed): the record layout grows aux
    # fields derived from the model-outputs tap; the extended item_s flows
    # into the buffer, reps and exchange shapes below unchanged.
    tap = use_rehearsal and strat.needs_outputs
    aux_spec = {}
    if tap:
        if not pipelined:
            raise ValueError(
                f"strategy {strat.name!r} requires the pipelined rehearsal "
                f"path (rehearsal.mode='async'): the sync form would need "
                f"the sampled representatives before the forward that "
                f"produces the aux values to store")
        if model.outputs is None:
            raise NotImplementedError(
                f"model family {cfg.family!r} exposes no outputs tap; "
                f"strategy {strat.name!r} is unavailable for it")

        def outputs_of(params, batch):
            return model.outputs(tree_cast(params, compute_dtype), batch, ctx)

        aux_spec = strat.record_fields(
            item_s, outputs_row_spec(outputs_of, params_s, batch_s), scfg)
        item_s = dict(item_s, **aux_spec)
    tiered = use_rehearsal and rcfg.tiered
    cold_placement = None
    if tiered:
        # Tiered configs are explicit about their capacity split (hot_slots /
        # cold_slots / demote_stage), so the config — not the flat budget knob —
        # is authoritative: the carry and pjit backends must materialize the
        # SAME TieredState for the same RunConfig (the parity contract).
        slots = rcfg.resolved_hot_slots
        buffer_s = jax.eval_shape(
            functools.partial(dist.init_distributed_from_config, item_s, rcfg, n_dp)
        )
        buffer_sh = tiered_mod.cold_shardings(buffer_s, mesh, dp)
        cold_placement = tiered_mod.resolve_cold_placement(mesh.devices.flat)
    elif use_rehearsal:
        # buffer_budget_bytes=None: the config's slots_per_bucket is
        # authoritative (the trainer path — carry and pjit backends must
        # allocate the SAME buffer); a byte budget derives slots the paper's
        # S_max way (the dry-run / direct-caller path).
        slots = (rcfg.slots_per_bucket if buffer_budget_bytes is None
                 else slots_for_budget(item_s, rcfg.num_buckets,
                                       buffer_budget_bytes))
        buffer_s = jax.eval_shape(
            functools.partial(dist.init_distributed_buffer, item_s, rcfg.num_buckets,
                              slots, n_dp, rcfg.policy)
        )
        buffer_s = rb.BufferState(*buffer_s)
        buffer_sh = buffer_shardings(buffer_s, mesh)
    if use_rehearsal:
        reps_s = jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct((n_dp, r) + l.shape, l.dtype), item_s
        )
        valid_s = jax.ShapeDtypeStruct((n_dp, r), jnp.bool_)
        sharded_update = dist.make_sharded_update(mesh, dp, rcfg, exchange=exchange)
    else:
        slots = 0
        buffer_s = reps_s = valid_s = buffer_sh = None
    key_s = jax.ShapeDtypeStruct(key0.shape, key0.dtype)

    # --- step fn ---
    def loss_of(params, batch):
        return model.loss(tree_cast(params, compute_dtype), batch, ctx)

    def grad_fn(params, batch):
        with scope("train"):
            return jax.value_and_grad(loss_of, has_aux=True)(params, batch)

    def opt_step(grads, opt_state, params):
        with scope("optimizer"):
            return opt_update(grads, opt_state, params)

    if not use_rehearsal:

        def step(params, opt_state, batch, key):
            (loss, metrics), grads = grad_fn(params, batch)
            params, opt_state, om = opt_step(grads, opt_state, params)
            metrics = dict(metrics, **om, loss=loss)
            if obs_on:
                metrics.update(obs_step_metrics(grads=grads, params=params,
                                                cfg=ocfg))
            return params, opt_state, metrics

        args = (params_s, opt_s, batch_s, key_s)
        shardings = (
            params_shardings(params_s, cfg, mesh),
            _opt_shardings(opt_s, params_s, cfg, mesh, zero1=tcfg.zero1),
            batch_shardings(batch_s, mesh),
            NamedSharding(mesh, P()),
        )
    elif not pipelined:  # sync — the paper's blocking baseline (Fig. 6)

        def step(params, opt_state, buffer, reps, valid, batch, key):
            # issue + immediately consume: exchange on the critical path
            buffer, new_reps, new_valid = sharded_update(
                buffer, batch, batch[task_field], key
            )
            aug = dist.augment_global(batch, new_reps, new_valid, n_dp,
                                      rcfg.label_field)
            (loss, metrics), grads = grad_fn(params, aug)
            params, opt_state, om = opt_step(grads, opt_state, params)
            fingerprints = {
                "buffer_fill": buffer_api.buffer_fill(buffer).astype(jnp.float32),
                "rep_checksum": rep_checksum(new_reps, new_valid, rcfg.label_field),
            }
            metrics = dict(metrics, **om, **fingerprints, loss=loss)
            if obs_on:
                metrics.update(obs_step_metrics(
                    buffer=buffer, rcfg=rcfg, valid=new_valid,
                    new_rows=shape.global_batch, grads=grads, params=params,
                    staleness=0.0, cfg=ocfg))
            return params, opt_state, buffer, new_reps, new_valid, metrics

    elif tap:  # pipelined tap strategy: DER(++) / grasp_embed (DESIGN.md §9)
        tap_loss = strat.build_loss(None, outputs_of, scfg,
                                    label_field=rcfg.label_field)

        def grad_tap(params, batch):
            with scope("train"):
                return jax.value_and_grad(tap_loss, has_aux=True)(params, batch)

        bg = shape.global_batch

        def step(params, opt_state, buffer, reps, valid, batch, key):
            # consume the pending slot; new rows carry aux placeholders
            # (masked out of the loss via is_replay — only valid replay rows
            # distill), replay rows their stored aux fields
            aug = dist.augment_global(
                dict(batch, **strat.placeholder_fields(aux_spec, bg)),
                reps, valid, n_dp, rcfg.label_field)
            aug = dict(aug, is_replay=dist.global_replay_mask(bg, n_dp, valid))
            (loss, (metrics, outs)), grads = grad_tap(params, aug)
            # store the new rows with this step's outputs; depends on the
            # forward only, so the exchange still overlaps the backward pass.
            # r comes from the actual pending slot: a small exchange group can
            # deliver fewer than num_representatives rows (sample_global).
            outs_b = dist.global_batch_rows(
                {k: v for k, v in outs.items() if getattr(v, "ndim", 0)},
                bg, n_dp, valid.shape[1])
            store = strat.on_store(batch, outs_b, scfg)
            buffer, next_reps, next_valid = sharded_update(
                buffer, store, batch[task_field], key
            )
            params, opt_state, om = opt_step(grads, opt_state, params)
            fingerprints = {
                "buffer_fill": buffer_api.buffer_fill(buffer).astype(jnp.float32),
                "rep_checksum": rep_checksum(reps, valid, rcfg.label_field),
            }
            metrics = dict(metrics, **om, **fingerprints, loss=loss)
            if obs_on:
                from repro.obs.metrics import aux_row_bytes
                metrics.update(obs_step_metrics(
                    buffer=buffer, rcfg=rcfg, valid=valid,
                    new_rows=bg, grads=grads, params=params,
                    staleness=1.0, aux_bytes=aux_row_bytes(aux_spec),
                    cfg=ocfg))
            return params, opt_state, buffer, next_reps, next_valid, metrics

    else:  # pipelined — the paper's contribution (one-step-stale double buffer)

        def step(params, opt_state, buffer, reps, valid, batch, key):
            # consume the pending slot: representatives issued at t-1
            aug = dist.augment_global(batch, reps, valid, n_dp, rcfg.label_field)
            (loss, metrics), grads = grad_fn(params, aug)
            # issue t+1's sample: independent of grads -> overlaps with backward
            # (tiered configs flush last step's staged demotions inside this
            # update — also free of any dependency on the gradient subgraph)
            buffer, next_reps, next_valid = sharded_update(
                buffer, batch, batch[task_field], key
            )
            params, opt_state, om = opt_step(grads, opt_state, params)
            fingerprints = {
                "buffer_fill": buffer_api.buffer_fill(buffer).astype(jnp.float32),
                "rep_checksum": rep_checksum(reps, valid, rcfg.label_field),
            }
            metrics = dict(metrics, **om, **fingerprints, loss=loss)
            if obs_on:
                metrics.update(obs_step_metrics(
                    buffer=buffer, rcfg=rcfg, valid=valid,
                    new_rows=shape.global_batch, grads=grads, params=params,
                    staleness=1.0, cfg=ocfg))
            return params, opt_state, buffer, next_reps, next_valid, metrics


    if use_rehearsal:  # all three rehearsal forms share the same signature
        args = (params_s, opt_s, buffer_s, reps_s, valid_s, batch_s, key_s)
        shardings = _rehearsal_shardings(params_s, opt_s, buffer_sh, reps_s,
                                         batch_s, cfg, mesh, zero1=tcfg.zero1)
    donate_argnums = tuple(range(len(args) - 2)) if donate else ()
    # out shardings pin the carried state to its input layout (params, opt,
    # buffer, reps, valid round-trip through the step across calls — without
    # the constraint GSPMD may pick a different layout for an output leaf and
    # the next call's in_shardings reject it); metrics replicate.
    n_state = len(args) - 2
    out_shardings = tuple(shardings[:n_state]) + (NamedSharding(mesh, P()),)
    fn = jax.jit(step, in_shardings=shardings, out_shardings=out_shardings,
                 donate_argnums=donate_argnums)
    # checked mode: host-side epoch bookkeeping around the compiled step —
    # never touches array values, so fingerprints stay bit-identical
    from repro.runtime.sanitizer import resolve_sanitizer, wrap_built_step
    san = resolve_sanitizer(
        True if getattr(run, "sanitize", False) else None, "pjit_step")
    if san is not None:
        fn = wrap_built_step(fn, san,
                             pipelined=bool(use_rehearsal and pipelined),
                             donated_args=len(args) - 2 if donate else 0)
    aux_bytes = {
        name: int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
        for name, s in aux_spec.items()
    }
    meta = {
        "kind": "train",
        "mode": mode if use_rehearsal else "off",
        "pipelined": bool(use_rehearsal and pipelined),
        "strategy": strat.name,
        "aux_fields": aux_bytes,  # per-record bytes of strategy aux fields
        "n_dp": n_dp,
        "slots_per_bucket": slots,
        "tiering": rcfg.tiering if use_rehearsal else "off",
        "cold_slots_per_bucket": rcfg.resolved_cold_slots if tiered else 0,
        "cold_placement": cold_placement,  # None unless tiered
        "augmented_global_batch": shape.global_batch + (n_dp * r if use_rehearsal else 0),
        "tokens_per_step": (shape.global_batch + (n_dp * r if use_rehearsal else 0))
        * shape.seq_len,
        "obs": obs_on,
        "sanitize": san is not None,
    }
    if obs_on:
        from repro.obs.metrics import obs_keys
        meta["obs_metrics"] = obs_keys(
            rcfg if use_rehearsal else None,
            grad_norms=ocfg.grad_norms, has_aux=bool(aux_spec),
            policy=rcfg.policy if use_rehearsal else None)
    return BuiltStep(fn=fn, args=args, shardings=shardings, meta=meta)


def _opt_shardings(opt_s, params_s, cfg, mesh, zero1: bool = False):
    """Optimizer moments mirror the param tree: same sharding where shapes match
    (momentum / adam moments), replicated for scalar placeholders (sgd's nu).

    ``zero1=True`` additionally shards each moment over the 'data' axis on its
    largest still-unsharded divisible dim (ZeRO stage 1: optimizer state partitioned
    across data-parallel workers; GSPMD turns the gradient all-reduce into
    reduce-scatter + the update's param all-gather)."""
    pshard = params_shardings(params_s, cfg, mesh)
    rep = NamedSharding(mesh, P())
    flat_p = jax.tree_util.tree_leaves(pshard)
    flat_ps = jax.tree_util.tree_leaves(params_s)
    data_size = mesh.shape.get("data", 1)

    def zero1_spec(spec, shape):
        parts = list(spec)
        while len(parts) < len(shape):
            parts.append(None)
        best = -1
        for i, (ax, dim) in enumerate(zip(parts, shape)):
            if ax is None and dim % data_size == 0:
                if best < 0 or dim > shape[best]:
                    best = i
        if best >= 0:
            parts[best] = "data"
        return NamedSharding(mesh, P(*parts))

    def moment(tree_s):
        flat_m, treedef = jax.tree_util.tree_flatten(tree_s)
        leaves = []
        for m, sref, p in zip(flat_m, flat_ps, flat_p):
            if m.shape != sref.shape:
                leaves.append(rep)
            elif zero1:
                leaves.append(zero1_spec(p.spec, m.shape))
            else:
                leaves.append(p)
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return type(opt_s)(rep, moment(opt_s.mu), moment(opt_s.nu))


def _rehearsal_shardings(params_s, opt_s, buffer_sh, reps_s, batch_s, cfg, mesh,
                         zero1: bool = False):
    """``buffer_sh`` is the pre-built buffer sharding tree: worker-axis
    ``buffer_shardings`` for flat stores, ``tiered.cold_shardings`` (worker axis
    + ``pinned_host`` cold leaves) for tiered ones."""
    dp = dp_axes(mesh)
    return (
        params_shardings(params_s, cfg, mesh),
        _opt_shardings(opt_s, params_s, cfg, mesh, zero1=zero1),
        buffer_sh,
        _rep_sharding(reps_s, mesh),
        NamedSharding(mesh, P(dp, None)),
        batch_shardings(batch_s, mesh),
        NamedSharding(mesh, P()),
    )


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------


def build_prefill_step(run: RunConfig, mesh) -> BuiltStep:
    cfg, shape = run.model, run.shape
    model = build_model(cfg)
    compute_dtype = compute_dtype_of(run.train.compute_dtype)
    n_dp = int(np.prod([mesh.shape[a] for a in dp_axes(mesh)]))
    dp_sh = n_dp if (shape.global_batch * shape.seq_len) % n_dp == 0 else 1
    from repro.models.attention import ATTN_IMPL
    ATTN_IMPL["mode"] = run.train.attn_impl
    ctx = StackCtx(cfg=cfg, shard=make_shard_fn(mesh, run.train.sequence_parallel),
                   compute_dtype=compute_dtype,
                   remat="none", scan_layers=run.train.scan_layers, dp_shards=dp_sh,
                   moe_apply=make_moe_apply(mesh, cfg) if cfg.is_moe else None)
    params_s = jax.eval_shape(lambda k: model.init(k, shape.seq_len),
                              jax.random.PRNGKey(0))
    params_s = _cast_struct(params_s, compute_dtype)  # serving: bf16 weight storage
    batch_s = model.input_specs(shape)
    batch_s = {k: v for k, v in batch_s.items() if k not in ("labels",)}

    def prefill(params, batch):
        logits, _ = model.forward(tree_cast(params, compute_dtype), batch, ctx)
        return logits

    shardings = (params_shardings(params_s, cfg, mesh), batch_shardings(batch_s, mesh))
    fn = jax.jit(prefill, in_shardings=shardings)
    meta = {"kind": "prefill", "tokens_per_step": shape.global_batch * shape.seq_len}
    return BuiltStep(fn=fn, args=(params_s, batch_s), shardings=shardings, meta=meta)


def build_decode_step(run: RunConfig, mesh) -> BuiltStep:
    cfg, shape = run.model, run.shape
    model = build_model(cfg)
    compute_dtype = compute_dtype_of(run.train.compute_dtype)
    n_dp = int(np.prod([mesh.shape[a] for a in dp_axes(mesh)]))
    dp_sh = n_dp if shape.global_batch % n_dp == 0 else 1
    from repro.models.attention import ATTN_IMPL
    ATTN_IMPL["mode"] = run.train.attn_impl
    ctx = StackCtx(cfg=cfg, shard=make_shard_fn(mesh), compute_dtype=compute_dtype,
                   remat="none", scan_layers=run.train.scan_layers, dp_shards=dp_sh,
                   moe_apply=make_moe_apply(mesh, cfg) if cfg.is_moe else None)
    b = shape.global_batch
    params_s = jax.eval_shape(lambda k: model.init(k, shape.seq_len),
                              jax.random.PRNGKey(0))
    params_s = _cast_struct(params_s, compute_dtype)  # serving: bf16 weight storage
    kv_dtype = jnp.dtype(run.train.kv_dtype)
    caches_s = jax.eval_shape(
        functools.partial(model.init_cache, None, b, shape.seq_len, dtype=kv_dtype)
    ) if cfg.family != "encdec" else jax.eval_shape(
        lambda p: model.init_cache(p, b, shape.seq_len, dtype=kv_dtype), params_s
    )
    batch_s = model.decode_specs(shape)
    idx_s = jax.ShapeDtypeStruct((), jnp.int32)

    def decode(params, caches, batch, index):
        logits, new_caches = model.decode(
            tree_cast(params, compute_dtype), batch, caches, index, ctx
        )
        return logits, new_caches

    shardings = (
        params_shardings(params_s, cfg, mesh),
        cache_shardings(caches_s, mesh, cfg, b),
        batch_shardings(batch_s, mesh),
        NamedSharding(mesh, P()),
    )
    fn = jax.jit(decode, in_shardings=shardings, donate_argnums=(1,))
    meta = {"kind": "decode", "tokens_per_step": b,
            "cache_len": shape.seq_len}
    return BuiltStep(fn=fn, args=(params_s, caches_s, batch_s, idx_s),
                     shardings=shardings, meta=meta)


def build_step(run: RunConfig, mesh, **kw) -> BuiltStep:
    if run.shape.kind == "train":
        return build_train_step(run, mesh, **kw)
    if run.shape.kind == "prefill":
        return build_prefill_step(run, mesh)
    return build_decode_step(run, mesh)
