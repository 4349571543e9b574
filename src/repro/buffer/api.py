"""Config-driven dispatch over the buffer subsystem.

``repro.core`` talks to the buffer exclusively through these three functions: they
pick the policy from ``RehearsalConfig.policy`` and route to the flat or tiered
store, so every caller (sync step, pipelined step, shard_map exchange body,
pjit step builders) stays agnostic of which variant is configured. With the
defaults — ``policy='reservoir'``, ``tiering='off'`` — the dispatch collapses to
the exact pre-subsystem code path (the parity contract).
"""
from __future__ import annotations

from typing import Union

import jax
import jax.numpy as jnp

from repro.buffer.policies import resolve_policy
from repro.buffer.state import BufferState, init_buffer, local_sample, local_update
from repro.buffer.tiered import (
    TieredState,
    init_tiered,
    tiered_fill,
    tiered_obs,
    tiered_sample,
    tiered_update,
)
from repro.obs.scopes import scope

AnyBufferState = Union[BufferState, TieredState]


def _policy_of(rcfg):
    return resolve_policy(getattr(rcfg, "policy", None) if rcfg is not None else None)


def init_from_config(item_spec, rcfg) -> AnyBufferState:
    """Allocate the buffer the config describes: flat (HBM-only) or tiered."""
    pol = _policy_of(rcfg)
    if getattr(rcfg, "tiered", False):
        return init_tiered(item_spec, rcfg.num_buckets, rcfg.resolved_hot_slots,
                           rcfg.resolved_cold_slots, rcfg.resolved_demote_stage, pol)
    return init_buffer(item_spec, rcfg.num_buckets, rcfg.slots_per_bucket, pol)


def _fused_of(rcfg) -> bool:
    return bool(getattr(rcfg, "fused_kernels", False)) if rcfg is not None else False


def buffer_update(state: AnyBufferState, items, labels, key, rcfg, *,
                  cold_host: bool = False) -> AnyBufferState:
    """Policy-driven Alg-1 push of a candidate mini-batch into either store.
    ``cold_host``: a tiered store's cold records live in host memory."""
    pol = _policy_of(rcfg)
    with scope("buffer_update"):
        if isinstance(state, TieredState):
            return tiered_update(state, items, labels, key, rcfg.num_candidates,
                                 pol, fused=_fused_of(rcfg), cold_host=cold_host)
        return local_update(state, items, labels, key, rcfg.num_candidates, pol)


def buffer_sample(state: AnyBufferState, key, n: int, rcfg=None, *,
                  cold_host: bool = False):
    """Draw ``n`` representatives from either store under the configured policy."""
    pol = _policy_of(rcfg)
    with scope("buffer_sample"):
        if isinstance(state, TieredState):
            return tiered_sample(state, key, n, pol, fused=_fused_of(rcfg),
                                 cold_host=cold_host)
        return local_sample(state, key, n, pol)


def buffer_fill(state: AnyBufferState) -> jnp.ndarray:
    """Total resident records (the ``buffer_fill`` training metric)."""
    if isinstance(state, TieredState):
        return tiered_fill(state)
    return jnp.sum(state.counts)


def buffer_obs(state: AnyBufferState, rcfg=None):
    """Jit-safe ``obs/*`` gauges of either store (f32 scalars, DESIGN.md §11):
    fill totals, per-bucket min/max, offered-minus-resident eviction/demotion
    counters, plus whatever the active policy's ``obs_aux`` adds (GRASP's mean
    prototype distance). Pure reads — no RNG, no state change — and
    shape-polymorphic over local ``[K]`` and distributed ``[N_dp, K]`` states."""
    pol = _policy_of(rcfg)
    if isinstance(state, TieredState):
        out = tiered_obs(state)
        aux_host = state.hot  # the policy governs the hot tier
    else:
        k = state.counts.shape[-1]
        counts = state.counts.reshape(-1, k).sum(0).astype(jnp.float32)
        fill = jnp.sum(counts)
        offered = jnp.sum(state.seen).astype(jnp.float32)
        out = {
            "obs/fill": fill,
            "obs/bucket_fill_min": jnp.min(counts),
            "obs/bucket_fill_max": jnp.max(counts),
            "obs/evictions": jnp.maximum(offered - fill, 0.0),
        }
        aux_host = state
    out.update(pol.obs_aux(aux_host))
    return out


def resolve_placement(rcfg, devices=None) -> str:
    """Resolved storage placement of the configured buffer's bulk capacity:
    ``'device'`` for flat (HBM-only) configs, and for tiered configs the
    platform's cold-tier memory (``tiered.resolve_cold_placement``:
    ``'pinned_host'`` on an accelerator, ``'device'`` on the CPU). Dry-run
    records and ``BuiltStep.meta`` surface it."""
    from repro.buffer.tiered import resolve_cold_placement

    if not getattr(rcfg, "tiered", False):
        return "device"
    return resolve_cold_placement(devices)


def resolve_field(explicit, rcfg, attr: str, default: str) -> str:
    """Record-field name resolution: explicit argument > RehearsalConfig > default.

    This is the single place the ``label_field``/``task_field`` plumbing funnels
    through — call sites pass None to inherit the config's field names."""
    if explicit is not None:
        return explicit
    if rcfg is not None:
        return getattr(rcfg, attr, default)
    return default
