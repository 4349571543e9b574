"""Two-tier rehearsal store: hot working set in HBM, cold majority spilled as int8.

The paper's accuracy curve (Fig. 5a) is monotone in S_max, but a device-resident
buffer caps S_max at HBM size. This store splits each bucket into

  * a **hot tier** — raw records in device HBM, managed by the active policy
    (repro.buffer.policies); every Alg-1 insertion lands here first, and
  * a **cold tier** — records the hot tier evicts, row-quantized to int8 through
    the existing ``kernels/quantize.py`` + ``core/compression.py`` path (4x byte
    saving) and, on TPU, placed in host memory (``cold_shardings``), so
    ``slots_per_bucket`` can exceed device memory. A host-resident cold tier
    (``cold_host=True``) is never touched in place by device code: the sampled
    rows and the demoted rows cross memory kinds one row at a time
    (``host_gather_rows`` / ``host_scatter_rows``).

Demotion is *asynchronous and batched*, mirroring the PR-1 pipelining discipline
(DESIGN.md §3/§6): records evicted from the hot tier at step t are parked in a
fixed-size staging buffer and flushed — one batched encode + insert — by step
t+1's update, which shares no data dependency with the gradient subgraph, so
XLA's latency-hiding scheduler keeps the quantization off the critical path. The
staging buffer is bounded (``stage_rows``); eviction bursts beyond it drop the
overflow, exactly as a non-tiered buffer would have destroyed those records.

Sampling (promotion) draws tier-proportionally: a record is taken from the hot or
cold tier with probability proportional to that tier's fill, and cold rows are
dequantized on the way out — uniform within each tier ⇒ uniform over the union,
preserving the paper's unbiased sampling. All shapes static, everything jit-safe.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.buffer.policies import resolve_policy
from repro.buffer.state import (
    BufferState,
    buffer_dims,
    init_buffer,
    local_sample,
    local_sample_rows,
    local_update_rows,
    local_update_with_evicted,
)


class TieredState(NamedTuple):
    """Hot + cold tiers plus the one-step-stale demotion staging buffer."""

    hot: BufferState  # raw records [K, hot_slots, ...]
    cold: BufferState  # compressed records (int8 q + f32 scale) [K, cold_slots, ...]
    stage: Any  # raw record pytree [stage_rows, ...] awaiting demotion
    stage_labels: jnp.ndarray  # i32[stage_rows]
    stage_valid: jnp.ndarray  # bool[stage_rows]


def _compression():
    from repro.core import compression  # lazy: repro.core imports this package

    return compression


def init_tiered(item_spec, num_buckets: int, hot_slots: int, cold_slots: int,
                stage_rows: int, policy=None) -> TieredState:
    """Allocate both tiers + staging. The policy governs the hot tier; the cold
    tier is a plain reservoir archive (its records are opaque int8 blobs)."""
    comp = _compression()
    hot = init_buffer(item_spec, num_buckets, hot_slots, policy)
    cold = init_buffer(comp.compressed_spec(item_spec), num_buckets, cold_slots)

    def alloc(leaf):
        return jnp.zeros((stage_rows,) + tuple(leaf.shape), leaf.dtype)

    return TieredState(
        hot=hot,
        cold=cold,
        stage=jax.tree_util.tree_map(alloc, item_spec),
        stage_labels=jnp.zeros((stage_rows,), jnp.int32),
        stage_valid=jnp.zeros((stage_rows,), bool),
    )


def tiered_dims(state: TieredState) -> Tuple[int, int, int]:
    """(K, hot_slots, cold_slots)."""
    k, hot = buffer_dims(state.hot)
    return k, hot, buffer_dims(state.cold)[1]


def record_spec_of(state: TieredState):
    """Record ShapeDtypeStruct pytree recovered from the hot tier's leaves."""
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape[2:], l.dtype), state.hot.data
    )


def _pack_stage(evicted, labels, valid, stage_rows: int):
    """Compact the [b]-sized eviction feed into the fixed [stage_rows] staging slot
    (valid rows first; overflow beyond ``stage_rows`` is dropped)."""
    b = labels.shape[0]
    order = jnp.argsort(jnp.logical_not(valid))  # stable: valid rows first
    if b >= stage_rows:
        take = order[:stage_rows]
        in_range = jnp.ones((stage_rows,), bool)
    else:
        take = jnp.concatenate([order, jnp.zeros((stage_rows - b,), order.dtype)])
        in_range = jnp.arange(stage_rows) < b
    stage = jax.tree_util.tree_map(lambda x: x[take], evicted)
    return stage, labels[take], valid[take] & in_range


# Smallest record (in elements) the cold tier keeps in host memory. A host row
# transfer moves whole lane tiles, and the TPU cannot update a sub-tile slice
# of a host buffer; per-record scalars (labels, task ids, int8 row scales) are
# a few bytes per record and stay in device memory.
HOST_MIN_RECORD = 128


def host_resident(record_shape) -> bool:
    """Whether a cold leaf with this per-record shape lives in host memory
    when the cold tier is host-placed."""
    n = 1
    for d in record_shape:
        n *= d
    return len(record_shape) > 0 and n >= HOST_MIN_RECORD


def _host_space(x):
    return jax.device_put(x, jax.memory.Space.Host)


def _cold_rows_get(leaf, rows, cold_host: bool):
    if cold_host and host_resident(leaf.shape[2:]):
        return host_gather_rows(leaf, rows)
    return leaf.reshape((-1,) + leaf.shape[2:])[rows]


def _cold_rows_set(leaf, rows, values, cold_host: bool):
    if cold_host and host_resident(leaf.shape[2:]):
        return host_scatter_rows(leaf, rows, values)
    flat = leaf.reshape((-1,) + leaf.shape[2:])
    return flat.at[rows].set(values.astype(leaf.dtype), mode="drop").reshape(leaf.shape)


def host_gather_rows(leaf, rows):
    """Copy flat rows ``rows`` (i32[n], in range) of a host-resident
    ``[K, slots, ...]`` leaf into device memory: one row transfer each, so
    only the sampled bytes cross to the device. Returns ``[n, ...]``."""
    flat = _host_space(leaf).reshape((-1,) + leaf.shape[2:])
    n = rows.shape[0]

    def one(i, out):
        row = jax.lax.dynamic_index_in_dim(flat, rows[i], keepdims=False)
        return out.at[i].set(jax.device_put(row, jax.memory.Space.Device))

    return jax.lax.fori_loop(0, n, one, jnp.zeros((n,) + flat.shape[1:], leaf.dtype))


def host_scatter_rows(leaf, rows, values):
    """Write ``values[i]`` into flat row ``rows[i]`` of a host-resident
    ``[K, slots, ...]`` leaf, one row transfer each, in order (duplicate rows:
    last write wins, as the XLA scatter). Rows outside ``[0, K*slots)`` are
    dropped: their target keeps its bytes. Returns the updated leaf."""
    r = leaf.shape[0] * leaf.shape[1]
    flat = _host_space(leaf).reshape((r,) + leaf.shape[2:])

    def one(i, flat):
        keep = (rows[i] >= 0) & (rows[i] < r)
        idx = jnp.clip(rows[i], 0, r - 1)
        old = jax.device_put(jax.lax.dynamic_index_in_dim(flat, idx, keepdims=False),
                             jax.memory.Space.Device)
        new = jnp.where(keep, values[i].astype(leaf.dtype), old)
        return jax.lax.dynamic_update_index_in_dim(flat, _host_space(new), idx, 0)

    flat = jax.lax.fori_loop(0, rows.shape[0], one, flat)
    return flat.reshape(leaf.shape)


def tiered_flush(state: TieredState, key, *, fused: bool = False,
                 cold_host: bool = False) -> TieredState:
    """Flush the pending demotions (staged at step t−1) into the cold archive:
    one batched int8 encode + reservoir insert. Clears ``stage_valid`` so a
    standalone flush cannot re-demote the same rows; ``tiered_update``
    overwrites the stage anyway.

    ``fused=True`` routes through the encode-on-scatter Pallas kernel
    (``compression.encode_scatter_batch``): the staged rows are quantized and
    written into their cold target rows in one pass, with no intermediate
    encoded batch. Row targeting and key use go through the same
    ``local_update_rows`` as the XLA path, so both are bit-identical. The cold
    tier always runs the default reservoir policy (stateless aux), which is
    what lets the fused form skip the generic ``update_aux`` hook.

    ``cold_host=True`` (the cold records live in host memory): the staged rows
    are encoded on the device and each lands in its host row through
    ``host_scatter_rows``. ``fused`` does not apply there: the fused kernels
    address an HBM table."""
    comp = _compression()
    flat, _, _, _, new_counts, new_seen = local_update_rows(
        state.cold, state.stage_labels, key,
        num_candidates=state.stage_labels.shape[0],
        accept_mask=state.stage_valid)
    spec = record_spec_of(state)
    if fused and not cold_host:
        new_data = comp.encode_scatter_batch(state.cold.data, state.stage,
                                             spec, flat)
    else:
        new_data = jax.tree_util.tree_map(
            lambda leaf, x: _cold_rows_set(leaf, flat, x, cold_host),
            state.cold.data, comp.encode_batch(state.stage, spec))
    cold = BufferState(new_data, new_counts, new_seen, state.cold.aux)
    return state._replace(cold=cold,
                          stage_valid=jnp.zeros_like(state.stage_valid))


def tiered_push(state: TieredState, items, labels, key, num_candidates: int,
                policy=None) -> TieredState:
    """Policy-driven hot-tier update, staging whatever it displaced for the
    next flush (the stage is fully replaced — call ``tiered_flush`` first)."""
    pol = resolve_policy(policy)
    hot, evicted, evicted_valid = local_update_with_evicted(
        state.hot, items, labels, key, num_candidates, pol
    )
    stage, stage_labels, stage_valid = _pack_stage(
        evicted, labels, evicted_valid, state.stage_labels.shape[0]
    )
    return TieredState(hot, state.cold, stage, stage_labels, stage_valid)


def tiered_update(state: TieredState, items, labels, key, num_candidates: int,
                  policy=None, *, fused: bool = False,
                  cold_host: bool = False) -> TieredState:
    """One tiered Alg-1 step: flush last step's staged demotions into the cold tier
    (batched int8 encode — off the critical path), update the hot tier under the
    policy, and stage whatever the hot tier evicted for the next flush.

    Composed as ``tiered_push(tiered_flush(state, k_flush), ..., k_hot)`` with
    the same key split as always — bit-identical to the pre-decomposition fused
    form (the flush touches only ``cold``/``stage_valid``; the push reads
    ``hot`` and replaces the stage wholesale)."""
    k_hot, k_flush = jax.random.split(key)
    flushed = tiered_flush(state, k_flush, fused=fused, cold_host=cold_host)
    return tiered_push(flushed, items, labels, k_hot, num_candidates, policy)


def tiered_sample(state: TieredState, key, n: int, policy=None, *,
                  fused: bool = False, cold_host: bool = False):
    """Draw ``n`` records across both tiers, tier chosen ∝ fill (unbiased over the
    union); cold rows are dequantized back to the record dtypes. Returns
    (items [n, ...], valid bool[n]).

    ``fused=True`` reads the cold tier through the dequant-on-gather Pallas
    kernel (``compression.decode_gather_batch``): int8 rows dequantize in VMEM
    on the way out instead of materialising a full-width gathered batch first.
    Row selection shares ``local_sample_rows`` with the XLA path — same key
    use, same rows, bit-identical output.

    ``cold_host=True``: the same rows are copied out of host memory one by one
    (``host_gather_rows``) and decoded on the device; ``fused`` does not
    apply there."""
    comp = _compression()
    k_hot, k_cold, k_mix = jax.random.split(key, 3)
    hot_items, hot_valid = local_sample(state.hot, k_hot, n, policy)
    cold_rows, cold_valid = local_sample_rows(state.cold, k_cold, n)
    if fused and not cold_host:
        cold_items = comp.decode_gather_batch(
            state.cold.data, record_spec_of(state), cold_rows)
    else:
        cold_stored = jax.tree_util.tree_map(
            lambda leaf: _cold_rows_get(leaf, cold_rows, cold_host), state.cold.data)
        cold_items = comp.decode_batch(cold_stored, record_spec_of(state))

    hot_total = jnp.sum(state.hot.counts)
    cold_total = jnp.sum(state.cold.counts)
    total = hot_total + cold_total
    p_hot = hot_total.astype(jnp.float32) / jnp.maximum(total, 1).astype(jnp.float32)
    use_hot = jax.random.uniform(k_mix, (n,)) < p_hot
    use_hot = jnp.where(cold_total == 0, True, jnp.where(hot_total == 0, False, use_hot))

    def pick(h, c):
        sel = use_hot.reshape((n,) + (1,) * (h.ndim - 1))
        return jnp.where(sel, h, c.astype(h.dtype))

    items = jax.tree_util.tree_map(pick, hot_items, cold_items)
    valid = jnp.where(use_hot, hot_valid, cold_valid)
    return items, valid


def tiered_fill(state: TieredState) -> jnp.ndarray:
    """Total records resident across both tiers (the buffer_fill metric)."""
    return jnp.sum(state.hot.counts) + jnp.sum(state.cold.counts)


def tiered_obs(state: TieredState):
    """Jit-safe ``obs/*`` gauges of a tiered store (f32 scalars; DESIGN.md §11).

    Shape-polymorphic over local ``[K, ...]`` and distributed ``[N_dp, K, ...]``
    states: counts reduce over every leading axis. ``evictions``/``demotions``
    are *offered-minus-resident* upper bounds (``seen`` counts every offered
    candidate, accepted or not — the honest derivation that needs no new
    state leaves)."""
    k = state.hot.counts.shape[-1]
    hot_counts = state.hot.counts.reshape(-1, k).sum(0).astype(jnp.float32)
    cold_counts = state.cold.counts.reshape(-1, k).sum(0).astype(jnp.float32)
    hot_fill = jnp.sum(hot_counts)
    cold_fill = jnp.sum(cold_counts)
    hot_offered = jnp.sum(state.hot.seen).astype(jnp.float32)
    per_bucket = hot_counts + cold_counts
    return {
        "obs/fill": hot_fill + cold_fill,
        "obs/hot_fill": hot_fill,
        "obs/cold_fill": cold_fill,
        "obs/bucket_fill_min": jnp.min(per_bucket),
        "obs/bucket_fill_max": jnp.max(per_bucket),
        "obs/evictions": jnp.maximum(hot_offered - hot_fill, 0.0),
        "obs/demotions": jnp.sum(state.cold.seen).astype(jnp.float32),
        "obs/stage_pending": jnp.sum(state.stage_valid).astype(jnp.float32),
    }


COLD_MEMORY_KIND = "pinned_host"  # the HBM-relief memory the cold tier requests


def device_memory_kinds(dev) -> set:
    """Memory kinds one device exposes ({} on runtimes without the API)."""
    try:
        return {m.kind for m in dev.addressable_memories()}
    except (AttributeError, NotImplementedError, RuntimeError):
        return set()


def resolve_cold_placement(devices=None) -> str:
    """Where the cold tier's records live, decided by platform.

    On the CPU it is ``'device'``: host and device memory are the same there,
    and the CPU backend cannot compile memory-space annotations. On an
    accelerator it is ``'pinned_host'``; a runtime that lacks that memory kind
    is an error, never a silent fallback to HBM."""
    # probe a device THIS process can address: in a multi-host run the mesh's
    # device 0 belongs to process 0, and addressable_memories() on a remote
    # device raises
    proc = jax.process_index()
    devs = [d for d in (list(devices) if devices is not None else [])
            if getattr(d, "process_index", proc) == proc]
    dev = devs[0] if devs else jax.local_devices()[0]
    if dev.platform == "cpu":
        return "device"
    kinds = device_memory_kinds(dev)
    if COLD_MEMORY_KIND not in kinds:
        raise RuntimeError(
            f"tiered cold tier: {dev.device_kind} exposes no {COLD_MEMORY_KIND!r} "
            f"memory (kinds: {sorted(kinds) or 'none'}); the cold tier does not "
            f"fall back to device memory")
    return COLD_MEMORY_KIND


def cold_shardings(state: TieredState, mesh, dp_axes):
    """NamedShardings for a distributed TieredState (leading worker axis over
    dp). The cold tier's record leaves go to ``resolve_cold_placement``'s
    memory: ``pinned_host`` on an accelerator, so its capacity is not bounded
    by HBM. Its per-bucket counters and per-record scalars stay in device
    memory (``host_resident``)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    placement = resolve_cold_placement(mesh.devices.flat)

    def worker_axis(leaf):
        return NamedSharding(mesh, P(dp_axes, *([None] * (len(leaf.shape) - 1))))

    def host(leaf):
        s = worker_axis(leaf)
        if placement == COLD_MEMORY_KIND and host_resident(leaf.shape[3:]):
            return s.with_memory_kind(COLD_MEMORY_KIND)
        return s

    cold = jax.tree_util.tree_map(worker_axis, state.cold)
    return TieredState(
        hot=jax.tree_util.tree_map(worker_axis, state.hot),
        cold=cold._replace(data=jax.tree_util.tree_map(host, state.cold.data)),
        stage=jax.tree_util.tree_map(worker_axis, state.stage),
        stage_labels=worker_axis(state.stage_labels),
        stage_valid=worker_axis(state.stage_valid),
    )
