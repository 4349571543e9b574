"""Buffer store: the paper's per-process B_n with policy-driven Algorithm-1 updates.

The buffer stores *records* — arbitrary pytrees matching one training sample (tokens +
labels + task id for LMs; images + label for the paper's CNNs). Each leaf is stored as
``[K, slots, *leaf_shape]``: K per-class/per-task sub-buffers R_n^i with ``slots``
capacity each (= S_max / K, the paper's even split that avoids class bias).

What goes in, what gets evicted, and what comes out are delegated to a pluggable
``Policy`` (repro.buffer.policies); the default reservoir policy reproduces the
paper's Algorithm 1 bit-for-bit (the parity contract, tests/test_buffer_policies).
The store itself stays a dumb static-shape pytree: validity travels as masks, and a
policy's private state lives in ``BufferState.aux``.

Everything here is per-worker ("embarrassingly parallel" — paper §IV-B); the
cross-worker exchange lives in ``repro.core.distributed``, the HBM/host tiered
variant in ``repro.buffer.tiered``. All functions are jit-safe with static shapes.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs.scopes import scope


class BufferState(NamedTuple):
    """Per-worker rehearsal buffer B_n (a pytree: ``data`` leaves are [K, slots, ...]).

    ``aux`` is the active policy's private state (empty for the default reservoir:
    FIFO carries a write cursor, GRASP carries class prototypes + per-slot
    distances). It defaults to ``()`` so three-field construction sites — and
    checkpoints written before the subsystem existed — keep working unchanged.
    """

    data: Any  # pytree of [K, slots, *item_shape]
    counts: jnp.ndarray  # i32[K] filled slots per bucket
    seen: jnp.ndarray  # i32[K] total candidates offered per bucket (stats)
    aux: Any = ()  # policy-private state (pytree; () = stateless policy)


def init_buffer(item_spec, num_buckets: int, slots: int, policy=None) -> BufferState:
    """``item_spec``: pytree of ShapeDtypeStruct (or arrays) describing ONE record."""
    from repro.buffer.policies import resolve_policy

    def alloc(leaf):
        shape = (num_buckets, slots) + tuple(leaf.shape)
        return jnp.zeros(shape, leaf.dtype)

    return BufferState(
        data=jax.tree_util.tree_map(alloc, item_spec),
        counts=jnp.zeros((num_buckets,), jnp.int32),
        seen=jnp.zeros((num_buckets,), jnp.int32),
        aux=resolve_policy(policy).init_aux(item_spec, num_buckets, slots),
    )


def buffer_dims(state: BufferState) -> Tuple[int, int]:
    leaf = jax.tree_util.tree_leaves(state.data)[0]
    return leaf.shape[0], leaf.shape[1]  # (K, slots)


def _row_start(buf, f):
    cap = buf.shape[1]
    return (f // cap, f % cap) + (0,) * (buf.ndim - 2)


def read_rows(buf, flat):
    """Flat rows ``flat`` (i32[n], in range) of a ``[K, slots, ...]`` leaf:
    ``[n, ...]``, one dynamic slice per row.

    Row moves of the buffer are dynamic slices, unrolled, never a gather or
    scatter over the leaf reshaped to ``[K * slots, ...]`` and never a loop.
    XLA:TPU stores a large leaf in the layout that packs it best, which can
    put the slot axis minor-most (an f32[4, 2750, 224, 224, 3] leaf has its
    slots innermost). A row gather or scatter cannot run in that layout, nor
    can a loop that carries the leaf, so XLA then copies the whole leaf to
    another layout and back; an unrolled dynamic slice runs in place in any
    layout."""
    shape = (1, 1) + buf.shape[2:]
    if flat.shape[0] == 0:
        return jnp.zeros((0,) + buf.shape[2:], buf.dtype)
    return jnp.concatenate([jax.lax.dynamic_slice(buf, _row_start(buf, flat[i]), shape)[0]
                            for i in range(flat.shape[0])])


def write_rows(buf, flat, rows):
    """``buf`` (``[K, slots, ...]``) with ``rows[i]`` written to flat row
    ``flat[i]``, in place (see ``read_rows``): the semantics of
    ``.at[flat].set(rows, mode="drop")`` on the flat view. Negative rows wrap,
    rows outside ``[0, K * slots)`` are dropped, and where two rows share a
    target the later one wins.

    Every candidate makes one write. The dropped ones come first and write
    flat row 0 back as it was, then the kept ones follow in order."""
    total = buf.shape[0] * buf.shape[1]
    f = jnp.where(flat < 0, flat + total, flat)
    keep = (f >= 0) & (f < total)
    order = jnp.argsort(keep, stable=True)
    row0 = buf[:1, :1]
    out = buf
    for i in range(flat.shape[0]):
        j, ok = order[i], keep[order[i]]
        row = jax.lax.dynamic_index_in_dim(rows, j, keepdims=True)[None]
        out = jax.lax.dynamic_update_slice(
            out, jnp.where(ok, row.astype(buf.dtype), row0),
            _row_start(buf, jnp.where(ok, f[j], 0)))
    return out


def local_update(
    state: BufferState, items, labels, key, num_candidates: int, policy=None,
    accept_mask=None,
) -> BufferState:
    """Algorithm 1, vectorised and policy-parameterised.

    ``items``: record pytree with leading batch axis [b, ...]; ``labels``: i32[b]
    bucket ids. The policy decides acceptance (default: every sample enters R_n^i
    with probability c/b) and the eviction slot for full buckets (default: uniform
    at random — age-agnostic, so each stored representative of a class is equally
    likely to be replaced). New candidates always fill empty slots in arrival
    order. ``accept_mask`` overrides the acceptance lottery (tiered demotion
    flushes insert every staged-valid record unconditionally).
    """
    new_state, _, _ = _local_update_traced(
        state, items, labels, key, num_candidates, policy, accept_mask
    )
    return new_state


def local_update_with_evicted(
    state: BufferState, items, labels, key, num_candidates: int, policy=None
):
    """``local_update`` that also returns the records it overwrote.

    Returns ``(new_state, evicted items [b, ...], evicted_valid bool[b])`` where
    ``evicted_valid[i]`` marks candidates that displaced a previously *filled* slot
    (the demotion feed of the tiered store). When several candidates of one batch
    target the same slot, each reports the pre-batch occupant — the intermediate
    overwrite is lost, the bounded-staging analogue of a dropped demotion.
    """
    return _local_update_traced(state, items, labels, key, num_candidates, policy)


def local_update_rows(state, labels, key, num_candidates, policy=None,
                      accept_mask=None):
    """Row-targeting core of Algorithm 1: which flat buffer rows this batch
    writes, and the count bookkeeping — WITHOUT touching the record bytes.

    Shared verbatim by the XLA scatter path (``_local_update_traced``) and the
    fused Pallas encode-on-scatter path (``buffer.tiered`` with
    ``fused_kernels=True``): both consume the key with the same
    ``(k_accept, k_evict)`` split and emit the same target rows, which is what
    makes the two paths bit-identical.

    Returns ``(flat i32[b], accept bool[b], pos i32[b], slot i32[b],
    new_counts, new_seen)`` where ``flat[i] == K*cap`` (OOB) marks a dropped
    candidate.
    """
    from repro.buffer.policies import resolve_policy

    pol = resolve_policy(policy)
    k_buckets, cap = buffer_dims(state)
    k_accept, k_evict = jax.random.split(key)

    if accept_mask is None:
        accept = pol.select_candidates(state, labels, k_accept, num_candidates)
    else:
        accept = accept_mask
    onehot = jax.nn.one_hot(labels, k_buckets, dtype=jnp.int32) * accept[:, None].astype(
        jnp.int32
    )
    # rank among *prior* accepted candidates of the same bucket within this batch
    rank = jnp.take_along_axis(
        jnp.cumsum(onehot, axis=0) - onehot, labels[:, None], axis=1
    )[:, 0]
    pos = state.counts[labels] + rank
    slot = pol.evict(state, labels, pos, rank, k_evict)
    flat = jnp.where(accept, labels * cap + slot, k_buckets * cap)  # OOB ⇒ dropped
    accepted_per_bucket = jnp.sum(onehot, axis=0)
    new_counts = jnp.minimum(cap, state.counts + accepted_per_bucket)
    new_seen = state.seen + jnp.sum(
        jax.nn.one_hot(labels, k_buckets, dtype=jnp.int32), axis=0
    )
    return flat, accept, pos, slot, new_counts, new_seen


def _local_update_traced(state, items, labels, key, num_candidates, policy=None,
                         accept_mask=None):
    from repro.buffer.policies import resolve_policy

    pol = resolve_policy(policy)
    k_buckets, cap = buffer_dims(state)
    flat, accept, pos, slot, new_counts, new_seen = local_update_rows(
        state, labels, key, num_candidates, pol, accept_mask
    )
    # a true demotion displaces a slot that was filled BEFORE this batch; a slot
    # filled earlier within the same batch yields the pre-batch (empty) value, so
    # it must not be reported (the within-batch occupant is simply dropped)
    evicted_valid = accept & (pos >= cap) & (slot < state.counts[labels])

    safe = jnp.clip(flat, 0, k_buckets * cap - 1)
    evicted = jax.tree_util.tree_map(lambda buf: read_rows(buf, safe), state.data)
    new_data = jax.tree_util.tree_map(lambda buf, it: write_rows(buf, flat, it),
                                      state.data, items)
    new_aux = pol.update_aux(state, items, labels, accept, flat, new_counts)
    return BufferState(new_data, new_counts, new_seen, new_aux), evicted, evicted_valid


def local_sample_rows(state: BufferState, key, n: int, policy=None):
    """Row-selection core of sampling: the flat rows the policy draws, without
    gathering the record bytes. Returns ``(flat i32[n], valid bool[n])`` with
    ``flat`` always in-range (validity travels as the mask).

    The gather hook of the fused dequant-on-gather path (``buffer.tiered`` with
    ``fused_kernels=True``): the fused and XLA paths call this identically, so
    they consume the same key and read the same rows.
    """
    from repro.buffer.policies import resolve_policy

    return resolve_policy(policy).sample(state, key, n)


def local_sample(state: BufferState, key, n: int, policy=None):
    """Draw ``n`` records from this worker's buffer under the policy's sampling rule.

    Returns (items pytree [n, ...], valid bool[n]). The default reservoir rule is
    uniform over *filled* slots — every stored representative has equal selection
    probability regardless of class, the unbiased sampling the paper requires.
    (Drawn with replacement; for n ≪ |B_n| this matches the paper's
    without-replacement sampling to O(n/|B_n|).)
    """
    flat, valid = local_sample_rows(state, key, n, policy)

    return jax.tree_util.tree_map(lambda buf: read_rows(buf, flat), state.data), valid


def mask_invalid(items, valid, label_field: str = "labels"):
    """Neutralise invalid records: set their loss labels to -1 (ignored by the CE)."""

    def fix(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in (label_field, "label"):
            shape = (leaf.shape[0],) + (1,) * (leaf.ndim - 1)
            return jnp.where(valid.reshape(shape), leaf, -1)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, items)


def augment_batch(batch, reps, valid, label_field: str = "labels"):
    """Concatenate the incoming mini-batch (size b) with r representatives → b + r.

    Invalid representatives (empty buffer at step 0 — the paper trains un-augmented on
    the first iteration) contribute zero loss via label masking, preserving static
    shapes.
    """
    with scope("augment"):
        reps = mask_invalid(reps, valid, label_field)
        return jax.tree_util.tree_map(
            lambda a, b_: jnp.concatenate([a, b_.astype(a.dtype)], axis=0), batch, reps
        )
