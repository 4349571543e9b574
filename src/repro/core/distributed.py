"""Distributed rehearsal buffer: global sampling across data-parallel workers.

The paper implements global mini-batch augmentation with RDMA-enabled point-to-point
RPCs (Mochi). The TPU-native equivalent here is a fixed-shape ``lax.all_to_all`` inside
``shard_map`` over the data-parallel mesh axes:

  * every worker draws one candidate from its local buffer *per peer* (N items),
  * one all_to_all delivers to each worker exactly one candidate from every peer,
  * each worker keeps a uniformly random r-subset (validity-aware).

Received items are therefore sampled *without replacement at the source level* —
each of the r representatives comes from a distinct, uniformly chosen peer, and
uniformly within that peer's filled slots. With balanced fill levels (symmetric Alg-1
updates) this matches the paper's unbiased global sampling; see DESIGN.md §2 for the
assumption change. Exchange volume is max(r, N)·item_bytes per worker per step.

Exchange modes (``RehearsalConfig`` via the step builder):
  * ``full``      — all_to_all over ('pod','data'): paper-faithful global diversity.
  * ``pod_local`` — all_to_all over 'data' only: hierarchical (beyond-paper) variant
                    that keeps rehearsal traffic off the inter-pod links; sources are
                    uniform within the pod. O(pod_size) volume independent of pod count.
  * ``local``     — no exchange: the paper's biased embarrassingly-parallel baseline.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.buffer import api as buffer_api
from repro.core import rehearsal as rb
from repro.obs.scopes import scope
from repro.utils.compat import shard_map


def init_distributed_buffer(item_spec, num_buckets: int, slots: int, n_dp: int,
                            policy=None):
    """Global buffer: every leaf gets a leading worker axis [N_dp, ...] to shard on dp."""
    local = rb.init_buffer(item_spec, num_buckets, slots, policy)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (n_dp,) + x.shape), local, is_leaf=None
    )


def init_distributed_from_config(item_spec, rcfg, n_dp: int):
    """Config-driven distributed buffer (flat or tiered): worker axis on every leaf."""
    local = buffer_api.init_from_config(item_spec, rcfg)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (n_dp,) + x.shape), local
    )


def _exchange(items, valid, axis_names):
    """One all_to_all: send item j to peer j, receive one item from every peer.

    Deterministic collective — takes no PRNG key. (It used to accept the
    already-consumed ``k_draw`` and ignore it, a replint RPL001 finding.)"""
    with scope("exchange"):
        recv = jax.tree_util.tree_map(
            lambda x: jax.lax.all_to_all(x, axis_names, split_axis=0, concat_axis=0,
                                         tiled=True),
            items,
        )
        recv_valid = jax.lax.all_to_all(valid, axis_names, split_axis=0,
                                        concat_axis=0, tiled=True)
    return recv, recv_valid


def sample_global(state, key, r: int, axis_names, exchange: str, rcfg=None, *,
                  cold_host: bool = False):
    """Per-worker body (inside shard_map). Returns (reps [r, ...], valid bool[r]).

    ``state`` is a BufferState or TieredState; ``rcfg`` selects the sampling policy
    (None ⇒ the paper's uniform-over-filled reservoir rule); ``cold_host``: a
    tiered store's cold records live in host memory."""
    if axis_names is None or exchange == "local":
        return buffer_api.buffer_sample(state, key, r, rcfg, cold_host=cold_host)

    n = jax.lax.psum(1, axis_names)  # number of peers in the exchange group
    k_draw, k_pick = jax.random.split(key)
    items, valid = buffer_api.buffer_sample(state, k_draw, n, rcfg,
                                            cold_host=cold_host)
    recv, recv_valid = _exchange(items, valid, axis_names)
    # keep a uniformly random valid r-subset of the n received candidates
    scores = jax.random.uniform(k_pick, (n,)) + jnp.where(recv_valid, 0.0, 1e3)
    take = jnp.argsort(scores)[:r]
    reps = jax.tree_util.tree_map(lambda x: x[take], recv)
    return reps, recv_valid[take]


class PendingSample(NamedTuple):
    """An in-flight global sample: representatives drawn + exchanged at step *t*
    that the pipelined train step will consume at step *t+1* (DESIGN.md §3).

    ``reps`` are raw (unmasked) so the slot is a pure transport buffer; masking of
    invalid records happens at consumption time (``consume_reps``)."""

    reps: Any  # record pytree [r, ...]
    valid: Any  # bool[r]


def issue_sample(
    state,
    items,
    labels,
    key,
    rcfg,
    axis_names=None,
    exchange: str = "full",
    *,
    cold_host: bool = False,
) -> Tuple[Any, PendingSample]:
    """Producer half of the paper's ``RehearsalBuffer.update`` primitive, per worker:
    push candidates from the incoming mini-batch (Alg. 1), then launch the global
    sampling (local draw + all_to_all) of the next r representatives.

    Returns ``(new_state, pending)``. The collectives inside carry no data
    dependency on the current step's gradients, so when the caller consumes a
    *previous* ``PendingSample`` for training (pipelined mode), XLA's latency-hiding
    scheduler overlaps this exchange with the backward pass (DESIGN.md §3)."""
    k_up, k_samp = jax.random.split(key)
    new_state = buffer_api.buffer_update(state, items, labels, k_up, rcfg,
                                         cold_host=cold_host)
    reps, valid = sample_global(
        new_state, k_samp, rcfg.num_representatives, axis_names, exchange, rcfg,
        cold_host=cold_host)
    return new_state, PendingSample(reps, valid)


def consume_reps(pending: PendingSample, label_field: str = "labels"):
    """Consumer half: materialise a pending sample as training-ready representatives
    (invalid records' labels masked to -1 so they contribute zero loss).
    Returns ``(reps, valid)``."""
    return rb.mask_invalid(pending.reps, pending.valid, label_field), pending.valid


def update_and_sample(
    state,
    items,
    labels,
    key,
    rcfg,
    axis_names=None,
    exchange: str = "full",
    label_field: Optional[str] = None,
):
    """The fused (synchronous) form of the primitive: issue + immediately consume —
    the exchange sits on the critical path (the paper's blocking baseline, Fig. 6).
    ``label_field=None`` inherits ``rcfg.label_field``. Returns (new_state, reps,
    valid)."""
    label_field = buffer_api.resolve_field(label_field, rcfg, "label_field", "labels")
    idx = jax.lax.axis_index(axis_names) if axis_names is not None else 0
    new_state, pending = issue_sample(
        state, items, labels, jax.random.fold_in(key, idx), rcfg, axis_names, exchange
    )
    reps, valid = consume_reps(pending, label_field)
    return new_state, reps, valid


# ---------------------------------------------------------------------------
# shard_map wrappers — used inside the jitted train step
# ---------------------------------------------------------------------------


def _squeeze0(tree):
    # a reshape, not x[0]: on a host-resident leaf it stays a bitcast
    return jax.tree_util.tree_map(lambda x: x.reshape(x.shape[1:]), tree)


def _unsqueeze0(tree):
    return jax.tree_util.tree_map(lambda x: x.reshape((1,) + x.shape), tree)


def make_sharded_update(mesh, dp_axes: Tuple[str, ...], rcfg, exchange: str = "full",
                        label_field: Optional[str] = None):
    """Build ``fn(global_state, global_batch_items, global_labels, key)`` →
    (new_global_state, reps [N_dp, r, ...], valid [N_dp, r]).

    ``global_state`` leaves carry a leading worker axis sharded over ``dp_axes``;
    batch leaves are globally batched on axis 0. The returned fn must be called
    under ``mesh`` (inside or outside jit). ``label_field=None`` inherits
    ``rcfg.label_field``.

    A tiered store's cold records sit in the platform's cold-tier memory
    (``tiered.resolve_cold_placement``); when that is host memory, the body
    moves only the sampled and the demoted rows across memory kinds.
    """
    from repro.buffer import tiered as tiered_mod

    label_field = buffer_api.resolve_field(label_field, rcfg, "label_field", "labels")
    cold_host = bool(rcfg.tiered) and tiered_mod.resolve_cold_placement(
        mesh.devices.flat) == tiered_mod.COLD_MEMORY_KIND
    exchange_axes = None
    if exchange == "full":
        exchange_axes = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    elif exchange == "pod_local":
        exchange_axes = dp_axes[-1]  # innermost axis = within-pod 'data'
    elif exchange != "local":
        raise ValueError(f"unknown exchange mode {exchange!r}")

    def body(state, items, labels, key):
        state = _squeeze0(state)
        axes = exchange_axes
        if exchange == "local":
            axes = None
        # per-worker RNG stream: fold in the linearised dp index
        idx = jax.lax.axis_index(dp_axes if len(dp_axes) > 1 else dp_axes[0])
        k = jax.random.fold_in(key, idx)
        new_state, pending = issue_sample(state, items, labels, k, rcfg, axes,
                                          exchange, cold_host=cold_host)
        reps, valid = consume_reps(pending, label_field)
        return _unsqueeze0(new_state), _unsqueeze0(reps), valid[None]

    def caller(global_state, batch_items, labels, key):
        state_specs = jax.tree_util.tree_map(lambda _: P(dp_axes), global_state)
        item_specs = jax.tree_util.tree_map(lambda _: P(dp_axes), batch_items)
        fn = shard_map(
            body,
            mesh=mesh,
            in_specs=(state_specs, item_specs, P(dp_axes), P()),
            out_specs=(state_specs, jax.tree_util.tree_map(lambda _: P(dp_axes), batch_items), P(dp_axes)),
            check_vma=False,
        )
        return fn(global_state, batch_items, labels, key)

    return caller


def global_replay_mask(global_batch: int, n_dp: int, valid):
    """The ``is_replay`` row mask of an ``augment_global`` layout: f32
    [B_g + N_dp*r], 1.0 exactly on *valid* replay rows (each worker's shard is
    its b new rows followed by its r representatives). Tap strategies (DER)
    mask distillation/CE terms with it."""
    bw = global_batch // n_dp
    m = jnp.concatenate(
        [jnp.zeros((n_dp, bw), jnp.float32), valid.astype(jnp.float32)], axis=1)
    return m.reshape(-1)


def global_batch_rows(aug_tree, global_batch: int, n_dp: int, r: int):
    """Inverse of ``augment_global`` for the new rows: slice the b-per-worker
    batch rows out of augmented [B_g + N_dp*r, ...] leaves and restore the
    original [B_g, ...] order (the rows ``on_store`` attaches aux values to)."""
    bw = global_batch // n_dp

    def one(x):
        x2 = x.reshape((n_dp, bw + r) + x.shape[1:])
        return x2[:, :bw].reshape((global_batch,) + x.shape[1:])

    return jax.tree_util.tree_map(one, aug_tree)


def augment_global(batch, reps, valid, n_dp: int, label_field: str = "labels"):
    """Concat per-worker shards: batch [B_g, ...] (dp-sharded) + reps [N_dp, r, ...] →
    augmented [B_g + N_dp*r, ...] where each worker's shard is its own b + r rows.

    Invalid representatives get their ``label_field`` masked to -1 here, mirroring
    the single-device ``augment_batch`` (idempotent when the producer already
    masked them via ``consume_reps``, as ``make_sharded_update`` does)."""
    def cat(b_leaf, r_leaf):
        bg = b_leaf.shape[0]
        b2 = b_leaf.reshape((n_dp, bg // n_dp) + b_leaf.shape[1:])
        out = jnp.concatenate([b2, r_leaf.astype(b_leaf.dtype)], axis=1)
        return out.reshape((bg + n_dp * r_leaf.shape[1],) + b_leaf.shape[1:])

    with scope("augment"):
        flat = jax.tree_util.tree_map(lambda x: x.reshape((-1,) + x.shape[2:]), reps)
        flat = rb.mask_invalid(flat, valid.reshape(-1), label_field)
        reps = jax.tree_util.tree_map(
            lambda x, ref: x.reshape(ref.shape), flat, reps
        )
        return jax.tree_util.tree_map(cat, batch, reps)
