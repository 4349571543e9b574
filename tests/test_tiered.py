"""The two-tier (HBM hot / int8 host cold) rehearsal store (DESIGN.md §6).

Covers the demotion pipeline (evict -> stage -> one-step-stale batched flush),
tier-proportional sampling with dequantization, capacity beyond the hot tier,
and the end-to-end CL step with cold capacity > hot capacity (the acceptance
configuration)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.buffer as B
from repro.configs.base import RehearsalConfig
from repro.core import init_carry, make_cl_step


def _spec(d=8):
    return {
        "x": jax.ShapeDtypeStruct((d,), jnp.float32),
        "label": jax.ShapeDtypeStruct((), jnp.int32),
        "task": jax.ShapeDtypeStruct((), jnp.int32),
    }


def _batch(step, b=16, d=8, n_classes=4):
    r = np.random.default_rng(step)
    lab = r.integers(0, n_classes, b).astype(np.int32)
    return {
        "x": jnp.asarray(r.normal(size=(b, d)).astype(np.float32)),
        "label": jnp.asarray(lab),
        "task": jnp.asarray(lab % 2),
    }


def test_init_shapes_and_config_resolution():
    st = B.init_tiered(_spec(), num_buckets=2, hot_slots=4, cold_slots=12,
                       stage_rows=8)
    assert B.tiered_dims(st) == (2, 4, 12)
    assert st.hot.data["x"].shape == (2, 4, 8)
    # int8 rows, each one 128-lane slab (8 elements zero-padded to 128)
    assert st.cold.data["x"]["q"].shape == (2, 12, 1, 128)
    assert st.cold.data["x"]["q"].dtype == jnp.int8
    assert st.cold.data["label"]["raw"].shape == (2, 12)  # ints pass through
    assert st.stage["x"].shape == (8, 8)

    rcfg = RehearsalConfig(num_buckets=2, slots_per_bucket=4, tiering="host",
                           cold_slots=0, num_candidates=5)
    assert rcfg.tiered
    assert rcfg.resolved_hot_slots == 4
    assert rcfg.resolved_cold_slots == 12  # 3x hot default
    assert rcfg.resolved_demote_stage == 10
    assert rcfg.total_slots_per_bucket == 16
    st2 = B.init_from_config(_spec(), rcfg)
    assert isinstance(st2, B.TieredState)
    assert not RehearsalConfig().tiered
    assert isinstance(B.init_from_config(_spec(), RehearsalConfig()), B.BufferState)


def test_demotion_is_one_step_stale_and_batched():
    """Records evicted from the hot tier at step t appear in the cold tier only
    after step t+1's update (the pipelined flush)."""
    st = B.init_tiered(_spec(), 2, hot_slots=2, cold_slots=16, stage_rows=16)
    key = jax.random.PRNGKey(0)
    bt = _batch(0)
    # c == b: accept all 16 -> hot (2x2) overflows. Step 0 displaces only slots
    # filled within the same batch (pre-batch buffer empty) -> nothing to demote.
    st = B.tiered_update(st, bt, bt["task"], jax.random.fold_in(key, 0), 16)
    assert int(jnp.sum(st.hot.counts)) == 4
    assert int(st.stage_valid.sum()) == 0
    assert int(jnp.sum(st.cold.counts)) == 0
    # step 1: every accepted candidate displaces a pre-batch record -> staged...
    bt1 = _batch(1)
    st = B.tiered_update(st, bt1, bt1["task"], jax.random.fold_in(key, 1), 16)
    staged = int(st.stage_valid.sum())
    assert staged > 0
    assert int(jnp.sum(st.cold.counts)) == 0  # ...but not yet flushed
    # step 2 flushes step 1's stage into the cold tier
    bt2 = _batch(2)
    st = B.tiered_update(st, bt2, bt2["task"], jax.random.fold_in(key, 2), 16)
    assert int(jnp.sum(st.cold.counts)) == staged


def test_cold_records_roundtrip_quantized():
    """A demoted record sampled back out matches its original within the int8 grid."""
    spec = {"x": jax.ShapeDtypeStruct((16,), jnp.float32),
            "task": jax.ShapeDtypeStruct((), jnp.int32)}
    st = B.init_tiered(spec, 1, hot_slots=1, cold_slots=32, stage_rows=8)
    key = jax.random.PRNGKey(0)
    rows = jax.random.normal(jax.random.PRNGKey(9), (4, 16))
    for s in range(6):
        items = {"x": rows[s % 4][None], "task": jnp.zeros((1,), jnp.int32)}
        st = B.tiered_update(st, items, items["task"], jax.random.fold_in(key, s), 1)
    assert int(jnp.sum(st.cold.counts)) >= 3
    # force cold draws: hot tier has 1 record, cold several
    got, valid = B.tiered_sample(st, jax.random.PRNGKey(1), 16)
    assert bool(valid.all())
    orig = np.asarray(rows)
    for row in np.asarray(got["x"]):
        err = np.abs(orig - row[None]).max(axis=1).min()
        assert err < 0.05, err  # int8 row quantization error bound


def test_capacity_exceeds_hot_tier():
    """Distinct retrievable records exceed hot capacity — the point of tiering."""
    spec = {"v": jax.ShapeDtypeStruct((), jnp.float32),
            "task": jax.ShapeDtypeStruct((), jnp.int32)}
    st = B.init_tiered(spec, 1, hot_slots=2, cold_slots=16, stage_rows=8)
    key = jax.random.PRNGKey(0)
    for s in range(12):
        items = {"v": jnp.asarray([float(s + 1)]), "task": jnp.zeros((1,), jnp.int32)}
        st = B.tiered_update(st, items, items["task"], jax.random.fold_in(key, s), 1)
    assert int(B.tiered_fill(st)) > 2
    seen = set()
    for t in range(40):
        got, valid = B.tiered_sample(st, jax.random.PRNGKey(t), 4)
        assert bool(valid.all())
        seen |= {round(float(v)) for v in np.asarray(got["v"])}
    assert len(seen) > 2, seen  # more distinct records than the hot tier holds


def test_stage_overflow_drops_excess():
    """Eviction bursts beyond the staging capacity drop the overflow (bounded
    queue), never corrupt shapes or counts."""
    st = B.init_tiered(_spec(), 2, hot_slots=1, cold_slots=4, stage_rows=2)
    key = jax.random.PRNGKey(0)
    for s in range(3):
        bt = _batch(s)  # 16 candidates, all accepted -> many evictions, stage=2
        st = B.tiered_update(st, bt, bt["task"], jax.random.fold_in(key, s), 16)
    assert int(st.stage_valid.sum()) <= 2
    assert (np.asarray(st.cold.counts) <= 4).all()


def test_policy_governs_hot_tier():
    """The configured policy manages the hot tier of a tiered store (FIFO ring)."""
    spec = {"v": jax.ShapeDtypeStruct((), jnp.float32),
            "task": jax.ShapeDtypeStruct((), jnp.int32)}
    rcfg = RehearsalConfig(num_buckets=1, slots_per_bucket=2, tiering="host",
                           hot_slots=2, cold_slots=4, policy="fifo",
                           num_candidates=1)
    st = B.init_from_config(spec, rcfg)
    assert "cursor" in st.hot.aux
    key = jax.random.PRNGKey(0)
    for s in range(5):
        items = {"v": jnp.asarray([float(s)]), "task": jnp.zeros((1,), jnp.int32)}
        st = B.buffer_update(st, items, items["task"], jax.random.fold_in(key, s), rcfg)
    # hot tier holds the two newest records (ring), older ones were demoted
    assert sorted(np.asarray(st.hot.data["v"][0]).tolist()) == [3.0, 4.0]


@pytest.mark.parametrize("pipelined", [False, True])
def test_tiered_cl_step_end_to_end(pipelined):
    """The acceptance config — cold capacity > hot capacity — trains end-to-end
    through make_cl_step (sync and pipelined), loss decreasing."""
    rcfg = RehearsalConfig(num_buckets=2, slots_per_bucket=4, num_representatives=4,
                           num_candidates=8, mode="sync", pipelined=pipelined,
                           tiering="host", hot_slots=4, cold_slots=16,
                           label_field="label")

    def loss_fn(params, b):
        logits = b["x"] @ params["w"]
        onehot = jax.nn.one_hot(jnp.maximum(b["label"], 0), logits.shape[-1])
        mask = (b["label"] >= 0).astype(jnp.float32)
        ce = -jnp.sum(jax.nn.log_softmax(logits) * onehot, axis=-1)
        return jnp.sum(ce * mask) / jnp.maximum(mask.sum(), 1.0), {}

    def sgd(g, o, p):
        return jax.tree_util.tree_map(lambda pp, gg: pp - 0.1 * gg, p, g), o, {}

    step = make_cl_step(loss_fn, sgd, rcfg, strategy="rehearsal",
                        exchange="local", donate=False)
    carry = init_carry({"w": jnp.zeros((8, 4))}, None, _spec(), rcfg)
    key = jax.random.PRNGKey(0)
    for s in range(25):
        carry, m = step(carry, _batch(s), jax.random.fold_in(key, s))
        assert np.isfinite(float(m["loss"])), s
    assert isinstance(carry.buffer, B.TieredState)
    assert float(m["buffer_fill"]) > 2 * 4  # beyond hot capacity
    assert int(jnp.sum(carry.buffer.cold.counts)) > 0


# ---------------------------------------------------------------------------
# Elastic resharding of TieredState (grow / shrink invariance)
# ---------------------------------------------------------------------------


def _distributed_tiered(n_workers, rcfg, steps=8):
    """Stack ``n_workers`` independently-filled per-worker tiered states into a
    distributed state (leading worker axis), as the carry/pjit paths hold it."""
    states = []
    for w in range(n_workers):
        st = B.init_from_config(_spec(), rcfg)
        key = jax.random.PRNGKey(100 + w)
        for s in range(steps):
            bt = _batch(50 * w + s)
            st = B.buffer_update(st, bt, bt["task"], jax.random.fold_in(key, s),
                                 rcfg)
        states.append(st)
    return states, jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def _cold_rows(counts, q):
    """Set of distinct cold int8 rows actually resident (any worker layout)."""
    counts, q = np.asarray(counts), np.asarray(q)
    rows = set()
    for idx in np.ndindex(*counts.shape):
        for j in range(int(counts[idx])):
            rows.add(tuple(np.ravel(q[idx + (j,)]).tolist()))
    return rows


@pytest.mark.parametrize("n_old,n_new", [(2, 4), (4, 2)])
def test_tiered_reshard_grow_shrink_invariance(n_old, n_new):
    """2→4 and 4→2 worker resharding preserve total tiered_fill and the cold
    tier's int8 row contents: a shrink DEMOTES hot overflow into the cold
    archive (what the store itself does on eviction) instead of destroying it,
    so as long as the new aggregate cold capacity absorbs the pool, no record
    is lost."""
    from repro.runtime import reshard_tiered

    rcfg = RehearsalConfig(num_buckets=2, slots_per_bucket=4, tiering="host",
                           hot_slots=4, cold_slots=96, num_candidates=8,
                           num_representatives=3, mode="async",
                           label_field="label", policy="fifo")
    per_worker, dist = _distributed_tiered(n_old, rcfg)
    fill_before = sum(int(B.tiered_fill(s)) for s in per_worker)
    cold_before = _cold_rows(
        np.stack([np.asarray(s.cold.counts) for s in per_worker]),
        np.stack([np.asarray(s.cold.data["x"]["q"]) for s in per_worker]))
    staged_before = sum(int(s.stage_valid.sum()) for s in per_worker)
    assert fill_before > n_old * 2 * 4  # cold tier genuinely populated

    out = reshard_tiered(dist, n_new, policy="fifo")
    assert isinstance(out, B.TieredState)
    assert out.hot.counts.shape == (n_new, 2)
    assert out.cold.counts.shape == (n_new, 2)
    fill_after = int(jnp.sum(out.hot.counts) + jnp.sum(out.cold.counts))
    assert fill_after == fill_before
    # every pre-reshard cold row survives; a shrink adds the demoted hot rows
    cold_after = _cold_rows(out.cold.counts, out.cold.data["x"]["q"])
    assert cold_before <= cold_after
    if n_new >= n_old:
        assert cold_after == cold_before  # grow: nothing demoted
    # pending demotions survive the reshard (aggregate staging capacity allows)
    assert int(out.stage_valid.sum()) == staged_before
    # policy aux was REBUILT for the re-dealt slots, not cloned: the fifo ring
    # cursor must be consistent with each worker's new fill level
    cap = 4
    cursors = np.asarray(out.hot.aux["cursor"])
    counts = np.asarray(out.hot.counts)
    assert cursors.shape == (n_new, 2)
    np.testing.assert_array_equal(cursors, counts % cap)


def test_tiered_reshard_shrink_drops_overflow_uniformly():
    """Shrinking below aggregate capacity drops the tail, never corrupts
    shapes/counts (the paper's random-eviction semantics)."""
    from repro.runtime import reshard_tiered

    rcfg = RehearsalConfig(num_buckets=2, slots_per_bucket=2, tiering="host",
                           hot_slots=2, cold_slots=6, num_candidates=8,
                           num_representatives=2, mode="async",
                           label_field="label")
    per_worker, dist = _distributed_tiered(4, rcfg)
    out = reshard_tiered(dist, 1, policy="reservoir")
    assert (np.asarray(out.hot.counts) <= 2).all()
    assert (np.asarray(out.cold.counts) <= 6).all()
    fill_before = sum(int(B.tiered_fill(s)) for s in per_worker)
    fill_after = int(jnp.sum(out.hot.counts) + jnp.sum(out.cold.counts))
    assert 0 < fill_after <= min(fill_before, 1 * 2 * (2 + 6))


def test_reshard_carry_dispatches_tiered():
    """reshard_carry no longer raises on TieredState (the PR-2 guard is gone)
    and keeps sampling functional after the move."""
    from repro.core import init_carry
    from repro.runtime import reshard_carry

    rcfg = RehearsalConfig(num_buckets=2, slots_per_bucket=4, tiering="host",
                           hot_slots=4, cold_slots=8, num_candidates=8,
                           num_representatives=3, mode="async",
                           label_field="label")
    carry = init_carry({"w": jnp.zeros((2,))}, None, _spec(), rcfg, n_dp=2)
    key = jax.random.PRNGKey(0)
    # populate through the per-worker update (worker axis leading)
    per_worker, dist = _distributed_tiered(2, rcfg)
    carry = carry._replace(buffer=dist)
    new = reshard_carry(carry, n_new=4, policy="reservoir")
    assert isinstance(new.buffer, B.TieredState)
    assert new.buffer.hot.counts.shape[0] == 4
    assert jax.tree_util.tree_leaves(new.reps)[0].shape[0] == 4
    # each new worker's slice samples valid records
    w0 = jax.tree_util.tree_map(lambda x: x[0], new.buffer)
    got, valid = B.tiered_sample(w0, jax.random.PRNGKey(1), 4, rcfg.policy)
    assert bool(valid.any())


def test_checkpoint_roundtrip_of_tiered_carry():
    """TieredState is a plain pytree: numpy snapshot + restore resumes exactly."""
    rcfg = RehearsalConfig(num_buckets=2, slots_per_bucket=2, num_representatives=2,
                           num_candidates=6, mode="sync", tiering="host",
                           hot_slots=2, cold_slots=6, label_field="label")
    st = B.init_from_config(_spec(), rcfg)
    key = jax.random.PRNGKey(0)
    for s in range(4):
        bt = _batch(s)
        st = B.buffer_update(st, bt, bt["task"], jax.random.fold_in(key, s), rcfg)
    snap = jax.tree_util.tree_map(np.asarray, st)
    restored = jax.tree_util.tree_map(jnp.asarray, snap)
    a, _ = B.buffer_sample(st, jax.random.PRNGKey(5), 4, rcfg)
    b_, _ = B.buffer_sample(restored, jax.random.PRNGKey(5), 4, rcfg)
    for la, lb in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b_)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
