"""The repro.buffer policy subsystem: registry, per-policy semantics, and the
pre-refactor parity contract.

The parity traces below were generated by the pre-subsystem ``core/rehearsal.py``
(commit 331becb) and are PINNED: ``policy='reservoir'`` + ``tiering='off'`` must
reproduce the exact sampling sequence of the monolithic implementation — same
keys, same ops, bit-for-bit. Do not regenerate them from current code.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.buffer as B
from repro.configs.base import RehearsalConfig
from repro.core import init_carry, make_cl_step
from repro.core import rehearsal as legacy


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


# ---------------------------------------------------------------------------
# Registry + back-compat surface
# ---------------------------------------------------------------------------


def test_registry_has_the_four_policies():
    assert {"reservoir", "fifo", "class_balanced", "grasp"} <= set(B.POLICIES)
    assert B.resolve_policy(None) is B.get_policy("reservoir")
    assert B.resolve_policy("fifo").name == "fifo"
    with pytest.raises(KeyError):
        B.get_policy("nope")


def test_register_custom_policy():
    class Mine(B.Policy):
        name = "mine_test"

    B.register_policy(Mine())
    assert B.get_policy("mine_test").name == "mine_test"
    del B.POLICIES["mine_test"]


def test_legacy_module_reexports_subsystem():
    """repro.core.rehearsal is a shim over repro.buffer — same objects, not copies."""
    assert legacy.BufferState is B.BufferState
    assert legacy.local_update is B.local_update
    assert legacy.local_sample is B.local_sample
    assert legacy.init_buffer is B.init_buffer


def test_bufferstate_three_field_construction_still_works():
    """Pre-subsystem call sites built BufferState(data, counts, seen); aux defaults."""
    s = B.BufferState({"x": jnp.zeros((2, 4, 8))}, jnp.zeros(2, jnp.int32),
                      jnp.zeros(2, jnp.int32))
    assert s.aux == ()


# ---------------------------------------------------------------------------
# Parity: pinned pre-refactor traces (reservoir default)
# ---------------------------------------------------------------------------

# rb.local_update(c=6) + rb.local_sample(n=5) on a 2x8 buffer, keys fold_in(7, s)
# and fold_in(7, 100+s): [sum(reps.x), sum(reps.label), valid.sum(), counts]
# The traces were re-pinned when JAX made ``jax_threefry_partitionable`` the
# default, which changes the random bits each key yields. Under
# ``jax_threefry_partitionable=False`` the earlier traces still reproduce.
_RAW_TRACE = [
    [-2.7847, 10, 5, [6, 4]],
    [9.0489, 8, 5, [7, 8]],
    [-3.8469, 5, 5, [8, 8]],
    [-0.8515, 6, 5, [8, 8]],
    [-0.5766, 7, 5, [8, 8]],
    [3.6378, 7, 5, [8, 8]],
    [-1.9562, 8, 5, [8, 8]],
    [-8.5148, 10, 5, [8, 8]],
]

# make_cl_step(mode='sync', exchange='local', seed=3) rep_checksum per step, and
# the final sum|w| of the linear probe after 10 steps.
_STEP_TRACE = [5.0, 4.0, 8.0, 8.0, 9.0, 4.0, 3.0, 6.0, 5.0, 6.0]
_FINAL_W = 0.867979


def test_reservoir_matches_pinned_raw_trace():
    buf = B.init_buffer(_spec(), 2, 8)
    key = jax.random.PRNGKey(7)
    for s, expect in enumerate(_RAW_TRACE):
        bt = _batch(s)
        buf = B.local_update(buf, bt, bt["task"], jax.random.fold_in(key, s), 6)
        reps, valid = B.local_sample(buf, jax.random.fold_in(key, 100 + s), 5)
        got = [round(float(jnp.sum(reps["x"])), 4), int(jnp.sum(reps["label"])),
               int(valid.sum()), [int(c) for c in buf.counts]]
        assert got == expect, (s, got, expect)


def test_reservoir_cl_step_matches_pinned_step_trace():
    def loss(params, b):
        logits = b["x"] @ params["w"]
        onehot = jax.nn.one_hot(jnp.maximum(b["label"], 0), logits.shape[-1])
        mask = (b["label"] >= 0).astype(jnp.float32)
        ce = -jnp.sum(jax.nn.log_softmax(logits) * onehot, axis=-1)
        return jnp.sum(ce * mask) / jnp.maximum(mask.sum(), 1.0), {}

    def sgd(g, o, p):
        return jax.tree_util.tree_map(lambda pp, gg: pp - 0.1 * gg, p, g), o, {}

    rcfg = RehearsalConfig(num_buckets=2, slots_per_bucket=8, num_representatives=3,
                           num_candidates=6, mode="sync", label_field="label")
    step = make_cl_step(loss, sgd, rcfg, strategy="rehearsal", exchange="local",
                        donate=False)
    carry = init_carry({"w": jnp.zeros((8, 4))}, None, _spec(), rcfg, seed=3)
    key = jax.random.PRNGKey(0)
    cks = []
    for s in range(10):
        carry, m = step(carry, _batch(s), jax.random.fold_in(key, s))
        cks.append(round(float(m["rep_checksum"]), 4))
    assert cks == _STEP_TRACE
    assert round(float(jnp.sum(jnp.abs(carry.params["w"]))), 6) == _FINAL_W


# ---------------------------------------------------------------------------
# Per-policy semantics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", sorted(("reservoir", "fifo", "class_balanced",
                                           "grasp")))
def test_capacity_and_sampling_invariants(policy):
    """Every policy: counts <= cap, samples come from filled slots, jit-safe."""
    buf = B.init_buffer(_spec(4), 2, 4, policy)
    key = jax.random.PRNGKey(0)

    @jax.jit
    def upd(b, items, labels, k):
        return B.local_update(b, items, labels, k, 8, policy)

    for s in range(6):
        bt = _batch(s, d=4)
        buf = upd(buf, bt, bt["task"], jax.random.fold_in(key, s))
    assert (np.asarray(buf.counts) <= 4).all()
    reps, valid = B.local_sample(buf, jax.random.PRNGKey(1), 6, policy)
    assert reps["x"].shape == (6, 4)
    assert bool(valid.all())
    # sampled labels are consistent with their bucket assignment (task = label % 2)
    assert set(np.asarray(reps["task"]).tolist()) <= {0, 1}


def test_fifo_overwrites_oldest():
    """A full FIFO bucket must evict in arrival order (ring semantics)."""
    spec = {"v": jax.ShapeDtypeStruct((), jnp.int32),
            "task": jax.ShapeDtypeStruct((), jnp.int32)}
    buf = B.init_buffer(spec, 1, 3, "fifo")
    key = jax.random.PRNGKey(0)
    for s in range(5):  # insert values 0..4 one per step, always accepted (c=b)
        items = {"v": jnp.asarray([s], jnp.int32), "task": jnp.zeros((1,), jnp.int32)}
        buf = B.local_update(buf, items, items["task"], jax.random.fold_in(key, s),
                             1, "fifo")
    # capacity 3, inserted 0..4 -> survivors are the three newest: 2, 3, 4
    assert sorted(np.asarray(buf.data["v"][0]).tolist()) == [2, 3, 4]
    assert int(buf.aux["cursor"][0]) == 5 % 3


def test_class_balanced_sampling_balances_skewed_fill():
    """With one bucket 8x fuller than the other, balanced sampling replays both
    classes at comparable rates (uniform-over-filled would be ~8:1)."""
    spec = {"v": jax.ShapeDtypeStruct((), jnp.int32),
            "task": jax.ShapeDtypeStruct((), jnp.int32)}
    buf = B.init_buffer(spec, 2, 16, "class_balanced")
    # hand-fill: bucket 0 -> 16 records, bucket 1 -> 2 records
    buf = buf._replace(counts=jnp.asarray([16, 2], jnp.int32),
                       data={"v": buf.data["v"],
                             "task": jnp.broadcast_to(
                                 jnp.arange(2, dtype=jnp.int32)[:, None], (2, 16))})
    hits = np.zeros(2)
    for t in range(60):
        reps, valid = B.local_sample(buf, jax.random.PRNGKey(t), 8, "class_balanced")
        assert bool(valid.all())
        for b_ in np.asarray(reps["task"]):
            hits[b_] += 1
    ratio = hits[0] / hits[1]
    assert 0.5 < ratio < 2.0, hits  # balanced, not ~8


def test_grasp_evicts_least_prototypical():
    """A full GRASP bucket displaces the stored record farthest from the class
    prototype: an injected outlier is evicted before the prototypical cluster."""
    spec = {"x": jax.ShapeDtypeStruct((4,), jnp.float32),
            "task": jax.ShapeDtypeStruct((), jnp.int32)}
    buf = B.init_buffer(spec, 1, 4, "grasp")
    key = jax.random.PRNGKey(0)
    # fill with 3 prototypical records (~1.0) and one far outlier (100.0)
    cluster = [[1.0] * 4, [1.1] * 4, [0.9] * 4, [100.0] * 4]
    items = {"x": jnp.asarray(cluster, jnp.float32),
             "task": jnp.zeros((4,), jnp.int32)}
    buf = B.local_update(buf, items, items["task"], jax.random.fold_in(key, 0),
                         4, "grasp")
    assert int(buf.counts[0]) == 4
    # push one more prototypical record into the full bucket
    items2 = {"x": jnp.asarray([[1.05] * 4], jnp.float32),
              "task": jnp.zeros((1,), jnp.int32)}
    buf = B.local_update(buf, items2, items2["task"], jax.random.fold_in(key, 1),
                         1, "grasp")
    stored = np.asarray(buf.data["x"][0])
    assert stored.max() < 50.0, stored  # the outlier row was the one displaced


def test_grasp_sampling_prefers_prototypical():
    spec = {"x": jax.ShapeDtypeStruct((4,), jnp.float32),
            "task": jax.ShapeDtypeStruct((), jnp.int32)}
    buf = B.init_buffer(spec, 1, 8, "grasp")
    key = jax.random.PRNGKey(0)
    vals = [[1.0] * 4] * 6 + [[30.0] * 4, [40.0] * 4]
    items = {"x": jnp.asarray(vals, jnp.float32),
             "task": jnp.zeros((8,), jnp.int32)}
    buf = B.local_update(buf, items, items["task"], key, 8, "grasp")
    far = 0
    for t in range(50):
        reps, _ = B.local_sample(buf, jax.random.PRNGKey(t), 2, "grasp")
        far += int((np.asarray(reps["x"])[:, 0] > 10).sum())
    # outliers are 2/8 of the buffer; distance-ordered replay draws them well
    # below the 25% a uniform sampler would
    assert far / (50 * 2) < 0.20, far


def test_grasp_partial_fill_marks_surplus_draws_invalid():
    """GRASP samples without replacement: asking for more records than are filled
    must flag the surplus invalid (they'd otherwise train as zero-filled garbage
    with a real class-0 label)."""
    spec = {"x": jax.ShapeDtypeStruct((4,), jnp.float32),
            "task": jax.ShapeDtypeStruct((), jnp.int32)}
    buf = B.init_buffer(spec, 2, 8, "grasp")
    items = {"x": jnp.ones((4, 4), jnp.float32),
             "task": jnp.asarray([0, 0, 1, 1], jnp.int32)}
    buf = B.local_update(buf, items, items["task"], jax.random.PRNGKey(0), 4, "grasp")
    assert int(buf.counts.sum()) == 4
    reps, valid = B.local_sample(buf, jax.random.PRNGKey(1), 8, "grasp")
    assert int(valid.sum()) == 4  # exactly the filled slots; surplus invalid
    assert bool((np.asarray(reps["x"])[np.asarray(valid)] == 1.0).all())
    # n far beyond capacity (n > 2*K*cap) must still return exactly n rows
    reps, valid = B.local_sample(buf, jax.random.PRNGKey(2), 40, "grasp")
    assert reps["x"].shape == (40, 4) and valid.shape == (40,)


def test_grasp_same_batch_evictions_hit_distinct_slots():
    """Several same-bucket overflow candidates in one batch must displace distinct
    slots in least-prototypical-first order, not collide on one argmax slot."""
    spec = {"x": jax.ShapeDtypeStruct((2,), jnp.float32),
            "task": jax.ShapeDtypeStruct((), jnp.int32)}
    buf = B.init_buffer(spec, 1, 4, "grasp")
    key = jax.random.PRNGKey(0)
    fill = {"x": jnp.asarray([[1.0, 1.0]] * 4, jnp.float32),
            "task": jnp.zeros((4,), jnp.int32)}
    buf = B.local_update(buf, fill, fill["task"], key, 4, "grasp")
    burst = {"x": jnp.asarray([[2.0, 2.0]] * 3, jnp.float32),
             "task": jnp.zeros((3,), jnp.int32)}
    buf = B.local_update(buf, burst, burst["task"], jax.random.fold_in(key, 1),
                         3, "grasp")
    stored = np.asarray(buf.data["x"][0, :, 0])
    assert (stored == 2.0).sum() == 3, stored  # all three survived


def test_elastic_reshard_rebuilds_policy_aux():
    """Resharding compacts slots, so cloned aux would be misaligned: the FIFO
    cursor must be rebuilt to counts % cap (a stale cursor makes the next insert
    skip the first empty slot and sampling then returns never-written records)."""
    from repro.core.strategies import TrainCarry
    from repro.runtime import reshard_carry

    spec = {"v": jax.ShapeDtypeStruct((), jnp.float32),
            "task": jax.ShapeDtypeStruct((), jnp.int32)}
    buf = B.init_buffer(spec, 1, 8, "fifo")
    key = jax.random.PRNGKey(0)
    for s in range(6):  # 6 records -> cursor == counts == 6
        items = {"v": jnp.asarray([float(s + 1)]), "task": jnp.zeros((1,), jnp.int32)}
        buf = B.local_update(buf, items, items["task"], jax.random.fold_in(key, s),
                             1, "fifo")
    wide = jax.tree_util.tree_map(lambda x: jnp.broadcast_to(x, (2,) + x.shape), buf)
    carry = TrainCarry({}, None, wide, None, None)

    with pytest.raises(ValueError):  # aux present -> the policy must be named
        reshard_carry(carry, 4)

    new = reshard_carry(carry, 4, policy="fifo")
    for w in range(4):
        cnt = int(new.buffer.counts[w, 0])
        assert int(new.buffer.aux["cursor"][w, 0]) == cnt % 8
    # the next insert on a resharded worker lands in its first empty slot
    local = jax.tree_util.tree_map(lambda x: x[0], new.buffer)
    c0 = int(local.counts[0])
    assert 0 < c0 < 8
    items = {"v": jnp.asarray([99.0]), "task": jnp.zeros((1,), jnp.int32)}
    local = B.local_update(local, items, items["task"], key, 1, "fifo")
    assert float(local.data["v"][0, c0]) == 99.0


def test_elastic_reshard_recomputes_grasp_distances():
    from repro.core.strategies import TrainCarry
    from repro.runtime import reshard_carry

    spec = {"x": jax.ShapeDtypeStruct((4,), jnp.float32),
            "task": jax.ShapeDtypeStruct((), jnp.int32)}
    buf = B.init_buffer(spec, 1, 4, "grasp")
    items = {"x": jnp.ones((3, 4), jnp.float32), "task": jnp.zeros((3,), jnp.int32)}
    buf = B.local_update(buf, items, items["task"], jax.random.PRNGKey(0), 3, "grasp")
    wide = jax.tree_util.tree_map(lambda x: jnp.broadcast_to(x, (2,) + x.shape), buf)
    new = reshard_carry(TrainCarry({}, None, wide, None, None), 3, policy="grasp")
    dist = np.asarray(new.buffer.aux["dist"])  # [3, 1, 4]
    counts = np.asarray(new.buffer.counts)  # [3, 1]
    for w in range(3):
        c = int(counts[w, 0])
        assert (dist[w, 0, :c] < 1e29).all()  # filled slots: finite distances
        assert (dist[w, 0, c:] > 1e29).all()  # unfilled: sentinel


def test_policies_preserve_aux_through_distributed_carry():
    """Worker-axis squeeze/unsqueeze in the manual-DP step keeps policy aux intact
    (regression: aux used to be dropped by positional BufferState rebuilds)."""
    buf = B.init_buffer(_spec(), 2, 4, "fifo")
    wide = jax.tree_util.tree_map(lambda x: jnp.broadcast_to(x, (2,) + x.shape), buf)
    assert isinstance(wide, B.BufferState)
    assert wide.aux["cursor"].shape == (2, 2)
    back = jax.tree_util.tree_map(lambda x: x[0], wide)
    assert back.aux["cursor"].shape == (2,)
