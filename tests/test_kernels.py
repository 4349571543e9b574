"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (b, s, h, kv, hd, window, dtype, block)
    (1, 64, 2, 2, 32, 0, jnp.float32, 32),
    (2, 128, 4, 2, 32, 0, jnp.float32, 64),
    (1, 128, 8, 1, 64, 0, jnp.float32, 64),  # MQA, gemma-style
    (2, 128, 6, 3, 64, 64, jnp.float32, 32),  # SWA, GQA 2:1
    (1, 256, 4, 4, 128, 128, jnp.float32, 128),  # MXU-aligned tiles
    (2, 64, 4, 2, 32, 0, jnp.bfloat16, 32),
]


@pytest.mark.parametrize("b,s,h,kv,hd,win,dtype,blk", FLASH_CASES)
def test_flash_attention_matches_ref(b, s, h, kv, hd, win, dtype, blk):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, s, h, hd), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, kv, hd), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, kv, hd), dtype)
    out = ops.flash_attention(q, k, v, window=win, block_q=blk, block_k=blk)
    want = ref.flash_attention_ref(q, k, v, window=win)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_flash_rectangular_blocks():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 128, 2, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 2, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 128, 2, 32))
    out = ops.flash_attention(q, k, v, block_q=32, block_k=64)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

SSD_CASES = [
    # (b, s, h, p, n, chunk, hblock)
    (1, 32, 4, 16, 8, 8, 2),
    (2, 64, 8, 16, 16, 16, 4),
    (1, 64, 8, 32, 8, 64, 8),  # single chunk
    (1, 128, 16, 64, 128, 32, 8),  # mamba2-370m-like dims
]


@pytest.mark.parametrize("b,s,h,p,n,chunk,hb", SSD_CASES)
def test_ssd_scan_matches_sequential_ref(b, s, h, p, n, chunk, hb):
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (b, s, h)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (h,)) * 0.3)
    bm = jax.random.normal(jax.random.fold_in(key, 3), (b, s, n)) * 0.5
    cm = jax.random.normal(jax.random.fold_in(key, 4), (b, s, n)) * 0.5
    y = ops.ssd_scan(x, dt, a, bm, cm, chunk=chunk, head_block=hb)
    want, _ = ref.ssd_scan_ref(x, dt, a, bm, cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=5e-4, rtol=1e-3)


def test_ssd_matches_model_chunked_path():
    """Kernel == the model's jnp chunked implementation (independent derivations)."""
    from repro.models.ssm import ssd_chunked

    key = jax.random.PRNGKey(3)
    b, s, h, p, n = 1, 64, 4, 16, 8
    x = jax.random.normal(key, (b, s, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(key, 1), (b, s, h)))
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(key, 2), (h,)) * 0.3)
    bm = jax.random.normal(jax.random.fold_in(key, 3), (b, s, n)) * 0.5
    cm = jax.random.normal(jax.random.fold_in(key, 4), (b, s, n)) * 0.5
    y_kernel = ops.ssd_scan(x, dt, a, bm, cm, chunk=16, head_block=4)
    y_model, _ = ssd_chunked(x, dt, a, bm, cm, chunk=16)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model),
                               atol=5e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# rehearsal update+sample
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=15)
@given(
    r=st.integers(4, 32),
    l=st.integers(4, 32),
    c=st.integers(1, 8),
    s=st.integers(1, 8),
    seed=st.integers(0, 2**31 - 1),
)
def test_rehearsal_kernel_matches_ref(r, l, c, s, seed):
    key = jax.random.PRNGKey(seed)
    buf = jax.random.normal(key, (r, l))
    cands = jax.random.normal(jax.random.fold_in(key, 1), (c, l))
    # rows: mix of valid targets and -1 drops; duplicates resolved identically by
    # the sequential grid and the ref's scatter (last write wins)
    cand_rows = jax.random.randint(jax.random.fold_in(key, 2), (c,), -1, r)
    samp_rows = jax.random.randint(jax.random.fold_in(key, 3), (s,), 0, r)
    nb, reps = ops.rehearsal_update_sample(buf, cands, cand_rows, samp_rows)
    nbr, repsr = ref.rehearsal_update_sample_ref(buf, cands, cand_rows, samp_rows)
    # duplicate cand_rows make the winner ambiguous; compare only when unique
    rows = np.asarray(cand_rows)
    valid_rows = rows[rows >= 0]
    if len(np.unique(valid_rows)) == len(valid_rows):
        np.testing.assert_allclose(np.asarray(nb), np.asarray(nbr))
        np.testing.assert_allclose(np.asarray(reps), np.asarray(repsr))
    else:
        # invariant under duplicates: untouched rows identical
        untouched = np.setdiff1d(np.arange(r), valid_rows)
        np.testing.assert_allclose(np.asarray(nb)[untouched], np.asarray(nbr)[untouched])


def test_rehearsal_gather_sees_fresh_writes():
    """Paper ordering: sampling reads the post-update buffer (write-then-read)."""
    buf = jnp.zeros((8, 4))
    cands = jnp.ones((2, 4))
    cand_rows = jnp.array([3, 5], jnp.int32)
    samp_rows = jnp.array([3, 5, 0], jnp.int32)
    _, reps = ops.rehearsal_update_sample(buf, cands, cand_rows, samp_rows)
    np.testing.assert_allclose(np.asarray(reps[0]), 1.0)
    np.testing.assert_allclose(np.asarray(reps[1]), 1.0)
    np.testing.assert_allclose(np.asarray(reps[2]), 0.0)


@settings(deadline=None, max_examples=15)
@given(
    r=st.integers(4, 32),
    l=st.integers(4, 32),
    c=st.integers(1, 12),
    s=st.integers(1, 12),
    tile=st.sampled_from([1, 4, 8]),
    seed=st.integers(0, 2**31 - 1),
)
def test_rehearsal_tiled_matches_single_row_path(r, l, c, s, tile, seed):
    """The sublane-tiled scatter/gather == the original [1, L]-per-step form ==
    the ref, bit-for-bit — including duplicate targets (serialized last-write-
    wins) and dropped candidates."""
    key = jax.random.PRNGKey(seed)
    buf = jax.random.normal(key, (r, l))
    cands = jax.random.normal(jax.random.fold_in(key, 1), (c, l))
    cand_rows = jax.random.randint(jax.random.fold_in(key, 2), (c,), -1, r)
    samp_rows = jax.random.randint(jax.random.fold_in(key, 3), (s,), 0, r)
    nb_t, reps_t = ops.rehearsal_update_sample(buf, cands, cand_rows, samp_rows,
                                               row_tile=tile)
    nb_1, reps_1 = ops.rehearsal_update_sample(buf, cands, cand_rows, samp_rows,
                                               row_tile=1)
    np.testing.assert_array_equal(np.asarray(nb_t), np.asarray(nb_1))
    np.testing.assert_array_equal(np.asarray(reps_t), np.asarray(reps_1))


# ---------------------------------------------------------------------------
# fused tiered hot path: dequant-on-gather + encode-on-scatter
# ---------------------------------------------------------------------------


# The tiered kernels take int8 tables in the lane-dense record layout
# [R, W, 128] (core.compression): W lane rows of 128 per record.


@settings(deadline=None, max_examples=15)
@given(
    r=st.integers(1, 40),
    w=st.integers(1, 3),
    s=st.integers(1, 16),
    tile=st.sampled_from([1, 4, 8]),
    seed=st.integers(0, 2**31 - 1),
)
def test_gather_dequant_matches_ref(r, w, s, tile, seed):
    """Fused gather+dequant == the two-pass oracle over ragged shapes (rows
    clamp; duplicates are reads, so always well-defined)."""
    key = jax.random.PRNGKey(seed)
    q = jax.random.randint(key, (r, w, 128), -127, 128, dtype=jnp.int8)
    scales = jax.random.uniform(jax.random.fold_in(key, 1), (r, 1),
                                minval=1e-4, maxval=4.0)
    rows = jax.random.randint(jax.random.fold_in(key, 2), (s,), 0, r)
    got = ops.gather_dequant(q, scales, rows, row_tile=tile)
    want = ref.gather_dequant_rows_ref(q, scales, rows)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(deadline=None, max_examples=15)
@given(
    r=st.integers(1, 40),
    w=st.integers(1, 3),
    c=st.integers(1, 16),
    tile=st.sampled_from([1, 4, 8]),
    seed=st.integers(0, 2**31 - 1),
)
def test_encode_scatter_matches_ref(r, w, c, tile, seed):
    """Fused quantize+scatter == the two-pass oracle over ragged shapes and
    dropped (-1 and positive-OOB) targets; int8 payload pinned exact, scales to
    the kernel-vs-eager float tolerance (matching test_compression)."""
    key = jax.random.PRNGKey(seed)
    q = jax.random.randint(key, (r, w, 128), -127, 128, dtype=jnp.int8)
    scales = jax.random.uniform(jax.random.fold_in(key, 1), (r, 1),
                                minval=1e-4, maxval=4.0)
    x = jax.random.normal(jax.random.fold_in(key, 2), (c, w, 128)) * 3
    rows = jax.random.randint(jax.random.fold_in(key, 3), (c,), -1, r + 2)
    gq, gs = ops.encode_scatter(q, scales, x, rows)
    wq, ws = ref.encode_scatter_rows_ref(q, scales, x, rows)
    vals = np.asarray(rows)
    valid = vals[(vals >= 0) & (vals < r)]
    if len(np.unique(valid)) == len(valid):
        np.testing.assert_array_equal(np.asarray(gq), np.asarray(wq))
        np.testing.assert_allclose(np.asarray(gs), np.asarray(ws), rtol=1e-6)
    else:  # duplicate winners are order-defined; pin fused == fused-at-tile-1
        gq1, gs1 = ops.encode_scatter(q, scales, x, rows, row_tile=1)
        np.testing.assert_array_equal(np.asarray(gq), np.asarray(gq1))
        np.testing.assert_array_equal(np.asarray(gs), np.asarray(gs1))
    # untouched rows identical regardless
    untouched = np.setdiff1d(np.arange(r), valid)
    np.testing.assert_array_equal(np.asarray(gq)[untouched], np.asarray(wq)[untouched])
    np.testing.assert_array_equal(np.asarray(gs)[untouched], np.asarray(ws)[untouched])


def test_encode_scatter_all_invalid_stage_is_identity():
    """An empty demotion stage (all rows dropped) must leave the cold table
    bit-identical — the step-0 tiered flush."""
    q = jax.random.randint(jax.random.PRNGKey(0), (16, 2, 128), -127, 128,
                           dtype=jnp.int8)
    scales = jax.random.uniform(jax.random.PRNGKey(1), (16, 1))
    x = jax.random.normal(jax.random.PRNGKey(2), (6, 2, 128))
    for bad in (jnp.full((6,), -1, jnp.int32), jnp.full((6,), 99, jnp.int32)):
        gq, gs = ops.encode_scatter(q, scales, x, bad)
        np.testing.assert_array_equal(np.asarray(gq), np.asarray(q))
        np.testing.assert_array_equal(np.asarray(gs), np.asarray(scales))


def test_encode_scatter_duplicate_rows_last_write_wins():
    """Duplicate targets resolve in candidate order (the XLA scatter contract)."""
    q = jnp.zeros((8, 1, 128), jnp.int8)
    scales = jnp.ones((8, 1))
    x = jnp.stack([jnp.full((1, 128), 10.0), jnp.full((1, 128), 20.0),
                   jnp.full((1, 128), 30.0)])
    rows = jnp.array([5, 5, 5], jnp.int32)
    gq, gs = ops.encode_scatter(q, scales, x, rows)
    qr, sr = ref.quantize_rows_ref(x.reshape(3, -1))
    np.testing.assert_array_equal(np.asarray(gq[5]).ravel(), np.asarray(qr[2]))
    np.testing.assert_allclose(np.asarray(gs[5]), np.asarray(sr[2]), rtol=1e-6)


def test_gather_dequant_preserves_record_dtype():
    q = jax.random.randint(jax.random.PRNGKey(3), (10, 1, 128), -127, 128,
                           dtype=jnp.int8)
    scales = jax.random.uniform(jax.random.PRNGKey(4), (10, 1))
    rows = jnp.arange(4, dtype=jnp.int32)
    out = ops.gather_dequant(q, scales, rows, dtype=jnp.bfloat16)
    assert out.dtype == jnp.bfloat16
    want = ref.gather_dequant_rows_ref(q, scales, rows, dtype=jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(want, np.float32))
