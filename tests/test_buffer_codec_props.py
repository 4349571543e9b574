"""Property tests for the buffer codec path the tiered cold tier depends on:
``core.compression.encode_batch``/``decode_batch`` roundtrips on buffer-shaped
record pytrees, and the ``kernels.quantize`` row max-error bound at buffer row
shapes. (tests/test_compression.py covers fixed examples; these sweep shapes,
scales and dtypes property-style.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import compression as C
from repro.kernels import ops


def _record_spec(feat, seq, scalar_float):
    spec = {
        "emb": jax.ShapeDtypeStruct((feat, 4), jnp.float32),
        "tokens": jax.ShapeDtypeStruct((seq,), jnp.int32),
        "task": jax.ShapeDtypeStruct((), jnp.int32),
    }
    if scalar_float:
        spec["weight"] = jax.ShapeDtypeStruct((), jnp.float32)
    return spec


@settings(deadline=None, max_examples=15)
@given(
    b=st.integers(1, 9),
    feat=st.integers(1, 6),
    seq=st.integers(1, 12),
    scale=st.floats(1e-3, 1e3),
    scalar_float=st.booleans(),
    seed=st.integers(0, 2**31 - 1),
)
def test_encode_decode_roundtrip_buffer_records(b, feat, seq, scale, scalar_float,
                                                seed):
    """Roundtrip law on arbitrary buffer-shaped records: integer leaves exact,
    float leaves within the per-record int8 grid (row-maxabs/127 * 1/2)."""
    spec = _record_spec(feat, seq, scalar_float)
    key = jax.random.PRNGKey(seed)
    batch = {
        "emb": jax.random.normal(key, (b, feat, 4)) * scale,
        "tokens": jax.random.randint(jax.random.fold_in(key, 1), (b, seq), 0, 1000),
        "task": jax.random.randint(jax.random.fold_in(key, 2), (b,), 0, 7),
    }
    if scalar_float:
        batch["weight"] = jax.random.normal(jax.random.fold_in(key, 3), (b,)) * scale
    enc = C.encode_batch(batch, spec)
    # stored form is int8 + one f32 scale per record for every float leaf
    assert enc["emb"]["q"].dtype == jnp.int8
    assert enc["emb"]["q"].shape == (b, -(-feat * 4 // 128), 128)  # lane slabs
    assert enc["emb"]["scale"].shape == (b, 1)
    assert enc["tokens"]["raw"].dtype == jnp.int32
    dec = C.decode_batch(enc, spec)
    np.testing.assert_array_equal(np.asarray(dec["tokens"]), np.asarray(batch["tokens"]))
    np.testing.assert_array_equal(np.asarray(dec["task"]), np.asarray(batch["task"]))
    x = np.asarray(batch["emb"]).reshape(b, -1)
    y = np.asarray(dec["emb"]).reshape(b, -1)
    bound = np.abs(x).max(axis=1, keepdims=True) / 127.0 * 0.5 + 1e-6
    assert (np.abs(x - y) <= bound).all()
    assert dec["emb"].shape == batch["emb"].shape
    if scalar_float:
        wb = np.abs(np.asarray(batch["weight"]))[:, None] / 127.0 * 0.5 + 1e-6
        assert (np.abs(np.asarray(dec["weight"] - batch["weight"]))[:, None] <= wb).all()


@settings(deadline=None, max_examples=15)
@given(
    rows=st.integers(1, 48),
    length=st.integers(1, 96),
    scale=st.floats(1e-4, 1e4),
    seed=st.integers(0, 2**31 - 1),
)
def test_quantize_rows_max_error_bound(rows, length, scale, seed):
    """|x - dequant(quant(x))| <= row_maxabs/127 * 1/2 elementwise, at arbitrary
    buffer-table shapes [K*slots, L] (including non-multiple-of-8 rows)."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (rows, length)) * scale
    q, s = ops.quantize(x)
    assert q.dtype == jnp.int8 and s.shape == (rows, 1)
    deq = ops.dequantize(q, s)
    bound = np.asarray(jnp.max(jnp.abs(x), axis=1, keepdims=True)) / 127.0 * 0.5 + 1e-6
    assert (np.abs(np.asarray(deq - x)) <= bound).all()
    # quantization is idempotent on its own output (fixed point of the grid)
    q2, s2 = ops.quantize(deq)
    deq2 = ops.dequantize(q2, s2)
    np.testing.assert_allclose(np.asarray(deq2), np.asarray(deq), rtol=1e-5, atol=1e-6)


@settings(deadline=None, max_examples=10)
@given(
    k=st.integers(1, 3),
    slots=st.integers(1, 6),
    feat=st.integers(1, 8),
    seed=st.integers(0, 2**31 - 1),
)
def test_roundtrip_through_buffer_storage(k, slots, feat, seed):
    """encode -> Alg-1 insert -> sample -> decode recovers an inserted record
    (within the int8 grid) for any buffer geometry — the tiered cold-path law."""
    import repro.buffer as B

    spec = {"x": jax.ShapeDtypeStruct((feat,), jnp.float32),
            "task": jax.ShapeDtypeStruct((), jnp.int32)}
    b = 2 * k
    key = jax.random.PRNGKey(seed)
    batch = {"x": jax.random.normal(key, (b, feat)) * 3.0,
             "task": jnp.arange(b, dtype=jnp.int32) % k}
    enc = C.encode_batch(batch, spec)
    buf = B.init_buffer(C.compressed_spec(spec), k, slots)
    buf = B.local_update(buf, enc, batch["task"], jax.random.fold_in(key, 1), b)
    assert int(buf.counts.sum()) == k * min(slots, 2)  # 2 candidates per bucket
    stored, valid = B.local_sample(buf, jax.random.fold_in(key, 2), 4)
    assert bool(valid.all())
    dec = C.decode_batch(stored, spec)
    orig = np.asarray(batch["x"])
    for row in np.asarray(dec["x"]):
        err = np.abs(orig - row[None]).max(axis=1).min()
        assert err <= np.abs(orig).max() / 127.0 * 0.5 + 1e-5, err
