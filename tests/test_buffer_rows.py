"""The buffer's rows move by unrolled dynamic slices (``buffer.state.read_rows``
and ``write_rows``), never by a gather or scatter over the buffer reshaped to
rows and never inside a loop: on the TPU a large [K, S, H, W, C] buffer is
stored with its slot axis innermost, where those forms make XLA copy the whole
buffer to another layout and back every step (tests/test_tpu_compile.py holds
that compile). The moves keep the semantics of the row scatter and gather
they replace, and the pinned step trace of test_buffer_policies holds.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.buffer.state import read_rows, write_rows
from repro.configs.base import RehearsalConfig
from repro.core import init_carry, make_cl_step
from repro.runtime.sanitizer import SanitizerError
from test_buffer_policies import _STEP_TRACE, _batch, _spec

K, S = 3, 5  # 15 flat rows

# flat target rows of 6 candidates: 15 and above drop, negatives wrap
_TARGETS = {
    "some_dropped": [4, 15, 0, 15, 9, 14],
    "duplicates_later_wins": [7, 2, 7, 15, 2, 7],
    "negative_wraps": [-1, 3, -15, -16, 20, 1],
    "none_kept": [15, 15, 16, 99, -16, 15],
    "all_kept": [0, 1, 2, 3, 4, 5],
}


def _buffer(dtype):
    return (jnp.arange(K * S * 2 * 3, dtype=jnp.float32)
            .reshape(K, S, 2, 3).astype(dtype))


@pytest.mark.parametrize("case", sorted(_TARGETS))
def test_write_rows_is_the_dropping_row_scatter(case):
    buf = _buffer(jnp.float32)
    rows = -1.0 - jnp.arange(6 * 2 * 3, dtype=jnp.float32).reshape(6, 2, 3)
    flat = jnp.asarray(_TARGETS[case], jnp.int32)
    want = (buf.reshape(K * S, 2, 3).at[flat].set(rows, mode="drop")
            .reshape(buf.shape))
    got = jax.jit(write_rows)(buf, flat, rows)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if case == "none_kept":
        np.testing.assert_array_equal(np.asarray(got), np.asarray(buf))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_read_rows_is_the_row_gather(dtype):
    buf = _buffer(dtype)
    flat = jnp.asarray([14, 0, 7, 7, 3], jnp.int32)
    got = jax.jit(read_rows)(buf, flat)
    want = buf.reshape(K * S, 2, 3)[flat]
    assert got.dtype == dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert read_rows(buf, jnp.zeros((0,), jnp.int32)).shape == (0, 2, 3)


def _loss(params, b):
    logits = b["x"] @ params["w"]
    onehot = jax.nn.one_hot(jnp.maximum(b["label"], 0), logits.shape[-1])
    mask = (b["label"] >= 0).astype(jnp.float32)
    ce = -jnp.sum(jax.nn.log_softmax(logits) * onehot, axis=-1)
    return jnp.sum(ce * mask) / jnp.maximum(mask.sum(), 1.0), {}


def _sgd(g, o, p):
    return jax.tree_util.tree_map(lambda pp, gg: pp - 0.1 * gg, p, g), o, {}


RCFG = RehearsalConfig(num_buckets=2, slots_per_bucket=8, num_representatives=3,
                       num_candidates=6, mode="sync", label_field="label")


def _carry():
    return init_carry({"w": jnp.zeros((8, 4))}, None, _spec(), RCFG, seed=3)


@pytest.mark.parametrize("donate,sanitize", [(False, "0"), (True, "0"),
                                             (True, "1")])
def test_step_keeps_the_pinned_trace_and_the_buffer_layout(donate, sanitize,
                                                           monkeypatch):
    monkeypatch.setenv("REPRO_SANITIZE", sanitize)
    step = make_cl_step(_loss, _sgd, RCFG, strategy="rehearsal", exchange="local",
                        donate=donate)
    assert hasattr(step, "_sanitizer") == (sanitize == "1")
    carry = first = _carry()
    fmt = first.buffer.data["x"].format
    key = jax.random.PRNGKey(0)
    cks = []
    for s in range(3):
        carry, m = step(carry, _batch(s), jax.random.fold_in(key, s))
        cks.append(round(float(m["rep_checksum"]), 4))
    assert cks == _STEP_TRACE[:3]
    assert carry.buffer.data["x"].format == fmt
    if donate:
        assert first.buffer.data["x"].is_deleted()
    if sanitize == "1":
        with pytest.raises(SanitizerError, match="use-after-donate"):
            step(first, _batch(3), key)


def test_step_moves_buffer_rows_without_gather_or_scatter():
    """What the TPU's copy hinges on, visible on the CPU: the lowered step
    holds no gather, scatter or loop over the buffer's flat rows."""
    step = make_cl_step(_loss, _sgd, RCFG, strategy="rehearsal", exchange="local",
                        sanitize=False)
    text = step.lower(_carry(), _batch(0), jax.random.PRNGKey(0)).as_text()
    assert "dynamic_update_slice" in text
    flat_rows = f"tensor<{RCFG.num_buckets * RCFG.slots_per_bucket}x8xf32>"
    for line in text.splitlines():
        if flat_rows in line:
            assert not any(op in line for op in ("gather", "scatter", "while"))
