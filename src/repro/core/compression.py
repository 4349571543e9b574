"""Compressed rehearsal-buffer records (paper §VII's suggested data reduction).

Float record fields (VLM patch embeddings, audio frames — the fat records) are stored
int8 row-quantized: 4x more representatives per byte of S_max. Integer fields (tokens,
labels) pass through. The codec is applied at the strategy boundary: ``encode`` before
Alg-1 insertion, ``decode`` after sampling — the buffer itself stays a dumb pytree
store, and the all_to_all exchange moves the *compressed* bytes (4x wire saving too).

A quantized record is stored lane-dense: its flattened elements, zero-padded to a
multiple of 128, as one ``[W, 128]`` int8 slab (``W = ceil(n / 128)``). On the TPU
that makes each record a whole run of tiles, so the fused kernels can move one
record with one DMA; the zero padding leaves the row scale unchanged and is cut
off on decode.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops


def _is_float(leaf):
    return jnp.issubdtype(jnp.asarray(leaf).dtype if not hasattr(leaf, "dtype")
                          else leaf.dtype, jnp.floating)


LANES = 128  # lane width of a quantized record slab


def _numel(shape) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def lane_rows(n: int) -> int:
    """W: lane rows of the [W, 128] slab that stores an n-element record."""
    return -(-n // LANES)


def _to_slabs(x, n: int):
    """[B, ...] fp records -> [B, W, 128], zero-padded past the n elements."""
    b = x.shape[0]
    x = x.reshape(b, n)
    pad = lane_rows(n) * LANES - n
    if pad:
        x = jnp.concatenate([x, jnp.zeros((b, pad), x.dtype)], axis=1)
    return x.reshape(b, lane_rows(n), LANES)


def _from_slabs(x, spec_leaf):
    """[B, W, 128] slabs -> [B, *spec.shape] records (padding cut off)."""
    b = x.shape[0]
    n = _numel(spec_leaf.shape)
    return x.reshape(b, -1)[:, :n].reshape((b,) + tuple(spec_leaf.shape))


def compressed_spec(item_spec) -> Any:
    """Transform a record ShapeDtypeStruct spec into its stored (compressed) form."""

    def one(path, leaf):
        if not _is_float(leaf):
            return {"raw": leaf}
        return {
            "q": jax.ShapeDtypeStruct((lane_rows(_numel(leaf.shape)), LANES),
                                      jnp.int8),
            "scale": jax.ShapeDtypeStruct((1,), jnp.float32),
        }

    return jax.tree_util.tree_map_with_path(one, item_spec)


def encode_batch(batch, item_spec):
    """Quantize the float leaves of a [B, ...] record batch (per-record scales)."""

    def one(path, spec_leaf, x):
        if not _is_float(spec_leaf):
            return {"raw": x}
        b = x.shape[0]
        slabs = _to_slabs(x, _numel(spec_leaf.shape))
        q, s = ops.quantize(slabs.reshape(b, -1))
        return {"q": q.reshape(slabs.shape), "scale": s.reshape(b, 1)[:, 0:1]}

    return jax.tree_util.tree_map_with_path(
        lambda p, sl, xl: one(p, sl, xl), item_spec, batch
    )


def decode_batch(stored, item_spec):
    """Inverse of encode_batch: [B, ...] stored records -> original dtypes/shapes."""

    def one(spec_leaf, blob):
        if "raw" in blob:
            return blob["raw"]
        q = blob["q"]
        x = ops.dequantize(q.reshape(q.shape[0], -1), blob["scale"],
                           dtype=spec_leaf.dtype)
        return _from_slabs(x, spec_leaf)

    return jax.tree_util.tree_map(
        one, item_spec, stored,
        is_leaf=lambda n: isinstance(n, dict) and ("raw" in n or "q" in n),
    )


def encode_scatter_batch(cold_data, batch, item_spec, rows):
    """Fused demotion flush: quantize the [B, ...] staged ``batch`` and scatter it
    straight into flat ``rows`` of the compressed store (``cold_data``: pytree of
    ``{"q": [K, slots, W, 128], "scale": [K, slots, 1]}`` / ``{"raw": ...}`` blobs)
    in one Pallas kernel per float leaf — no intermediate encoded batch
    (``kernels.ops.encode_scatter``, DESIGN.md §14). ``rows[i] < 0`` or
    ``>= K*slots`` drops candidate i. Returns the updated ``cold_data``.

    Bit-identical to ``encode_batch`` + the XLA row scatter: same in-kernel
    quantization math, same last-write-wins duplicate-row order.
    """

    def one(spec_leaf, blob, x):
        k, slots = jax.tree_util.tree_leaves(blob)[0].shape[:2]
        r = k * slots
        safe = jnp.where(rows >= 0, rows, r)  # negative would wrap; OOB ⇒ dropped
        if "raw" in blob:
            flat_buf = blob["raw"].reshape((r,) + blob["raw"].shape[2:])
            out = flat_buf.at[safe].set(x.astype(flat_buf.dtype), mode="drop")
            return {"raw": out.reshape(blob["raw"].shape)}
        q3 = blob["q"].reshape((r,) + blob["q"].shape[2:])
        s2 = blob["scale"].reshape(r, 1)
        new_q, new_s = ops.encode_scatter(
            q3, s2, _to_slabs(x, _numel(spec_leaf.shape)), safe)
        return {"q": new_q.reshape(blob["q"].shape),
                "scale": new_s.reshape(blob["scale"].shape)}

    return jax.tree_util.tree_map(
        one, item_spec, cold_data, batch,
        is_leaf=lambda n: isinstance(n, jax.ShapeDtypeStruct),
    )


def decode_gather_batch(cold_data, item_spec, rows):
    """Fused sampling read: gather flat ``rows`` of the compressed store and
    dequantize them in VMEM on the way out — cold records never materialise at
    fp width in HBM (``kernels.ops.gather_dequant``, DESIGN.md §14). ``rows``
    must be in-range (sampling indices always are; validity is a mask).
    Returns a [n, ...] record batch in the original dtypes/shapes.

    Bit-identical to the XLA row gather + ``decode_batch``.
    """

    def one(spec_leaf, blob):
        k, slots = jax.tree_util.tree_leaves(blob)[0].shape[:2]
        r = k * slots
        if "raw" in blob:
            flat_buf = blob["raw"].reshape((r,) + blob["raw"].shape[2:])
            return flat_buf[rows]
        x = ops.gather_dequant(blob["q"].reshape((r,) + blob["q"].shape[2:]),
                               blob["scale"].reshape(r, 1),
                               rows, dtype=spec_leaf.dtype)
        return _from_slabs(x, spec_leaf)

    return jax.tree_util.tree_map(
        one, item_spec, cold_data,
        is_leaf=lambda n: isinstance(n, jax.ShapeDtypeStruct),
    )


def compression_ratio(item_spec) -> float:
    """Bytes(original) / bytes(stored)."""
    import numpy as np

    orig = stored = 0
    for leaf in jax.tree_util.tree_leaves(item_spec):
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        b = np.dtype(leaf.dtype).itemsize
        orig += n * b
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            stored += lane_rows(n) * LANES + 4  # int8 slab + f32 scale
        else:
            stored += n * b
    return orig / max(stored, 1)
