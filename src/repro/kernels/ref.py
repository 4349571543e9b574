"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each ``*_ref`` implements the mathematically obvious version — materialised attention
scores, the O(S) sequential SSM recurrence, scatter-then-gather buffer ops — and is the
ground truth for the interpret-mode allclose sweeps in tests/test_kernels.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, window: int = 0, causal: bool = True):
    """q [B,S,H,hd]; k/v [B,T,KV,hd] (GQA: H % KV == 0). Returns [B,S,H,hd]."""
    b, s, h, hd = q.shape
    t, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    scale = hd ** -0.5
    qg = (q * scale).reshape(b, s, kvh, g, hd)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
    if causal:
        qpos = jnp.arange(s)[:, None]
        kpos = jnp.arange(t)[None, :]
        mask = kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    return out.reshape(b, s, h, hd).astype(q.dtype)


def ssd_scan_ref(x, dt, a_head, bmat, cmat, initial_state=None):
    """Sequential SSM recurrence (the SSD semantics, O(S) steps).

    x [B,S,H,P]; dt [B,S,H]; a_head [H]; bmat/cmat [B,S,N].
    h_t = exp(dt_t·A)·h_{t-1} + dt_t·(B_t ⊗ x_t);  y_t = C_t·h_t.
    Returns (y [B,S,H,P], final_state [B,H,N,P]).
    """
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    f32 = jnp.float32
    h0 = (
        jnp.zeros((b, h, n, p), f32) if initial_state is None else initial_state.astype(f32)
    )

    def step(carry, inp):
        xt, dtt, bt, ct = inp
        lam = jnp.exp(dtt.astype(f32) * a_head.astype(f32))  # [B,H]
        inject = jnp.einsum("bn,bhp,bh->bhnp", bt.astype(f32), xt.astype(f32), dtt.astype(f32))
        new = lam[:, :, None, None] * carry + inject
        y = jnp.einsum("bn,bhnp->bhp", ct.astype(f32), new)
        return new, y

    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(bmat, 1, 0), jnp.moveaxis(cmat, 1, 0))
    final, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1).astype(x.dtype), final


def rehearsal_update_sample_ref(buffer, cands, cand_rows, samp_rows):
    """Scatter candidates into buffer rows, THEN gather sample rows (paper ordering:
    the update completes before the next global sampling reads).

    buffer [R, L]; cands [C, L]; cand_rows i32[C] (row < 0 ⇒ candidate dropped);
    samp_rows i32[S]. Returns (new_buffer [R, L], reps [S, L]).
    """
    rows = jnp.where(cand_rows >= 0, cand_rows, buffer.shape[0])  # OOB ⇒ dropped
    new_buffer = buffer.at[rows].set(cands, mode="drop")
    reps = new_buffer[jnp.clip(samp_rows, 0, buffer.shape[0] - 1)]
    return new_buffer, reps


def quantize_rows_ref(x):
    """Row-wise symmetric int8 quantization oracle."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_rows_ref(q, scales, dtype=jnp.float32):
    return (q.astype(jnp.float32) * scales).astype(dtype)


def gather_dequant_rows_ref(q_table, scales_table, rows, dtype=jnp.float32):
    """Two-pass oracle for the fused dequant-on-gather kernel: gather the int8
    rows and their scales, THEN dequantize the whole batch (the fp-width HBM
    intermediate the fused kernel avoids).

    q_table int8 [R, ...] (one record per row); scales_table f32 [R, 1];
    rows i32[S] (clamped). Returns [S, ...] ``dtype``.
    """
    r = q_table.shape[0]
    idx = jnp.clip(rows, 0, r - 1)
    q = q_table[idx]
    x = dequantize_rows_ref(q.reshape(q.shape[0], -1), scales_table[idx], dtype)
    return x.reshape(q.shape)


def encode_scatter_rows_ref(q_table, scales_table, x, rows):
    """Two-pass oracle for the fused encode-on-scatter kernel: quantize the
    whole staged batch, THEN scatter rows + scales (the encoded-batch
    intermediate the fused kernel avoids).

    q_table int8 [R, ...] (one record per row); scales_table f32 [R, 1];
    x fp [S, ...]; rows i32[S] (<0 or >= R ⇒ dropped). Returns
    (new_q_table, new_scales_table).
    """
    q, s = quantize_rows_ref(x.reshape(x.shape[0], -1))
    safe = jnp.where(rows >= 0, rows, q_table.shape[0])  # OOB ⇒ dropped
    return (q_table.at[safe].set(q.reshape(x.shape), mode="drop"),
            scales_table.at[safe].set(s, mode="drop"))
