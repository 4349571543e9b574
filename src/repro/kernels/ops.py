"""Public jit'd wrappers for the Pallas kernels.

Layout adaptation + padding + interpret-mode dispatch live here; model code calls
these, never the kernels directly. On the CPU ``interpret=True`` runs the kernel
bodies in Python for correctness validation; on TPU the same calls lower to
Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import quantize as _qz
from repro.kernels import rehearsal_ops as _ro
from repro.kernels import ssd_scan as _ssd


def _default_interpret() -> bool:
    # the CPU runs the kernel bodies in the Pallas interpreter; every other
    # backend compiles them (Mosaic on the TPU) or fails — never interprets
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("window", "causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, window: int = 0, causal: bool = True,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """q [B,S,H,hd]; k/v [B,T,KV,hd] -> [B,S,H,hd] (model layout, GQA-aware)."""
    interpret = _default_interpret() if interpret is None else interpret
    qt = jnp.swapaxes(q, 1, 2)  # [B,H,S,hd]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _fa.flash_attention_bhsd(
        qt, kt, vt, window=window, causal=causal,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    return jnp.swapaxes(out, 1, 2)


@functools.partial(jax.jit, static_argnames=("chunk", "head_block", "interpret"))
def ssd_scan(x, dt, a_head, bmat, cmat, *, chunk: int = 128, head_block: int = 8,
             interpret: bool | None = None):
    """Model layout: x [B,S,H,P]; dt [B,S,H]; a [H]; b/c [B,S,N] -> y [B,S,H,P]."""
    interpret = _default_interpret() if interpret is None else interpret
    b, s, h, p = x.shape
    q = min(chunk, s)
    assert s % q == 0, (s, q)
    nc = s // q
    a = dt.astype(jnp.float32) * a_head.astype(jnp.float32)
    cum = jnp.cumsum(a.reshape(b, nc, q, h), axis=2)
    y = _ssd.ssd_scan_chunked(
        x.reshape(b, nc, q, h, p),
        dt.reshape(b, nc, q, h),
        cum,
        bmat.reshape(b, nc, q, -1),
        cmat.reshape(b, nc, q, -1),
        a_head,
        chunk=q,
        head_block=head_block,
        interpret=interpret,
    )
    return y.reshape(b, s, h, p)


@functools.partial(jax.jit, static_argnames=("row_tile", "interpret"))
def rehearsal_update_sample(buffer, cands, cand_rows, samp_rows,
                            row_tile: int = 8,
                            interpret: bool | None = None):
    """buffer [R, L]; cands [C, L]; cand_rows i32[C]; samp_rows i32[S].
    ``row_tile`` records move per grid step (sublane-aligned tiles; 1 = the
    original one-record-per-step BlockSpec form)."""
    interpret = _default_interpret() if interpret is None else interpret
    return _ro.rehearsal_update_sample(buffer, cands, cand_rows, samp_rows,
                                       row_tile=row_tile, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("row_tile", "interpret"))
def rehearsal_pipelined_step(buffer, pending_reps, cands, cand_rows, samp_rows,
                             row_tile: int = 8,
                             interpret: bool | None = None):
    """One-step-stale rehearsal step: train on ``pending_reps`` (gathered last call)
    while issuing this call's scatter+gather. Returns (new_buffer, train_reps,
    next_pending)."""
    interpret = _default_interpret() if interpret is None else interpret
    return _ro.rehearsal_pipelined_step(buffer, pending_reps, cands, cand_rows,
                                        samp_rows, row_tile=row_tile,
                                        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def quantize(x, *, block_rows: int = 8, interpret: bool | None = None):
    """Row-wise int8 quantization: x [R, L] -> (q int8, scales f32 [R, 1]).
    Ragged row counts are padded to the block multiple inside the kernel."""
    interpret = _default_interpret() if interpret is None else interpret
    return _qz.quantize_rows(x, block_rows=block_rows, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("dtype", "block_rows", "interpret"))
def dequantize(q, scales, dtype=jnp.float32, *, block_rows: int = 8,
               interpret: bool | None = None):
    """Inverse of ``quantize``."""
    interpret = _default_interpret() if interpret is None else interpret
    return _qz.dequantize_rows(q, scales, dtype=dtype, block_rows=block_rows,
                               interpret=interpret)


@functools.partial(jax.jit, static_argnames=("dtype", "row_tile", "interpret"))
def gather_dequant(q_table, scales_table, rows, dtype=jnp.float32, *,
                   row_tile: int = 8, interpret: bool | None = None):
    """Fused cold-row sampling: gather ``rows`` of the int8 table and dequantize
    them in VMEM on the way out — bit-identical to gather-then-``dequantize``
    but with no fp-width HBM intermediate (DESIGN.md §14).

    q_table int8 [R, W, 128] (lane-dense records); scales_table f32 [R, 1];
    rows i32[S] (clamped into range — sampling indices are always in-range,
    validity travels as a mask). Returns [S, W, 128] ``dtype``."""
    interpret = _default_interpret() if interpret is None else interpret
    r = q_table.shape[0]
    idx = jnp.clip(rows, 0, r - 1)
    # per-row scales are S*4 bytes — gathered at XLA level; the wide int8 rows
    # are what the kernel moves
    row_scales = scales_table[idx]
    return _ro.gather_dequant_rows(q_table, row_scales, idx, dtype,
                                   row_tile=row_tile, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("row_tile", "interpret"))
def encode_scatter(q_table, scales_table, x, rows, *, row_tile: int = 8,
                   interpret: bool | None = None):
    """Fused demotion flush: row-quantize the staged fp rows and scatter them
    into their cold-table target rows in one kernel (``input_output_aliases``
    keeps the table in place) — bit-identical to ``quantize``-then-scatter but
    with no encoded-batch intermediate (DESIGN.md §14).

    q_table int8 [R, W, 128] (lane-dense records); scales_table f32 [R, 1];
    x fp [S, W, 128]; rows i32[S] (<0 or >= R ⇒ dropped). Returns
    (new_q_table, new_scales_table).
    """
    interpret = _default_interpret() if interpret is None else interpret
    new_q, row_scales = _ro.encode_scatter_rows(q_table, x, rows,
                                                row_tile=row_tile,
                                                interpret=interpret)
    safe = jnp.where(rows >= 0, rows, q_table.shape[0])  # OOB ⇒ dropped
    new_scales = scales_table.at[safe].set(row_scales, mode="drop")
    return new_q, new_scales
