"""Fused rehearsal-buffer Pallas-TPU kernels — the paper's hot spot.

The paper spends §IV-C/§V on making buffer updates + representative reads cheap under
concurrency (RDMA registration, RPC consolidation, fine-grain locks, Argobots). The
TPU-native translation:

  * The buffer is an HBM-resident [rows, L] table (rows = K·slots flattened records).
  * One kernel performs the paper's whole ``update`` primitive: scatter the accepted
    candidates into their target rows, THEN gather the sampled representative rows —
    the sequential TPU grid (phase-major order) *is* the lock: writes complete before
    any read, replacing the paper's fine-grain locking with a static schedule.
  * Dynamic row targeting uses scalar prefetch (``PrefetchScalarGridSpec``): the
    row-index vectors are prefetched to SMEM and drive either the BlockSpec
    index_maps (single-row path) or explicit per-row DMAs (tiled path) — the
    canonical TPU patterns for data-dependent DMA (the RDMA-offset analogue).
  * ``input_output_aliases`` updates the buffer in place — no copy of the (large)
    table, mirroring the paper's in-place pinned-memory buffers.

Three kernel families (DESIGN.md §14):

``rehearsal_update_sample``
    Scatter candidates, then gather representatives. ``row_tile=1`` is the
    original BlockSpec form (one [1, L] record per grid step); ``row_tile>1``
    moves ``row_tile`` records per grid step — candidate/representative tiles
    ride the automatic Pallas block pipeline as dense sublane-aligned
    [tile, L] transfers, and the buffer side issues per-row DMAs against the
    table left in ``ANY`` memory space (gather DMAs overlap; scatter DMAs are
    serialised in candidate order so duplicate targets stay last-write-wins
    deterministic, exactly like the single-row grid).

``gather_dequant_rows``
    Tiered cold-tier sampling: gather int8 rows by index and dequantize them in
    VMEM on the way out. The fp-precision representative batch is the ONLY
    fp-width traffic — cold records never materialize at fp precision in HBM
    (the two-pass XLA form gathers int8, then runs a second full-width
    dequant pass through an [n, L] f32 HBM intermediate).

``encode_scatter_rows``
    Tiered demotion flush: quantize staged fp rows row-wise to int8 in VMEM and
    scatter them straight into their cold-table target rows in the same kernel
    (``input_output_aliases`` keeps the table in place; the two-pass XLA form
    materializes the whole encoded batch before a separate scatter).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


# ---------------------------------------------------------------------------
# update+sample: single-row BlockSpec form (row_tile=1)
# ---------------------------------------------------------------------------


def _kernel(cand_rows, samp_rows, buf_ref, cands_ref, out_buf_ref, reps_ref,
            *, n_cand: int):
    i = pl.program_id(0)
    in_scatter = i < n_cand

    @pl.when(in_scatter)
    def _scatter():
        # drop candidates with row < 0 (rejected by the c/b lottery)
        row = cand_rows[jnp.minimum(i, n_cand - 1)]

        @pl.when(row >= 0)
        def _():
            out_buf_ref[0] = cands_ref[0]

    @pl.when(jnp.logical_not(in_scatter))
    def _gather():
        reps_ref[0] = out_buf_ref[0]


def _update_sample_single(buffer, cands, cand_rows, samp_rows, *,
                          interpret: bool = False):
    r, l = buffer.shape
    c = cands.shape[0]
    s = samp_rows.shape[0]

    def buf_index(i, cand_rows_ref, samp_rows_ref):
        # scatter phase: target the candidate's row; gather phase: the sampled row.
        in_scatter = i < c
        ci = jnp.minimum(i, c - 1)
        gi = jnp.clip(i - c, 0, s - 1)
        row = jnp.where(in_scatter,
                        jnp.clip(cand_rows_ref[ci], 0, r - 1),
                        jnp.clip(samp_rows_ref[gi], 0, r - 1))
        return (row, 0)

    def cand_index(i, cand_rows_ref, samp_rows_ref):
        return (jnp.minimum(i, c - 1), 0)

    def reps_index(i, cand_rows_ref, samp_rows_ref):
        return (jnp.clip(i - c, 0, s - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(c + s,),
        in_specs=[
            pl.BlockSpec((1, l), buf_index),
            pl.BlockSpec((1, l), cand_index),
        ],
        out_specs=[
            pl.BlockSpec((1, l), buf_index),
            pl.BlockSpec((1, l), reps_index),
        ],
    )
    kernel = functools.partial(_kernel, n_cand=c)
    new_buf, reps = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((r, l), buffer.dtype),
            jax.ShapeDtypeStruct((s, l), buffer.dtype),
        ],
        input_output_aliases={2: 0},  # buffer (after the 2 prefetch args) -> out 0
        interpret=interpret,
    )(cand_rows, samp_rows, buffer, cands)
    return new_buf, reps


# ---------------------------------------------------------------------------
# update+sample: multi-row tiled form (row_tile > 1)
# ---------------------------------------------------------------------------


def _tiled_kernel(cand_rows, samp_rows, buf_any, cands_ref, out_any, reps_ref,
                  sems, *, n_cand: int, n_samp: int, tile: int, n_rows: int):
    t = pl.program_id(0)
    ct = _ceil_div(n_cand, tile)
    in_scatter = t < ct

    @pl.when(in_scatter)
    def _scatter():
        # serialised per-row DMA: duplicate target rows within a tile resolve
        # last-write-wins in candidate order, matching the single-row grid
        for j in range(tile):
            idx = t * tile + j
            row = cand_rows[jnp.minimum(idx, n_cand - 1)]

            @pl.when((idx < n_cand) & (row >= 0) & (row < n_rows))
            def _():
                dma = pltpu.make_async_copy(
                    cands_ref.at[j], out_any.at[row], sems.at[j])
                dma.start()
                dma.wait()

    @pl.when(jnp.logical_not(in_scatter))
    def _gather():
        g = t - ct
        # reads race-free: start the whole tile's row DMAs, then drain — the
        # in-flight window is what saturates the HBM->VMEM path
        dmas = []
        for j in range(tile):
            idx = jnp.minimum(g * tile + j, n_samp - 1)
            row = jnp.clip(samp_rows[idx], 0, n_rows - 1)
            dma = pltpu.make_async_copy(
                out_any.at[row], reps_ref.at[j], sems.at[j])
            dma.start()
            dmas.append(dma)
        for dma in dmas:
            dma.wait()


def _update_sample_tiled(buffer, cands, cand_rows, samp_rows, *, row_tile: int,
                         interpret: bool = False):
    r, l = buffer.shape
    c = cands.shape[0]
    s = samp_rows.shape[0]
    ct, st = _ceil_div(c, row_tile), _ceil_div(s, row_tile)

    # pad the tile-blocked sides to the tile multiple; pad candidates carry
    # row -1 (dropped), pad samples clamp inside the kernel and are sliced off
    cpad, spad = ct * row_tile - c, st * row_tile - s
    if cpad:
        cands = jnp.concatenate([cands, jnp.zeros((cpad, l), cands.dtype)])
        cand_rows = jnp.concatenate(
            [cand_rows, jnp.full((cpad,), -1, cand_rows.dtype)])
    if spad:
        samp_rows = jnp.concatenate(
            [samp_rows, jnp.zeros((spad,), samp_rows.dtype)])

    def cand_index(t, cand_rows_ref, samp_rows_ref):
        return (jnp.minimum(t, ct - 1), 0)

    def reps_index(t, cand_rows_ref, samp_rows_ref):
        return (jnp.clip(t - ct, 0, st - 1), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(ct + st,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # buffer table, row-DMA'd
            pl.BlockSpec((row_tile, l), cand_index),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((row_tile, l), reps_index),
        ],
        scratch_shapes=[pltpu.SemaphoreType.DMA((row_tile,))],
    )
    kernel = functools.partial(_tiled_kernel, n_cand=c, n_samp=s,
                               tile=row_tile, n_rows=r)
    new_buf, reps = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((r, l), buffer.dtype),
            jax.ShapeDtypeStruct((st * row_tile, l), buffer.dtype),
        ],
        input_output_aliases={2: 0},  # buffer (after the 2 prefetch args) -> out 0
        interpret=interpret,
    )(cand_rows, samp_rows, buffer, cands)
    return new_buf, reps[:s]


def rehearsal_update_sample(buffer, cands, cand_rows, samp_rows, *,
                            row_tile: int = 1, interpret: bool = False):
    """buffer [R, L]; cands [C, L]; cand_rows i32[C] (<0 ⇒ dropped); samp_rows i32[S].
    Returns (new_buffer [R, L], reps [S, L]). In-place on ``buffer`` (aliased).
    ``row_tile > 1`` moves that many records per grid step (sublane-aligned
    tiles + per-row buffer DMAs); ``row_tile=1`` is the BlockSpec form."""
    if row_tile <= 1:
        return _update_sample_single(buffer, cands, cand_rows, samp_rows,
                                     interpret=interpret)
    return _update_sample_tiled(buffer, cands, cand_rows, samp_rows,
                                row_tile=row_tile, interpret=interpret)


def rehearsal_pipelined_step(buffer, pending_reps, cands, cand_rows, samp_rows, *,
                             row_tile: int = 1, interpret: bool = False):
    """One software-pipelined rehearsal step at the kernel level (DESIGN.md §3).

    The consumer trains on ``pending_reps`` — the rows gathered by the PREVIOUS
    call, stale by one step, so they cost nothing on this step's critical path —
    while this call's fused scatter-then-gather kernel produces the pending slot
    for the next step. The kernel's phase-major grid order still serialises the
    scatter before the gather *within* the issue, so the next pending reps always
    observe this step's buffer update (the static-schedule lock).

    Returns ``(new_buffer, train_reps, next_pending)`` where ``train_reps`` is
    ``pending_reps`` passed through (shape [S, L]) and ``next_pending`` feeds the
    next call.
    """
    new_buffer, next_pending = rehearsal_update_sample(
        buffer, cands, cand_rows, samp_rows, row_tile=row_tile,
        interpret=interpret
    )
    return new_buffer, pending_reps, next_pending


# ---------------------------------------------------------------------------
# dequant-on-gather: cold-tier sampling without the fp HBM intermediate
# ---------------------------------------------------------------------------
#
# Both tiered kernels take the int8 table in the lane-dense record layout
# [R, W, 128] (``core.compression``): one record is a whole [W, 128] slab, so a
# row DMA slices only the untiled leading dim. A [R, L] int8 table packs four
# records into each 32-bit word of a tile, and no DMA can address one of them.


def _gather_dequant_kernel(rows_ref, q_any, scales_ref, out_ref, qtile, sems,
                           *, n: int, n_rows: int, tile: int):
    t = pl.program_id(0)
    dmas = []
    for j in range(tile):
        idx = jnp.minimum(t * tile + j, n - 1)
        row = jnp.clip(rows_ref[idx], 0, n_rows - 1)
        dma = pltpu.make_async_copy(q_any.at[row], qtile.at[j], sems.at[j])
        dma.start()
        dmas.append(dma)
    for dma in dmas:
        dma.wait()
    # the dequant the XLA path runs as a second full-width pass, here on the
    # VMEM tile while the next tile's row DMAs are being scheduled
    out_ref[...] = (qtile[...].astype(jnp.float32)
                    * scales_ref[...]).astype(out_ref.dtype)


def gather_dequant_rows(q_table, row_scales, rows, dtype=jnp.float32, *,
                        row_tile: int = 8, interpret: bool = False):
    """q_table int8 [R, W, C] (lane-dense records); row_scales f32 [S, 1]
    (pre-gathered per sampled row); rows i32[S] (clamped into range). Returns
    fp ``dtype`` [S, W, C]: the sampled cold rows, dequantized in VMEM on the
    way out — the int8 table is the only full-width HBM read, and the fp
    batch the only full-width write."""
    r, w, c = q_table.shape
    s = rows.shape[0]
    st = _ceil_div(s, row_tile)
    pad = st * row_tile - s
    if pad:
        rows = jnp.concatenate([rows, jnp.zeros((pad,), rows.dtype)])
        row_scales = jnp.concatenate(
            [row_scales, jnp.ones((pad, 1), row_scales.dtype)])
    row_scales = row_scales.reshape(st * row_tile, 1, 1)

    def tile_index(t, rows_ref):
        return (t, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(st,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # int8 table, row-DMA'd
            pl.BlockSpec((row_tile, 1, 1), tile_index),
        ],
        out_specs=[pl.BlockSpec((row_tile, w, c), tile_index)],
        scratch_shapes=[
            pltpu.VMEM((row_tile, w, c), q_table.dtype),
            pltpu.SemaphoreType.DMA((row_tile,)),
        ],
    )
    kernel = functools.partial(_gather_dequant_kernel, n=s, n_rows=r,
                               tile=row_tile)
    out, = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((st * row_tile, w, c), dtype)],
        interpret=interpret,
    )(rows, q_table, row_scales)
    return out[:s]


# ---------------------------------------------------------------------------
# encode-on-scatter: demotion flush without the encoded-batch intermediate
# ---------------------------------------------------------------------------


def _encode_scatter_kernel(rows_ref, q_any, x_ref, out_q_any, scales_ref,
                           qtile, sems, *, n: int, n_rows: int, tile: int):
    t = pl.program_id(0)
    # row-wise symmetric int8 quantization — op-for-op the quantize.py kernel
    # (the row max taken lane-wise, then over the record's sublanes: max is
    # exact in any order), so the fused flush is bit-identical to
    # encode_batch + scatter
    x = x_ref[...].astype(jnp.float32)  # [tile, W, C]
    amax = jnp.max(jnp.max(jnp.abs(x), axis=2, keepdims=True), axis=1,
                   keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    qtile[...] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    scales_ref[...] = scale
    # serialised per-row DMA: duplicate target rows resolve last-write-wins in
    # stage order, matching the XLA scatter the parity tests pin against
    for j in range(tile):
        idx = t * tile + j
        row = rows_ref[jnp.minimum(idx, n - 1)]

        @pl.when((idx < n) & (row >= 0) & (row < n_rows))
        def _():
            dma = pltpu.make_async_copy(
                qtile.at[j], out_q_any.at[row], sems.at[j])
            dma.start()
            dma.wait()


def encode_scatter_rows(q_table, x, rows, *, row_tile: int = 8,
                        interpret: bool = False):
    """q_table int8 [R, W, C] (lane-dense records, updated in place via
    aliasing); x fp [S, W, C] staged rows; rows i32[S] target rows (<0 or
    >= R ⇒ dropped). Returns ``(new_q_table [R, W, C], row_scales f32 [S, 1])``
    — the quantized rows land directly in the table with no encoded-batch
    intermediate; the caller scatters the (tiny) returned scales into its
    scale table."""
    r, w, c = q_table.shape
    s = x.shape[0]
    st = _ceil_div(s, row_tile)
    pad = st * row_tile - s
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, w, c), x.dtype)])
        rows = jnp.concatenate([rows, jnp.full((pad,), -1, rows.dtype)])

    def tile_index(t, rows_ref):
        return (t, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(st,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),  # int8 table, row-DMA'd
            pl.BlockSpec((row_tile, w, c), tile_index),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((row_tile, 1, 1), tile_index),
        ],
        scratch_shapes=[
            pltpu.VMEM((row_tile, w, c), q_table.dtype),
            pltpu.SemaphoreType.DMA((row_tile,)),
        ],
    )
    kernel = functools.partial(_encode_scatter_kernel, n=s, n_rows=r,
                               tile=row_tile)
    new_q, scales = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((r, w, c), q_table.dtype),
            jax.ShapeDtypeStruct((st * row_tile, 1, 1), jnp.float32),
        ],
        input_output_aliases={1: 0},  # q_table (after the prefetch arg) -> out 0
        interpret=interpret,
    )(rows, q_table, x)
    return new_q, scales[:s].reshape(s, 1)
