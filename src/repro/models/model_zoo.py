"""Unified model API: ``build_model(cfg)`` returns an ``LM`` bundle of pure functions.

Every architecture exposes the same surface:
  * ``init(key, max_seq)``                      -> params
  * ``forward(params, batch, ctx)``             -> (logits, aux_loss)   (train / prefill)
  * ``loss(params, batch, ctx)``                -> (scalar, metrics)
  * ``init_cache(params, batch_size, seq_len)`` -> decode caches
  * ``decode(params, batch, caches, index, ctx)``-> (logits, new_caches)
  * ``input_specs(shape)`` / ``decode_specs(shape)`` -> ShapeDtypeStruct stand-ins

``input_specs`` is the single source of truth for what a training record looks like —
the rehearsal buffer stores exactly one record (minus the batch axis), which is how the
paper's technique stays architecture-agnostic (DESIGN.md §5).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.models import transformer as tf
from repro.models.transformer import StackCtx


@dataclass(frozen=True)
class LM:
    cfg: Any
    init: Callable
    forward: Callable
    loss: Callable
    init_cache: Callable
    decode: Callable
    input_specs: Callable
    decode_specs: Callable
    # Model-outputs tap (DESIGN.md §9): (params, batch, ctx) ->
    # {"logits": [B,S,V], "embed": [B,D], "aux": scalar} — hidden state runs
    # once, logits + per-record penultimate embedding share it. None for
    # families without the tap (enc-dec).
    outputs: Any = None


# MoE load-balance aux-loss weight: the single definition the LM losses and
# the tap-strategy losses (repro.strategy) share, so a strategy-built loss
# stays comparable to the plain model loss on the same model.
DEFAULT_AUX_WEIGHT = 0.01


def cross_entropy(logits, labels, mask=None, label_smoothing: float = 0.0):
    """Mean token-level CE in f32; labels < 0 are ignored."""
    logits = logits.astype(jnp.float32)
    valid = labels >= 0 if mask is None else mask & (labels >= 0)
    labels_safe = jnp.maximum(labels, 0)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels_safe[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if label_smoothing:
        nll = (1 - label_smoothing) * nll + label_smoothing * (
            logz - jnp.mean(logits, axis=-1)
        )
    denom = jnp.maximum(jnp.sum(valid), 1)
    return jnp.sum(jnp.where(valid, nll, 0.0)) / denom


# Largest float32 logits block the decoder loss materialises at once. Above it
# (long sequences over a big vocabulary: SmolLM's 49152 x 2048 tokens x 15 rows
# is 6 GB) the loss runs the head + CE over several sequence chunks, each
# rematerialised in the backward pass, so the full [B, S, V] logits never
# exist. Below it the whole sequence is one chunk.
LOGITS_BLOCK_BYTES = 512 << 20


def _ce_chunk(b: int, s: int, v: int) -> int:
    chunk = s
    while chunk % 2 == 0 and b * chunk * v * 4 > LOGITS_BLOCK_BYTES:
        chunk //= 2
    return chunk


def chunked_cross_entropy(head, hidden, labels, chunk: int):
    """``cross_entropy(head(hidden), labels)`` computed ``chunk`` positions at
    a time: hidden [B, S, D] -> per-chunk logits [B, chunk, V] in f32, summed
    NLL and valid counts carried through a scan, each chunk rematerialised in
    the backward pass. With ``chunk == S`` it is bit-identical to the
    one-block form; with more chunks, equal up to summation order."""
    b, s, d = hidden.shape
    n = s // chunk
    hs = jnp.swapaxes(hidden.reshape(b, n, chunk, d), 0, 1)
    ls = jnp.swapaxes(labels.reshape(b, n, chunk), 0, 1)

    @jax.checkpoint
    def piece(h, lab):
        logits = head(h).astype(jnp.float32)
        valid = lab >= 0
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, jnp.maximum(lab, 0)[..., None],
                                   axis=-1)[..., 0]
        return jnp.sum(jnp.where(valid, logz - gold, 0.0)), jnp.sum(valid)

    def body(carry, xs):
        nll, cnt = piece(*xs)
        return (carry[0] + nll, carry[1] + cnt), None

    (nll, cnt), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32),
                                        jnp.zeros((), jnp.int32)), (hs, ls))
    return nll / jnp.maximum(cnt, 1)


# ---------------------------------------------------------------------------
# Input specs per family — ShapeDtypeStruct stand-ins (no allocation; dry-run contract)
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _train_specs(cfg, shape):
    b, s = shape.global_batch, shape.seq_len
    if cfg.family == "encdec":
        return {
            "frames": _sds((b, s, cfg.d_model), jnp.float32),  # stubbed audio frontend
            "tokens": _sds((b, s), jnp.int32),
            "labels": _sds((b, s), jnp.int32),
            "task": _sds((b,), jnp.int32),
        }
    if cfg.frontend == "patch_stub":
        return {
            "embeddings": _sds((b, s, cfg.d_model), jnp.float32),  # stubbed vision frontend
            "positions": _sds((b, s, 3), jnp.int32),  # M-RoPE (t, h, w)
            "labels": _sds((b, s), jnp.int32),
            "task": _sds((b,), jnp.int32),
        }
    return {
        "tokens": _sds((b, s), jnp.int32),
        "labels": _sds((b, s), jnp.int32),
        "task": _sds((b,), jnp.int32),
    }


def _decode_specs(cfg, shape):
    b = shape.global_batch
    if cfg.frontend == "patch_stub":
        return {"embedding": _sds((b, 1, cfg.d_model), jnp.float32)}
    return {"token": _sds((b, 1), jnp.int32)}


# ---------------------------------------------------------------------------
# Builders
# ---------------------------------------------------------------------------


def build_model(cfg) -> LM:
    if cfg.family == "encdec":
        return _build_encdec(cfg)
    return _build_decoder(cfg)


def _build_decoder(cfg) -> LM:
    def init(key, max_seq: int):
        return tf.init_decoder(key, cfg, max_seq)

    def forward(params, batch, ctx: StackCtx):
        return tf.forward_decoder(params, batch, cfg, ctx)

    def loss(params, batch, ctx: StackCtx, aux_weight: float = DEFAULT_AUX_WEIGHT):
        hidden, aux = tf.hidden_decoder(params, batch, cfg, ctx)
        b, s, _ = hidden.shape
        ce = chunked_cross_entropy(
            lambda h: tf.logits_from(params, h, cfg, ctx), hidden,
            batch["labels"], _ce_chunk(b, s, cfg.vocab_size))
        metrics = {"ce": ce, "aux": aux}
        return ce + aux_weight * aux, metrics

    def outputs(params, batch, ctx: StackCtx):
        hidden, aux = tf.hidden_decoder(params, batch, cfg, ctx)
        logits = tf.logits_from(params, hidden, cfg, ctx)
        # per-record embedding: mean over sequence positions of the
        # post-final-norm hidden state (the activations the head consumes)
        embed = jnp.mean(hidden.astype(jnp.float32), axis=1)
        return {"logits": logits, "embed": embed, "aux": aux}

    def init_cache(params, batch_size: int, seq_len: int, dtype=jnp.bfloat16):
        return tf.init_decoder_cache(cfg, batch_size, seq_len, dtype)

    def decode(params, batch, caches, index, ctx: StackCtx):
        return tf.decode_step(params, batch, caches, index, cfg, ctx)

    return LM(
        cfg=cfg,
        init=init,
        forward=forward,
        loss=loss,
        init_cache=init_cache,
        decode=decode,
        input_specs=lambda shape: _train_specs(cfg, shape),
        decode_specs=lambda shape: _decode_specs(cfg, shape),
        outputs=outputs,
    )


def _build_encdec(cfg) -> LM:
    def init(key, max_seq: int):
        return tf.init_encdec(key, cfg, max_seq)

    def forward(params, batch, ctx: StackCtx):
        enc_out = tf.encode(params, batch["frames"], cfg, ctx)
        logits = tf.decode_train_encdec(params, batch["tokens"], enc_out, cfg, ctx)
        return logits, jnp.zeros((), jnp.float32)

    def loss(params, batch, ctx: StackCtx, aux_weight: float = 0.0):
        logits, aux = forward(params, batch, ctx)
        ce = cross_entropy(logits, batch["labels"])
        return ce, {"ce": ce, "aux": aux}

    def init_cache(params, batch_size: int, seq_len: int, dtype=jnp.bfloat16):
        # Serving context: encoder output for a stubbed frame window of the same length.
        enc_out = jnp.zeros((batch_size, seq_len, cfg.d_model), dtype)
        return tf.init_encdec_cache(params, cfg, batch_size, seq_len, enc_out=None, dtype=dtype)

    def decode(params, batch, caches, index, ctx: StackCtx):
        return tf.decode_step_encdec(params, batch, caches, index, cfg, ctx)

    return LM(
        cfg=cfg,
        init=init,
        forward=forward,
        loss=loss,
        init_cache=init_cache,
        decode=decode,
        input_specs=lambda shape: _train_specs(cfg, shape),
        decode_specs=lambda shape: _decode_specs(cfg, shape),
    )
