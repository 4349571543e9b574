"""Llama-style decoder family (SmolLM): the program's configuration, the
benchmark's weights, the model FLOPs and a plain float32 reference.

The reference follows the Hugging Face Llama block: RMSNorm, rotary position
embedding on the two halves of each head (``rotate_half``), grouped-query
causal attention (query head h reads key/value head h // (H / KV)), SwiGLU MLP
``down(silu(gate(x)) * up(x))``, final RMSNorm and, with tied embeddings, the
embedding table as the output head. Its cross-entropy is the mean over every
position whose label is not negative.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.precision import HIGHEST, quantize


def program_model(cfg):
    """The program's ``ModelConfig`` for this configuration file."""
    from repro.configs.base import ModelConfig

    if cfg["hidden_act"] != "silu" or cfg["attention_bias"]:
        raise ValueError("the program's dense block is SwiGLU without biases")
    if cfg["rms_norm_eps"] != 1e-6:
        raise ValueError("the program's RMSNorm epsilon is fixed at 1e-6")
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        activation="swiglu", rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"], source=cfg["source"])


def param_shapes(cfg):
    """The parameter tree in the program's layout (layers stacked on a leading
    axis under ``units/layer0``), as ShapeDtypeStructs."""
    f32 = jnp.float32
    sds = lambda *s: jax.ShapeDtypeStruct(s, f32)  # noqa: E731
    n, d, ff = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["intermediate_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    layer = {"norm1": {"scale": sds(n, d)},
             "attn": {"wq": sds(n, d, hq), "wk": sds(n, d, hkv),
                      "wv": sds(n, d, hkv), "wo": sds(n, hq, d)},
             "norm2": {"scale": sds(n, d)},
             "mlp": {"wi": sds(n, d, ff), "wo": sds(n, ff, d), "wg": sds(n, d, ff)}}
    out = {"embed": sds(cfg["vocab_size"], d), "units": {"layer0": layer},
           "final_norm": {"scale": sds(d)}}
    if not cfg["tie_word_embeddings"]:
        out["lm_head"] = sds(cfg["vocab_size"], d)
    return out


def init_leaf(path: str, shape):
    """(mean, std): unit norm scales, 0.02 embeddings, 1/sqrt(fan_in) dense."""
    if path.endswith("['scale']"):
        return 1.0, 0.0
    if "embed" in path or "lm_head" in path:
        return 0.0, 0.02
    return 0.0, float(1.0 / np.sqrt(shape[-2]))


def _matmul_params(cfg) -> float:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    per_layer = 2 * d * hq + 2 * d * hkv + 3 * d * ff
    return float(cfg["num_hidden_layers"] * per_layer + d * cfg["vocab_size"])


def train_flops_per_row(cfg, tr) -> float:
    """Forward and backward FLOPs of one sequence, as PaLM (arXiv 2204.02311,
    appendix B) counts them: 6 N per token, N the weights of every matrix
    product (the output head included, the embedding lookup not), plus
    12 L H d_head S per token for attention over the whole sequence."""
    s = tr["seq_len"]
    attn = 12.0 * cfg["num_hidden_layers"] * cfg["num_attention_heads"] \
        * cfg["head_dim"] * s
    return s * (6.0 * _matmul_params(cfg) + attn)


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------


def _mm(x, w, qdt):
    return jnp.matmul(quantize(x, qdt), quantize(w, qdt), precision=HIGHEST)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, angles):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(cfg, qdt, angles, x, lp):
    s = x.shape[0]
    hd, h, kv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps = cfg["rms_norm_eps"]
    a = _rms(x, lp["norm1"]["scale"], eps)
    q = _rope(_mm(a, lp["attn"]["wq"], qdt).reshape(s, h, hd), angles)
    k = _rope(_mm(a, lp["attn"]["wk"], qdt).reshape(s, kv, hd), angles)
    v = _mm(a, lp["attn"]["wv"], qdt).reshape(s, kv, hd)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    scores = jnp.einsum("shd,thd->hst", quantize(q, qdt), quantize(k, qdt),
                        precision=HIGHEST) / np.sqrt(hd)
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    o = jnp.einsum("hst,thd->shd", quantize(probs, qdt), quantize(v, qdt),
                   precision=HIGHEST).reshape(s, h * hd)
    x = x + _mm(o, lp["attn"]["wo"], qdt)
    m = _rms(x, lp["norm2"]["scale"], eps)
    up = _mm(m, lp["mlp"]["wi"], qdt)
    gate = _mm(m, lp["mlp"]["wg"], qdt)
    return x + _mm(jax.nn.silu(gate) * up, lp["mlp"]["wo"], qdt)


def _one_row(cfg, params, tokens, labels, qdt):
    s = tokens.shape[0]
    hd = cfg["head_dim"]
    inv = 1.0 / (cfg["rope_theta"] ** (jnp.arange(hd // 2, dtype=jnp.float32)
                                       / (hd // 2)))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    x = params["embed"][tokens]
    layer = jax.checkpoint(lambda c, lp: (_layer(cfg, qdt, angles, c, lp), None))
    x, _ = jax.lax.scan(layer, x, params["units"]["layer0"])
    x = _rms(x, params["final_norm"]["scale"], cfg["rms_norm_eps"])
    head = params.get("lm_head", params["embed"])
    logits = _mm(x, head.T, qdt)
    valid = labels >= 0
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(valid, logz - gold, 0.0)), jnp.sum(valid)


def row_nll(cfg, params, rows, qdt=None):
    """Summed next-token cross-entropy of ``rows`` ({"tokens", "labels"}) and
    the count of positions with a label, one sequence at a time."""
    def body(acc, row):
        nll, cnt = _one_row(cfg, params, row[0], row[1], qdt)
        return (acc[0] + nll, acc[1] + cnt), None

    (nll, cnt), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32)),
        (rows["tokens"], rows["labels"]))
    return nll, cnt
