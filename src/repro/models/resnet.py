"""ResNet-18/50 and a GhostNet-style variant — the paper's own evaluation models.

Pure-JAX CNN classifiers used by the faithful reproduction benchmarks (Figs. 5-7 at CPU
scale). GroupNorm substitutes for BatchNorm (functional purity under data parallelism;
noted in DESIGN.md — the paper's technique is norm-agnostic).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _conv_init(key, kh, kw, cin, cout):
    fan_in = kh * kw * cin
    return jax.random.normal(key, (kh, kw, cin, cout)) * np.sqrt(2.0 / fan_in)


def conv(x, w, stride=1, padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def init_groupnorm(c, groups=8):
    return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}


def groupnorm(p, x, eps=1e-5):
    b, h, w, c = x.shape
    g = min(8, c)
    while c % g:
        g -= 1
    xg = x.reshape(b, h, w, g, c // g).astype(jnp.float32)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(xg, axis=(1, 2, 4), keepdims=True)
    xn = ((xg - mu) * jax.lax.rsqrt(var + eps)).reshape(b, h, w, c)
    return (xn * p["scale"] + p["bias"]).astype(x.dtype)


def _init_basic_block(key, cin, cout, stride):
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "conv1": _conv_init(k1, 3, 3, cin, cout),
        "gn1": init_groupnorm(cout),
        "conv2": _conv_init(k2, 3, 3, cout, cout),
        "gn2": init_groupnorm(cout),
    }
    if stride != 1 or cin != cout:
        p["proj"] = _conv_init(k3, 1, 1, cin, cout)
        p["gnp"] = init_groupnorm(cout)
    return p


def _apply_basic_block(p, x, stride):
    h = jax.nn.relu(groupnorm(p["gn1"], conv(x, p["conv1"], stride)))
    h = groupnorm(p["gn2"], conv(h, p["conv2"]))
    sc = x if "proj" not in p else groupnorm(p["gnp"], conv(x, p["proj"], stride))
    return jax.nn.relu(h + sc)


def _init_bottleneck(key, cin, cout, stride):
    mid = cout // 4
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p = {
        "conv1": _conv_init(k1, 1, 1, cin, mid),
        "gn1": init_groupnorm(mid),
        "conv2": _conv_init(k2, 3, 3, mid, mid),
        "gn2": init_groupnorm(mid),
        "conv3": _conv_init(k3, 1, 1, mid, cout),
        "gn3": init_groupnorm(cout),
    }
    if stride != 1 or cin != cout:
        p["proj"] = _conv_init(k4, 1, 1, cin, cout)
        p["gnp"] = init_groupnorm(cout)
    return p


def _apply_bottleneck(p, x, stride):
    h = jax.nn.relu(groupnorm(p["gn1"], conv(x, p["conv1"])))
    h = jax.nn.relu(groupnorm(p["gn2"], conv(h, p["conv2"], stride)))
    h = groupnorm(p["gn3"], conv(h, p["conv3"]))
    sc = x if "proj" not in p else groupnorm(p["gnp"], conv(x, p["proj"], stride))
    return jax.nn.relu(h + sc)


def _init_ghost_block(key, cin, cout, stride):
    """Ghost module: half the features from a dense conv, half from a cheap depthwise."""
    half = cout // 2
    k1, k2, k3 = jax.random.split(key, 3)
    p = {
        "primary": _conv_init(k1, 3, 3, cin, half),
        "gn1": init_groupnorm(half),
        "cheap": jax.random.normal(k2, (3, 3, 1, half)) * 0.2,  # depthwise (HWIO, I=1)
        "gn2": init_groupnorm(half),
    }
    if stride != 1 or cin != cout:
        p["proj"] = _conv_init(k3, 1, 1, cin, cout)
        p["gnp"] = init_groupnorm(cout)
    return p


def _apply_ghost_block(p, x, stride):
    prim = jax.nn.relu(groupnorm(p["gn1"], conv(x, p["primary"], stride)))
    cheap = jax.lax.conv_general_dilated(
        prim, p["cheap"].astype(x.dtype), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=prim.shape[-1],
    )
    cheap = jax.nn.relu(groupnorm(p["gn2"], cheap))
    h = jnp.concatenate([prim, cheap], axis=-1)
    sc = x if "proj" not in p else groupnorm(p["gnp"], conv(x, p["proj"], stride))
    return jax.nn.relu(h + sc)


_BLOCKS = {
    "resnet18": (_init_basic_block, _apply_basic_block, 1),
    "resnet50": (_init_bottleneck, _apply_bottleneck, 4),
    "ghostnet": (_init_ghost_block, _apply_ghost_block, 1),
}


def init_cnn(key, cfg):
    init_blk, _, expand = _BLOCKS[cfg.variant]
    keys = jax.random.split(key, 2 + sum(cfg.stage_blocks))
    ki = iter(keys)
    k_stem = 7 if cfg.stem == "imagenet" else 3
    params = {"stem": _conv_init(next(ki), k_stem, k_stem, cfg.channels, cfg.width),
              "gn_stem": init_groupnorm(cfg.width)}
    cin = cfg.width
    stages = []
    for s, nblocks in enumerate(cfg.stage_blocks):
        cout = cfg.width * (2 ** s) * expand
        blocks = []
        for b in range(nblocks):
            stride = 2 if (b == 0 and s > 0) else 1
            blocks.append(init_blk(next(ki), cin, cout, stride))
            cin = cout
        stages.append(blocks)
    params["stages"] = stages
    params["head"] = jax.random.normal(next(ki), (cin, cfg.num_classes)) * (1.0 / np.sqrt(cin))
    return params


def cnn_outputs(params, images, cfg):
    """The model-outputs tap: images [B,H,W,C] ->
    {"logits": [B,num_classes], "embed": [B,D]} where ``embed`` is the pooled
    penultimate activation (the feature the head projects) — computed once per
    step and shared by the loss, DER logit storage, and the GRASP
    embedding-space prototype distances (DESIGN.md §9)."""
    _, apply_blk, _ = _BLOCKS[cfg.variant]
    stride = 2 if cfg.stem == "imagenet" else 1
    x = jax.nn.relu(groupnorm(params["gn_stem"], conv(images, params["stem"], stride)))
    if cfg.stem == "imagenet":
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                                  (1, 2, 2, 1), "SAME")
    for s, blocks in enumerate(params["stages"]):
        for b, blk in enumerate(blocks):
            stride = 2 if (b == 0 and s > 0) else 1
            x = apply_blk(blk, x, stride)
    x = jnp.mean(x, axis=(1, 2))
    return {"logits": x @ params["head"].astype(x.dtype), "embed": x}


def apply_cnn(params, images, cfg):
    """images [B,H,W,C] -> logits [B,num_classes]."""
    return cnn_outputs(params, images, cfg)["logits"]
