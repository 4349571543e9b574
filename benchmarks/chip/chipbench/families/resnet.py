"""ResNet family: the program's configuration, the benchmark's weights, the
model FLOPs and a plain float32 reference of the forward pass and the loss.

The reference follows He et al. (arXiv 1512.03385), ResNet v1.5 bottlenecks
(stride on the 3x3 convolution), with the departures the configuration file
states: GroupNorm in place of BatchNorm, and XLA's 'SAME' padding.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.precision import HIGHEST, quantize


def program_model(cfg):
    """The program's ``CNNConfig`` for this configuration file."""
    from repro.configs.resnet50_cl import CNNConfig

    if cfg["bottleneck_expansion"] != 4 or cfg["stem_kernel"] != 7:
        raise ValueError("the program builds bottleneck ResNets with a 7x7 stem only")
    return CNNConfig(name=cfg["name"], variant="resnet50",
                     num_classes=cfg["num_classes"], width=cfg["width"],
                     stage_blocks=tuple(cfg["stage_blocks"]), bottleneck=True,
                     image_size=cfg["image_size"], channels=cfg["channels"],
                     stem="imagenet")


def _blocks(cfg):
    """(stage, block, cin, mid, cout, stride) of every bottleneck block."""
    out, cin = [], cfg["width"]
    for s, n in enumerate(cfg["stage_blocks"]):
        cout = cfg["width"] * (2 ** s) * cfg["bottleneck_expansion"]
        for b in range(n):
            out.append((s, b, cin, cout // cfg["bottleneck_expansion"], cout,
                        2 if (b == 0 and s > 0) else 1))
            cin = cout
    return out


def param_shapes(cfg):
    """The parameter tree in the program's layout, as ShapeDtypeStructs."""
    f32 = jnp.float32
    sds = lambda *s: jax.ShapeDtypeStruct(s, f32)  # noqa: E731
    gn = lambda c: {"scale": sds(c), "bias": sds(c)}  # noqa: E731
    k, w = cfg["stem_kernel"], cfg["width"]
    stages = [[] for _ in cfg["stage_blocks"]]
    for s, _, cin, mid, cout, stride in _blocks(cfg):
        p = {"conv1": sds(1, 1, cin, mid), "gn1": gn(mid),
             "conv2": sds(3, 3, mid, mid), "gn2": gn(mid),
             "conv3": sds(1, 1, mid, cout), "gn3": gn(cout)}
        if stride != 1 or cin != cout:
            p["proj"] = sds(1, 1, cin, cout)
            p["gnp"] = gn(cout)
        stages[s].append(p)
    cin = _blocks(cfg)[-1][4]
    return {"stem": sds(k, k, cfg["channels"], w), "gn_stem": gn(w),
            "stages": stages, "head": sds(cin, cfg["num_classes"])}


def init_leaf(path: str, shape):
    """(mean, std) of the benchmark's random weights for one leaf: He-normal
    convolutions, a 1/sqrt(fan_in) head, unit GroupNorm scale, zero bias."""
    if path.endswith("['scale']"):
        return 1.0, 0.0
    if path.endswith("['bias']"):
        return 0.0, 0.0
    if len(shape) == 4:
        return 0.0, float(np.sqrt(2.0 / (shape[0] * shape[1] * shape[2])))
    return 0.0, float(1.0 / np.sqrt(shape[0]))


def forward_flops(cfg) -> float:
    """Forward FLOPs of one image: 2 x multiply-adds of every convolution and
    of the head (normalisation, pooling and activations not counted)."""
    h = cfg["image_size"]

    def conv(hw, k, cin, cout):
        return 2.0 * hw * hw * k * k * cin * cout

    hw = -(-h // cfg["stem_stride"])
    total = conv(hw, cfg["stem_kernel"], cfg["channels"], cfg["width"])
    hw = -(-hw // 2)  # 3x3 stride-2 max pool
    for _, _, cin, mid, cout, stride in _blocks(cfg):
        out = -(-hw // stride)
        total += conv(hw, 1, cin, mid) + conv(out, 3, mid, mid) + conv(out, 1, mid, cout)
        if stride != 1 or cin != cout:
            total += conv(out, 1, cin, cout)
        hw = out
    return total + 2.0 * _blocks(cfg)[-1][4] * cfg["num_classes"]


def train_flops_per_row(cfg, tr) -> float:
    """Forward and backward of one row: three times the forward."""
    return 3.0 * forward_flops(cfg)


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------


def _conv(x, w, stride, qdt):
    return jax.lax.conv_general_dilated(
        quantize(x, qdt), quantize(w, qdt), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)


def _gn(p, x, cfg):
    b, h, w, c = x.shape
    g = min(cfg["norm_groups"], c)
    while c % g:
        g -= 1
    xg = x.reshape(b, h, w, g, c // g)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mu), axis=(1, 2, 4), keepdims=True)
    xn = ((xg - mu) / jnp.sqrt(var + cfg["norm_eps"])).reshape(b, h, w, c)
    return xn * p["scale"] + p["bias"]


def row_nll(cfg, params, rows, qdt=None):
    """Summed cross-entropy of ``rows`` ({"images", "label"}) and the count of
    rows with a label (label < 0 counts for nothing)."""
    x = rows["images"].astype(jnp.float32)
    x = jax.nn.relu(_gn(params["gn_stem"], _conv(x, params["stem"],
                                                  cfg["stem_stride"], qdt), cfg))
    p = cfg["stem_pool"]
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, p, p, 1),
                              (1, 2, 2, 1), "SAME")
    for s, b, cin, _, cout, stride in _blocks(cfg):
        blk = params["stages"][s][b]
        h = jax.nn.relu(_gn(blk["gn1"], _conv(x, blk["conv1"], 1, qdt), cfg))
        h = jax.nn.relu(_gn(blk["gn2"], _conv(h, blk["conv2"], stride, qdt), cfg))
        h = _gn(blk["gn3"], _conv(h, blk["conv3"], 1, qdt), cfg)
        sc = x if "proj" not in blk else _gn(blk["gnp"],
                                             _conv(x, blk["proj"], stride, qdt), cfg)
        x = jax.nn.relu(h + sc)
    x = jnp.mean(x, axis=(1, 2))
    logits = jnp.dot(quantize(x, qdt), quantize(params["head"], qdt),
                     precision=HIGHEST)
    labels = rows["label"]
    valid = labels >= 0
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(valid, logz - gold, 0.0)), jnp.sum(valid)
