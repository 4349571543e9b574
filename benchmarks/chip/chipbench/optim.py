"""Plain reference of the optimizers the configurations state: the paper's
SGD with momentum (arXiv 2406.03285, section VI-A) and AdamW, both after a
clip of the gradient's global norm and under a linear warm-up of the learning
rate (``peak * min(1, (step + 1) / warmup)``, the peak times the number of
workers where ``linear_scaling`` is set, capped at ``max_scaled_lr``)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

_map = jax.tree_util.tree_map


def init(tc, params):
    zeros = _map(jnp.zeros_like, params)
    state = {"step": 0, "mu": zeros}
    if tc["optimizer"] == "adamw":
        state["nu"] = _map(jnp.zeros_like, params)
    return state


def update(tc, grads, state, params, n_workers=1):
    """One step. Returns (params, state, the clipped gradient)."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.minimum(1.0, tc["grad_clip"] / jnp.maximum(norm, 1e-9))
    grads = _map(lambda g: g * scale, grads)
    peak = tc["peak_lr"] * (n_workers if tc["linear_scaling"] else 1)
    peak = min(peak, tc["max_scaled_lr"])
    step = state["step"]
    lr = peak * min(1.0, (step + 1) / max(tc["warmup_steps"], 1))
    wd = tc["weight_decay"]
    if tc["optimizer"] == "adamw":
        b1, b2, eps = tc["b1"], tc["b2"], tc["eps"]
        mu = _map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
        nu = _map(lambda v, g: b2 * v + (1 - b2) * g * g, state["nu"], grads)
        t = step + 1
        params = _map(lambda p, m, v: p - lr * ((m / (1 - b1 ** t))
                                                / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
                                                + wd * p), params, mu, nu)
        return params, {"step": t, "mu": mu, "nu": nu}, grads
    if tc["optimizer"] == "sgd":
        mu = _map(lambda m, g, p: tc["momentum"] * m + g + wd * p,
                  state["mu"], grads, params)
        params = _map(lambda p, m: p - lr * m, params, mu)
        return params, {"step": step + 1, "mu": mu}, grads
    raise ValueError(f"unknown optimizer {tc['optimizer']!r}")
