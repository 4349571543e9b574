"""The plain reference of the first checked steps of a cell.

It replays, from the seed alone, what the timed path did in its first steps:
the benchmark's weights, the stream rows, the buffer's Algorithm 1 and sample
(``chipbench.reservoir``), the family's float32 forward pass and loss, their
gradients, and the optimizer (``chipbench.optim``). It imports nothing of the
program and takes nothing the program made. Rows are processed in blocks so
that it fits on the chip once the program's state is freed.

``qdt`` runs it as the control (operands rounded, ``chipbench.precision``);
``fault`` plants a fault in it, so that it can stand in the program's place:
``half_batch`` trains on the first half of each step's rows, the mean taken
over them; ``no_replay`` leaves the replay rows out of every step;
``frozen`` returns the state unchanged.
"""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import generate, optim
from chipbench.reservoir import Reservoir


def make_params(seed_key, shapes, init_leaf):
    """The benchmark's weights: one normal draw per leaf, keyed by its index
    in the flattened tree, scaled by the family's rule (``init_leaf``)."""
    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (path, s) in enumerate(flat):
        mean, std = init_leaf(jax.tree_util.keystr(path), s.shape)
        if std == 0.0:
            out.append(jnp.full(s.shape, mean, s.dtype))
        else:
            out.append(mean + std * jax.random.normal(
                jax.random.fold_in(seed_key, i), s.shape, s.dtype))
    return jax.tree_util.tree_unflatten(tree, out)


def weights_key(seed):
    return jax.random.fold_in(generate.root_key(seed), 11)


def lineage_key(seed):
    """Root of the step keys: step g's key is ``fold_in(lineage, g)``, the key
    the buffer update of step g+1 starts from."""
    return jax.random.fold_in(generate.root_key(seed), 13)


def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree_util.tree_leaves(tree)])


def _grad_fn(family, cfg, qdt):
    def total(params, rows):
        return family.row_nll(cfg, params, rows, qdt)

    return jax.jit(jax.value_and_grad(total, has_aux=True))


def _rows_of(seed, tr, ids: List[tuple]):
    """Make rows by identity, grouped by kind, in the given order."""
    out = None
    for kind in sorted({k for k, _, _ in ids}):
        sel = [(i, r) for i, r in enumerate(ids) if r[0] == kind]
        got = generate.make_rows(seed, kind, [r[1] for _, r in sel],
                                 [r[2] for _, r in sel], tr)
        got = {k: np.asarray(v) for k, v in got.items()}
        if out is None:
            out = {k: np.zeros((len(ids),) + v.shape[1:], v.dtype)
                   for k, v in got.items()}
        for j, (i, _) in enumerate(sel):
            for k in out:
                out[k][i] = got[k][j]
    return out


def _pad(rows, block, label_field):
    """Pad the rows to whole blocks (one compiled shape) with zero rows whose
    labels are -1: they count for nothing in the loss."""
    n = len(rows[label_field])
    extra = -n % block
    if not extra:
        return rows
    out = {k: np.concatenate([v, np.zeros((extra,) + v.shape[1:], v.dtype)])
           for k, v in rows.items()}
    out[label_field][n:] = -1
    return out


def replay(cfg, tr, family, layout, seed, steps, *, qdt=None, fault=None,
           observed=None):
    """Replay ``steps`` steps. ``layout``: {"n_workers", "group" (None for a
    local sample), "slots", "rehearse", "initial_reps" ("sample" or
    "invalid")}. ``observed``: the program's pending fingerprints per step, to
    follow its choice at a collision (see ``Reservoir``).

    Returns {"loss": [..], "mu1": leaf norms of the first moment after step 1,
    "mu_last_tree": its leaves after the last step,
    "grad1": leaf norms of the first (clipped) gradient, "delta": leaf norms
    of the parameters' change over the steps, "pending": per step, per worker,
    the candidate rows of each representative and its validity, "buffer": the
    final Reservoir}."""
    n = layout["n_workers"]
    b = tr["batch_per_chip"]
    lf = generate.label_field(tr)
    params = make_params(weights_key(seed), family.param_shapes(cfg), family.init_leaf)
    p0 = jax.tree_util.tree_map(jnp.copy, params)
    opt = optim.init(cfg["train"], params)
    grad_fn = _grad_fn(family, cfg, qdt)
    block = tr["reference_block_rows"]
    key0 = lineage_key(seed)

    res = None
    pending = None
    if layout["rehearse"]:
        slots = layout["slots"]
        k_b = tr["buckets"]
        res = Reservoir(n, k_b, slots, lambda w, k, s: (
            generate.PREFILL, (w * k_b + k) * slots + s, k))
        if layout["initial_reps"] == "sample":
            picks, ok = res.draw(0, key0, tr["reps"])
            pending = [[(res.slots[0][k][s], ok) for k, s in picks]]
        else:
            pending = [[((None,), False)] * tr["reps"] for _ in range(n)]
    out = {"loss": [], "pending": []}
    for g in range(steps):
        stream = [(generate.STREAM, g * b * n + j, tr["window_task"])
                  for j in range(b * n)]
        train_ids = list(stream)
        if pending is not None:
            if observed is not None and g > 0:
                _follow(res, pending, observed[g - 1], seed, tr)
            for w in range(n):
                train_ids += [cands[0] for cands, ok in pending[w] if ok]
        if fault == "no_replay":
            train_ids = list(stream)
        if fault == "half_batch":
            train_ids = train_ids[: len(train_ids) // 2]
        rows = _pad(_rows_of(seed, tr, train_ids), block, lf)
        nll, cnt, grads = 0.0, 0, None
        for i in range(0, len(rows[lf]), block):
            blk = {k: jnp.asarray(v[i:i + block]) for k, v in rows.items()
                   if k in (lf, "images", "tokens")}
            (s, c), g_blk = grad_fn(params, blk)
            nll, cnt = nll + s, cnt + c
            grads = g_blk if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g_blk)
        loss = nll / cnt
        grads = jax.tree_util.tree_map(lambda x: x / cnt, grads)
        out["loss"].append(float(loss))
        if pending is not None:
            buckets = [[tr["window_task"]] * b for _ in range(n)]
            worker_rows = [stream[w * b:(w + 1) * b] for w in range(n)]
            issue = key0 if g == 0 else jax.random.fold_in(key0, g - 1)
            pending = res.step(issue, worker_rows, buckets, tr["candidates"],
                               tr["reps"], layout["group"])
            out["pending"].append(pending)
        if fault != "frozen":
            params, opt, clipped = optim.update(cfg["train"], grads, opt, params,
                                                n_workers=n)
        else:
            clipped = grads
        if g == 0:
            out["mu1"] = np.asarray(leaf_norms(opt["mu"]))
            out["grad1"] = np.asarray(leaf_norms(clipped))
        if g == steps - 1:
            out["mu_last_tree"] = jax.tree_util.tree_leaves(opt["mu"])
    out["delta"] = np.asarray(leaf_norms(
        jax.tree_util.tree_map(jnp.subtract, params, p0)))
    out["buffer"] = res
    return out


def _follow(res, pending, observed_fp, seed, tr):
    """At a collision, adopt the writer whose fingerprint the program shows."""
    for w, reps in enumerate(pending):
        for j, (cands, ok) in enumerate(reps):
            if ok and len(cands) > 1:
                fps = np.asarray(generate.fingerprint(
                    _rows_of(seed, tr, list(cands)), 1))
                hit = [c for c, fp in zip(cands, fps)
                       if np.array_equal(fp, observed_fp[w][j])]
                if hit:
                    res.resolve(cands, hit[0])
                    reps[j] = ((hit[0],), ok)


@functools.lru_cache(maxsize=None)
def _prefill_fp_fn(tr_items, n_workers, slots, chunk):
    tr = dict(tr_items)

    def fn(seed_key):
        k_b = tr["buckets"]
        total = n_workers * k_b * slots

        def body(i, acc):
            ids = i * chunk + jnp.arange(chunk, dtype=jnp.int32)
            rows = generate.rows(seed_key, generate.PREFILL, ids,
                                 (ids // slots) % k_b, tr)
            return jax.lax.dynamic_update_slice_in_dim(
                acc, generate.fingerprint(rows, 1), i * chunk, axis=0)

        width = len(generate.record_spec(tr)) * len(generate.FP_POS)
        acc = jnp.zeros((total, width), jnp.float32)
        return jax.lax.fori_loop(0, total // chunk, body, acc).reshape(
            n_workers, k_b, slots, width)

    return jax.jit(fn)


def expected_buffer(res: Reservoir, seed, tr) -> Dict[str, object]:
    """Fingerprints of what every slot may hold: an array for the prefill
    contents and, for each slot written in the replay, its candidates'."""
    items = tuple(sorted((k, v) for k, v in tr.items()
                         if isinstance(v, (int, float, str))))
    prefill = np.asarray(_prefill_fp_fn(items, res.n, res.s, tr["prefill_chunk"])(
        generate.root_key(seed)))
    written = {}
    for w, k, s in sorted(res.written):
        cands = res.slots[w][k][s]
        written[(w, k, s)] = np.asarray(generate.fingerprint(
            _rows_of(seed, tr, list(cands)), 1))
    return {"prefill": prefill, "written": written,
            "counts": res.counts.copy(), "seen": res.seen.copy()}


def pending_fingerprints(pending, seed, tr):
    """[[(candidate fingerprints, valid)]] of one step's pending reps."""
    out = []
    for reps in pending:
        row = []
        for cands, ok in reps:
            fp = (np.asarray(generate.fingerprint(_rows_of(seed, tr, list(cands)), 1))
                  if ok else None)
            row.append((fp, ok))
        out.append(row)
    return out
