"""The numbers that decide ``correct``, each held against its limit.

* ``loss_gap``: the largest relative gap of a checked step's loss;
* ``grad_gap``: the first gradient as the optimizer got it, read as the norm
  of each leaf of its first moment after one step, by the worst leaf: the gap
  between the two norms over the larger of the reference leaf's norm and the
  median leaf's;
* ``update_gap``: the same, of each leaf's change over the checked steps,
  leaving out leaves whose reference gradient is under a thousandth of the
  median leaf's (they move by round-off alone);
* ``moment_dir_gap``: the distance of the first moments after the last
  checked step, by the worst leaf: the norm of the difference of the two
  leaves over the larger of the reference leaf's norm and the median leaf's.
  Norms alone barely see rounding noise, which averages out in them; this
  sees it. In a cell whose pending representatives start invalid, the first
  step trains no replay row; the later steps do, and this sees them;
* ``moment_dir_total``: the same distance of the whole trees, over the
  reference tree's norm. Where rounding alone turns a few small leaves'
  gradients (bfloat16 convolutions ahead of a normalisation), the worst
  leaf reads the same for a sound program and its control; the whole tree
  does not;
* ``buffer_mismatches`` (cells with a buffer): every count, offered-count,
  slot content and pending representative that differs from the reference.
"""
from __future__ import annotations

import numpy as np

NEGLIGIBLE_GRAD = 1e-3


def worst_leaf_gap(got, want, keep=None):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if keep is None:
        keep = np.ones(want.shape, bool)
    floor = np.maximum(want, np.median(want[keep]))
    gaps = np.abs(got - want) / np.maximum(floor, 1e-30)
    return float(np.max(gaps[keep]))


def _leaf_dists(got_tree, want_tree):
    """Per leaf: the norm of the difference of the two leaves, and the
    reference leaf's norm."""
    diff, want = [], []
    for a, b in zip(got_tree, want_tree):
        b = np.asarray(b, np.float64)
        diff.append(np.linalg.norm(np.asarray(a, np.float64) - b))
        want.append(np.linalg.norm(b))
    return np.asarray(diff), np.asarray(want)


def dir_gap(got_tree, want_tree):
    """The worst leaf's norm of the difference of two trees' leaves, over the
    larger of the reference leaf's norm and the median leaf's."""
    diff, want = _leaf_dists(got_tree, want_tree)
    return float(np.max(diff / np.maximum(np.maximum(want, np.median(want)), 1e-30)))


def dir_gap_total(got_tree, want_tree):
    """The norm of the difference of the whole trees over the reference's."""
    diff, want = _leaf_dists(got_tree, want_tree)
    return float(np.sqrt(np.sum(diff ** 2) / max(np.sum(want ** 2), 1e-60)))


def training_numbers(got, ref):
    """``got``/``ref``: {"loss", "mu1", "delta", "mu_last_tree"}
    (and ``ref["grad1"]``). Every number is worked out; a cell compares those
    its limits name."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(got["loss"], ref["loss"]))
    g1 = np.asarray(ref["grad1"])
    keep = g1 >= NEGLIGIBLE_GRAD * np.median(g1)
    out = {"loss_gap": float(loss),
           "grad_gap": worst_leaf_gap(got["mu1"], ref["mu1"]),
           "update_gap": worst_leaf_gap(got["delta"], ref["delta"], keep)}
    out["moment_dir_gap"] = dir_gap(got["mu_last_tree"], ref["mu_last_tree"])
    out["moment_dir_total"] = dir_gap_total(got["mu_last_tree"], ref["mu_last_tree"])
    return out, int(np.sum(~keep))


def worst_leaves(got, want, names, k=3, keep=None):
    """The k leaves with the largest gaps: (name, gap, got, want, median)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    keep = np.ones(want.shape, bool) if keep is None else keep
    med = np.median(want[keep])
    gaps = np.where(keep, np.abs(got - want) / np.maximum(np.maximum(want, med), 1e-30), -1)
    order = np.argsort(-gaps)[:k]
    return [[names[i], float(gaps[i]), float(got[i]), float(want[i]), float(med)]
            for i in order]


def _fp_in(fp, cands):
    return any(np.array_equal(fp, c) for c in cands)


def buffer_mismatches(got, want, pending_want):
    """``got``: {"fp": [W,K,S,F], "counts", "seen", "pending": per step
    ([W,r,F], valid [W,r])}; ``want``: ``reference.expected_buffer``;
    ``pending_want``: per step ``reference.pending_fingerprints``."""
    bad = int(np.sum(np.asarray(got["counts"]) != want["counts"]))
    bad += int(np.sum(np.asarray(got["seen"]) != want["seen"]))
    fp = np.asarray(got["fp"])
    same = np.all(fp == want["prefill"], axis=-1)
    for (w, k, s) in want["written"]:
        same[w, k, s] = _fp_in(fp[w, k, s], want["written"][(w, k, s)])
    bad += int(np.sum(~same))
    for (rep_fp, valid), step_want in zip(got["pending"], pending_want):
        for w, reps in enumerate(step_want):
            for j, (cands, ok) in enumerate(reps):
                if bool(valid[w][j]) != bool(ok):
                    bad += 1
                elif ok and not _fp_in(rep_fp[w][j], cands):
                    bad += 1
    return bad


def verdict(numbers, limits):
    """(correct, lines): every number the limits name, with its limit, in a
    fixed order."""
    names = sorted(k for k in limits if k != "set_from")
    lines = {k: {"value": numbers[k], "limit": limits[k]} for k in names}
    return all(numbers[k] <= limits[k] for k in names), lines
