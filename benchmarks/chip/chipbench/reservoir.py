"""Plain reference of the rehearsal buffer (the paper's Algorithm 1 with the
global sampling of section IV), written over row identities.

It shares no code with the program. What it fixes, from the paper and the
program's documented semantics:

* K buckets of S slots per worker; a full bucket stays full;
* each candidate of a mini-batch of b enters with probability c/b (one uniform
  draw per candidate); accepted candidates of one bucket fill its empty slots
  in arrival order, and in a full bucket each replaces a slot drawn uniformly
  (one draw per candidate, whether accepted or not);
* a sample of n is drawn with replacement, uniformly over the filled slots of
  all buckets, in the order of the buckets;
* with an exchange group of N workers, each worker draws N candidates, sends
  its j-th to worker j, and keeps a uniformly random valid r-subset of the N it
  receives;
* the random numbers are those of ``jax.random`` under the key lineage the
  program documents: worker w of step g uses ``fold_in(issue_key(g), w)``,
  split into (update, sample) keys; the update key splits into (accept,
  evict); a group sample's key splits into (draw, pick).

Two accepted candidates of one step that draw the same full slot are a
collision: the program does not say which one survives, so the slot holds
either, and the reference follows the one the program kept as soon as it is
shown (a sampled representative or the final contents).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Row = Tuple[int, int, int]  # (kind, row id, task)


class Reservoir:
    def __init__(self, n_workers: int, buckets: int, slots: int, prefill):
        """``prefill(w, k, s) -> Row`` names the record each full slot holds."""
        self.n, self.k, self.s = n_workers, buckets, slots
        self.slots = [[[(prefill(w, k, s),) for s in range(slots)]
                       for k in range(buckets)] for w in range(n_workers)]
        self.counts = np.full((n_workers, buckets), slots, np.int64)
        self.seen = np.full((n_workers, buckets), slots, np.int64)
        self.written = set()  # (w, k, s) written in the replayed steps

    # -- Algorithm 1 -----------------------------------------------------
    def update(self, w: int, rows: List[Row], buckets, key, c: int):
        b = len(rows)
        k_accept, k_evict = jax.random.split(key)
        accept = np.asarray(jax.random.uniform(k_accept, (b,)) < (c / b))
        evict = np.asarray(jax.random.randint(k_evict, (b,), 0, self.s))
        taken = np.zeros(self.k, np.int64)
        writes = {}
        for i in range(b):
            k = int(buckets[i])
            self.seen[w, k] += 1
            if not accept[i]:
                continue
            pos = self.counts[w, k] + taken[k]
            taken[k] += 1
            slot = int(pos) if pos < self.s else int(evict[i])
            writes.setdefault((k, slot), []).append(rows[i])
        for (k, slot), who in writes.items():
            self.slots[w][k][slot] = tuple(who)
            self.written.add((w, k, slot))
        self.counts[w] = np.minimum(self.s, self.counts[w] + taken)

    def draw(self, w: int, key, n: int):
        """n (bucket, slot) draws, uniform over filled slots, and validity."""
        counts = self.counts[w]
        total = int(counts.sum())
        u = np.asarray(jax.random.randint(key, (n,), 0, max(total, 1)))
        cum = np.cumsum(counts)
        out = []
        for x in u:
            k = min(int(np.searchsorted(cum, x, side="right")), self.k - 1)
            within = int(x) - int(cum[k] - counts[k])
            out.append((k, min(max(within, 0), self.s - 1)))
        return out, total > 0

    # -- one step of every worker ------------------------------------------
    def step(self, issue_key, worker_rows, worker_buckets, c: int, r: int,
             group: Optional[int]):
        """Update every worker with its rows, then sample its next r
        representatives: locally (``group=None``) or through an exchange
        group of ``group`` workers. Returns per worker a list of
        (candidates, valid) with ``candidates`` the tuple of rows the drawn
        slot may hold."""
        keys = [jax.random.split(jax.random.fold_in(issue_key, w))
                for w in range(self.n)]
        for w in range(self.n):
            self.update(w, worker_rows[w], worker_buckets[w], keys[w][0], c)
        if group is None:
            out = []
            for w in range(self.n):
                picks, ok = self.draw(w, keys[w][1], r)
                out.append([(self.slots[w][k][s], ok) for k, s in picks])
            return out
        n = group
        draws = []
        for w in range(self.n):
            k_draw, k_pick = jax.random.split(keys[w][1])
            picks, ok = self.draw(w, k_draw, n)
            draws.append(([(self.slots[w][k][s], ok) for k, s in picks], k_pick))
        out = []
        for w in range(self.n):
            g0 = (w // n) * n
            recv = [draws[g0 + p][0][w - g0] for p in range(n)]
            valid = jnp.asarray([ok for _, ok in recv])
            scores = (jax.random.uniform(draws[w][1], (n,))
                      + jnp.where(valid, 0.0, 1e3))
            take = np.asarray(jnp.argsort(scores))[:r]
            out.append([recv[int(t)] for t in take])
        return out

    def resolve(self, candidates, chosen: Row):
        """Follow the program's choice among collided writers of a slot."""
        for w in range(self.n):
            for k in range(self.k):
                for s in range(self.s):
                    if self.slots[w][k][s] == candidates:
                        self.slots[w][k][s] = (chosen,)
