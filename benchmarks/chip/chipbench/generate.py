"""Traffic: the class-incremental streams, made on the device from the seed.

The semantics are those of the repository's ``data/synthetic.py`` generators,
copied here so that no change to the program can move them:

* images (``ClassIncrementalImages``): task t owns classes
  ``[t*C, (t+1)*C)``; a sample is its class prototype plus Gaussian noise
  (``noise`` standard deviation), float32 [H, W, 3];
* tokens (``TaskTokenStream``): task t is a Markov-1 chain over its own band
  ``[lo_t, lo_t + span)`` of the active vocabulary, with Dirichlet(alpha)
  transition rows; ``labels`` are the tokens shifted by one.

Unlike those numpy generators, every row here is a pure function of
``(seed, kind, row id)`` and is drawn with ``jax.random`` on the device, so a
pool of rows is made in bulk at set-up and the plain reference can make any
single row again, bit for bit, without keeping it.

Row kinds: ``STREAM`` rows are the batches the window trains on (row id =
pool batch * b + j); ``PREFILL`` rows are what the buffer holds at set-up
(row id = bucket * slots + slot, task = bucket).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

STREAM, PREFILL = 1, 2

# Positions of each record leaf that its fingerprint keeps (after flattening):
# four at each end. Random rows never agree on all of them.
FP_POS = (0, 1, 2, 3, -4, -3, -2, -1)


def root_key(seed: int):
    """A key that uses every bit of ``seed`` (``PRNGKey`` keeps only 32)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def _kind_key(seed_key, kind: int, what: int):
    return jax.random.fold_in(jax.random.fold_in(seed_key, kind), what)


# ---------------------------------------------------------------------------
# images
# ---------------------------------------------------------------------------


def image_rows(seed_key, kind, ids, tasks, tr):
    """Rows ``ids`` (i32[n]) of tasks ``tasks`` (i32[n]):
    {"images": f32[n,H,W,3], "label": i32[n], "task": i32[n]}."""
    h = tr["image_size"]
    shape = (h, h, 3)
    c = tr["classes_per_task"]
    k_lab = _kind_key(seed_key, kind, 0)
    k_noise = _kind_key(seed_key, kind, 1)
    k_proto = _kind_key(seed_key, 0, 2)  # prototypes are shared by all kinds

    def one(i, t):
        label = t * c + jax.random.randint(jax.random.fold_in(k_lab, i), (), 0, c)
        proto = jax.random.normal(jax.random.fold_in(k_proto, label), shape)
        noise = jax.random.normal(jax.random.fold_in(k_noise, i), shape)
        return proto + tr["noise"] * noise, label.astype(jnp.int32)

    images, labels = jax.vmap(one)(ids, tasks)
    return {"images": images, "label": labels, "task": tasks.astype(jnp.int32)}


# ---------------------------------------------------------------------------
# tokens
# ---------------------------------------------------------------------------


def token_band(tr, task):
    span = int(tr["vocab_active"] * (1 - tr["shared_frac"])) // tr["num_tasks"]
    lo = int(tr["vocab_active"] * tr["shared_frac"]) + task * span
    return lo, span


def transition_logits(seed_key, tr):
    """Log Dirichlet(alpha) transition rows, f32[num_tasks, span, span]."""
    _, span = token_band(tr, 0)
    k = _kind_key(seed_key, 0, 3)
    return jnp.stack([
        jax.random.loggamma(jax.random.fold_in(k, t), tr["dirichlet_alpha"],
                            (span, span))
        for t in range(tr["num_tasks"])])


def token_rows(seed_key, kind, ids, tasks, tr, trans=None):
    """Rows ``ids`` of tasks ``tasks``: {"tokens", "labels": i32[n,S],
    "task": i32[n]}."""
    if trans is None:
        trans = transition_logits(seed_key, tr)
    s = tr["seq_len"]
    _, span = token_band(tr, 0)
    base = int(tr["vocab_active"] * tr["shared_frac"])
    k_row = _kind_key(seed_key, kind, 4)
    keys = jax.vmap(lambda i: jax.random.fold_in(k_row, i))(ids)
    first = jax.vmap(lambda k: jax.random.randint(k, (), 0, span))(keys)

    def step(prev, pos):
        def draw(k, t, p):
            return jax.random.categorical(jax.random.fold_in(k, pos + 1),
                                          trans[t, p])
        nxt = jax.vmap(draw)(keys, tasks, prev).astype(jnp.int32)
        return nxt, nxt

    _, rest = jax.lax.scan(step, first.astype(jnp.int32), jnp.arange(s))
    chain = jnp.concatenate([first[None].astype(jnp.int32), rest], axis=0).T
    chain = chain + (base + tasks * span)[:, None].astype(jnp.int32)
    return {"tokens": chain[:, :-1], "labels": chain[:, 1:],
            "task": tasks.astype(jnp.int32)}


# ---------------------------------------------------------------------------
# one interface over both
# ---------------------------------------------------------------------------


def rows(seed_key, kind, ids, tasks, tr):
    if tr["records"] == "images":
        return image_rows(seed_key, kind, ids, tasks, tr)
    if tr["records"] == "tokens":
        return token_rows(seed_key, kind, ids, tasks, tr)
    raise ValueError(f"unknown record kind {tr['records']!r}")


def record_spec(tr):
    """ShapeDtypeStructs of one record (the buffer's item spec)."""
    if tr["records"] == "images":
        h = tr["image_size"]
        return {"images": jax.ShapeDtypeStruct((h, h, 3), jnp.float32),
                "label": jax.ShapeDtypeStruct((), jnp.int32),
                "task": jax.ShapeDtypeStruct((), jnp.int32)}
    s = tr["seq_len"]
    return {"tokens": jax.ShapeDtypeStruct((s,), jnp.int32),
            "labels": jax.ShapeDtypeStruct((s,), jnp.int32),
            "task": jax.ShapeDtypeStruct((), jnp.int32)}


def label_field(tr):
    return "label" if tr["records"] == "images" else "labels"


def fingerprint(records, lead):
    """Per-record fingerprint: for each leaf (sorted by name), the values at
    ``FP_POS`` of the flattened record as f32 (an int leaf's values are exact
    in f32 below 2**24). The ``lead`` leading axes are kept:
    f32[*lead_shape, n_leaves * 8]. Elements are read by index, so no copy of
    the records is made."""
    parts = []
    for name in sorted(records):
        x = jnp.asarray(records[name])
        rec = x.shape[lead:]
        size = int(np.prod(rec)) if rec else 1
        for p in FP_POS:
            idx = np.unravel_index(p % size, rec) if rec else ()
            parts.append(x[(Ellipsis,) + tuple(int(i) for i in idx)]
                         .astype(jnp.float32))
    return jnp.stack(parts, axis=-1)


def stream_pool(seed, tr, n_chips):
    """The window's batches: ``pool_batches`` distinct global batches of
    ``batch_per_chip * n_chips`` rows of task ``window_task``, made on the
    device in one call and held on the host (numpy)."""
    b = tr["batch_per_chip"] * n_chips
    n = tr["pool_batches"] * b
    ids = jnp.arange(n, dtype=jnp.int32)
    tasks = jnp.full((n,), tr["window_task"], jnp.int32)
    make = jax.jit(lambda k: rows(k, STREAM, ids, tasks, tr))
    out = jax.device_get(make(root_key(seed)))
    return [{k: np.ascontiguousarray(v[i * b:(i + 1) * b]) for k, v in out.items()}
            for i in range(tr["pool_batches"])]


def prefill_records(seed_key, tr, n_workers, slots, chunk):
    """The full buffer's records, [n_workers, K, slots, ...], made in chunks of
    ``chunk`` rows inside one program so that only one chunk's temporaries are
    live. Worker w, bucket k, slot s holds PREFILL row
    ``(w * K + k) * slots + s`` of task k."""
    k_b = tr["buckets"]
    total = n_workers * k_b * slots
    spec = record_spec(tr)
    out = {name: jnp.zeros((total,) + s.shape, s.dtype) for name, s in spec.items()}
    per_task = k_b  # row r belongs to bucket (r // slots) % K

    def body(i, acc):
        ids = i * chunk + jnp.arange(chunk, dtype=jnp.int32)
        tasks = (ids // slots) % per_task
        got = rows(seed_key, PREFILL, ids, tasks, tr)
        return {name: jax.lax.dynamic_update_slice_in_dim(acc[name], got[name],
                                                          i * chunk, axis=0)
                for name in acc}

    assert total % chunk == 0, (total, chunk)
    out = jax.lax.fori_loop(0, total // chunk, body, out)
    return {name: v.reshape((n_workers, k_b, slots) + v.shape[1:])
            for name, v in out.items()}


@functools.lru_cache(maxsize=None)
def _row_maker(tr_items):
    tr = dict(tr_items)
    return jax.jit(lambda k, kind, ids, tasks: rows(k, kind, ids, tasks, tr),
                   static_argnums=(1,))


def make_rows(seed, kind, ids, tasks, tr):
    """Rows by id, on the device (used by the reference)."""
    items = tuple(sorted((k, v) for k, v in tr.items()
                         if isinstance(v, (int, float, str))))
    return _row_maker(items)(root_key(seed), kind,
                             jnp.asarray(ids, jnp.int32),
                             jnp.asarray(tasks, jnp.int32))
