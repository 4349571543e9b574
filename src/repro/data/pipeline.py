"""Host data pipeline: background prefetch + device placement + resumable cursor.

Plays DALI's role from the paper (§V): mini-batches are produced and staged on a
background thread so the Load step overlaps the training iteration. The cursor
(task id, step within task) is part of the checkpoint state — restart replays the
exact stream position.
"""
from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional

import jax
import numpy as np

from repro.obs.trace import get_tracer

INPUT_TID = 2  # the prefetch thread's track in the Tracer (0 main, 1 checkpoint)


@dataclass
class Cursor:
    task: int = 0
    step: int = 0

    def to_dict(self):
        return {"task": self.task, "step": self.step}

    @classmethod
    def from_dict(cls, d):
        return cls(task=int(d["task"]), step=int(d["step"]))


class _FetchError:
    """Sentinel carrying an exception from the prefetch thread to ``next()``."""

    def __init__(self, exc: BaseException):
        self.exc = exc


class _EndOfStream:
    """Sentinel the worker enqueues after its last ``_limit``-bounded fetch —
    without it, a ``next()`` call past the limit would block forever on an
    empty queue whose producer has already exited."""


class Prefetcher:
    """Wraps ``fetch(cursor) -> batch`` with a bounded background prefetch queue.

    ``convert`` (e.g. ``jnp.asarray``) is applied to every batch leaf on the
    background thread, so host→device conversion overlaps training instead of
    sitting on the critical path (the trainer's Load stage, paper §V).

    Spans (``repro.obs.trace``, on any profiler trace's host plane):
    ``input.fetch`` (the ``fetch`` call) and ``input.convert`` (the
    ``convert`` call) on the thread that runs them, ``input.wait`` (``next()``
    blocked on the queue) and ``input.place`` (the placement on ``sharding``
    in ``next()``). ``counters()`` reads four always-on counters, cumulative
    over the prefetcher's life: ``batches`` handed out, ``not_ready`` (of
    those, batches with a leaf whose host-to-device copy was still in flight,
    a non-blocking check), ``convert_s`` and ``wait_s`` (host seconds in
    ``input.convert`` and ``input.wait``). Each counter has one writer
    thread: ``convert_s`` the thread that converts, the others ``next()``'s.
    """

    def __init__(self, fetch: Callable[[Cursor], Dict[str, np.ndarray]],
                 cursor: Optional[Cursor] = None, depth: int = 2,
                 sharding=None, convert: Optional[Callable] = None,
                 limit: Optional[int] = None):
        self._fetch = fetch
        self.cursor = cursor or Cursor()
        self._depth = depth
        self._sharding = sharding
        self._convert = convert
        self._limit = limit  # max fetches; None = unbounded (stop() bounds it)
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._exhausted = False  # worker hit _limit and enqueued _EndOfStream
        self._served = 0  # batches handed out by next(), either path: ONE limit
        self._counts = {"batches": 0, "not_ready": 0, "convert_s": 0.0,
                        "wait_s": 0.0}

    def counters(self) -> Dict[str, float]:
        """A snapshot of the input counters (see the class docstring)."""
        return dict(self._counts)

    def _produce(self, cur: Cursor, tid: int):
        """fetch + convert of one batch, under the input spans."""
        tracer = get_tracer()
        with tracer.span("input.fetch", cat="input", tid=tid):
            batch = self._fetch(cur)
        if self._convert is not None:
            t0 = time.perf_counter()
            with tracer.span("input.convert", cat="input", tid=tid):
                batch = {k: self._convert(v) for k, v in batch.items()}
            self._counts["convert_s"] += time.perf_counter() - t0
        return batch

    def _hand_out(self, batch):
        """Place the batch on ``sharding`` and count it."""
        if self._sharding is not None:
            with get_tracer().span("input.place", cat="input"):
                batch = jax.tree_util.tree_map(jax.device_put, batch,
                                               self._sharding)
        self._counts["batches"] += 1
        if any(isinstance(x, jax.Array) and not x.is_ready()
               for x in jax.tree_util.tree_leaves(batch)):
            self._counts["not_ready"] += 1
        return batch

    def _worker(self, start: Cursor):
        cur = Cursor(start.task, start.step)
        fetched = 0
        while not self._stop.is_set():
            if self._limit is not None and fetched >= self._limit:
                # don't speculate past the consumer's last step — but DO tell
                # the consumer the stream ended (next() raises StopIteration)
                self._enqueue((None, _EndOfStream()))
                return
            try:
                batch = self._produce(cur, INPUT_TID)
            except BaseException as e:  # surface in next(), don't hang the consumer
                batch = _FetchError(e)
            self._enqueue((Cursor(cur.task, cur.step), batch))
            if isinstance(batch, _FetchError):
                return
            fetched += 1
            cur.step += 1

    def _enqueue(self, item):
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return
            except queue.Full:
                continue

    def start(self):
        if self._thread is None:
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._worker, args=(self.cursor,), daemon=True
            )
            self._thread.start()
        return self

    def next(self):
        # ONE limit across both serving modes: a stopped threaded prefetcher
        # falling back to synchronous fetches must not serve extra batches
        if self._exhausted or (self._limit is not None
                               and self._served >= self._limit):
            raise StopIteration(f"prefetch limit ({self._limit}) reached")
        if self._thread is None:  # synchronous fallback
            batch = self._produce(self.cursor, 0)
            cur = Cursor(self.cursor.task, self.cursor.step)
            self.cursor.step += 1
            self._served += 1
            return cur, self._hand_out(batch)
        t0 = time.perf_counter()
        with get_tracer().span("input.wait", cat="input"):
            cur, batch = self._q.get()
        self._counts["wait_s"] += time.perf_counter() - t0
        if isinstance(batch, _EndOfStream):
            # the producer exited after its last allowed fetch; reclaim the
            # (already finished) thread and report exhaustion, not a hang
            self._exhausted = True
            self.stop()
            raise StopIteration(f"prefetch limit ({self._limit}) reached")
        if isinstance(batch, _FetchError):
            # the producer thread exited; reset so a caller that catches the
            # error and retries hits the synchronous path, not a dead queue
            self.stop()
            raise batch.exc
        self.cursor = Cursor(cur.task, cur.step + 1)
        self._served += 1
        return cur, self._hand_out(batch)

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=2.0)
            self._thread = None

    def reset(self, cursor: Cursor):
        """Reposition (e.g. new task, or checkpoint restore)."""
        self.stop()
        self.cursor = cursor
        self._exhausted = False
        self._served = 0
        return self
