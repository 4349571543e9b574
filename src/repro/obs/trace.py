"""Host-side trace spans in the Chrome trace-event format (DESIGN.md §11).

A ``Tracer`` collects ``ph='X'`` (complete) spans, ``ph='i'`` instants and
``ph='C'`` counter samples and serialises them as the ``trace.json`` document
Perfetto / ``chrome://tracing`` load directly::

    {"traceEvents": [{"name": ..., "ph": "X", "ts": <µs>, "dur": <µs>,
                      "pid": <rank>, "tid": <track>, ...}, ...],
     "displayTimeUnit": "ms"}

Spans are *host-side*: they time host work — the input thread
(``input.fetch``/``input.convert``/``input.wait``/``input.place``), eval,
checkpoint save/restore, reshard and autoscale decisions. Every span is also a
``jax.profiler.TraceAnnotation``, so a profiler trace shows it on its host
plane beside the device's ops, on one clock. Inside one fused jitted program
host timestamps are meaningless: the step's stages carry named scopes instead
(``repro.obs.scopes``), which a profiler trace sums by stage.

Per-rank tracks: ``pid`` defaults to the ``REPRO_MP_PID`` rank of
``runtime/multiproc.py`` (0 single-process), so an N-process mesh writing one
trace file per rank merges into N labelled process tracks in Perfetto. ``tid``
separates host threads within a rank (0 = main loop, 1 = the checkpoint
writer's async thread, 2 = the ``Prefetcher``'s input thread).

The module-global tracer starts *disabled* (every call is a cheap no-op);
``repro.obs.configure`` swaps in a live one.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, List, Optional

import jax

_REQUIRED_PHASE_FIELDS = {"name", "ph", "ts", "pid", "tid"}


class Tracer:
    """Collects Chrome trace events; thread-safe; ``enabled=False`` ⇒ no-ops."""

    def __init__(self, enabled: bool = True, pid: Optional[int] = None,
                 process_name: Optional[str] = None):
        self.enabled = enabled
        if pid is None:
            pid = int(os.environ.get("REPRO_MP_PID", "0") or 0)
        self.pid = pid
        self._events: List[Dict[str, Any]] = []
        self._lock = threading.Lock()
        if enabled:
            name = process_name or f"rank {self.pid}"
            self._append({"name": "process_name", "ph": "M", "ts": 0,
                          "pid": self.pid, "tid": 0,
                          "args": {"name": name}})

    @staticmethod
    def _now_us() -> float:
        return time.perf_counter() * 1e6

    def _append(self, ev: Dict[str, Any]):
        with self._lock:
            self._events.append(ev)

    @contextlib.contextmanager
    def span(self, name: str, cat: str = "host", tid: int = 0, **args):
        """Time a ``with`` block as one complete ('X') span. The block is also
        a ``jax.profiler.TraceAnnotation`` of the same name, enabled or not, so
        it lands on the host plane of any profiler trace, on the clock of the
        device's ops (close to free while no profiler session is active)."""
        with jax.profiler.TraceAnnotation(name):
            if not self.enabled:
                yield
                return
            t0 = self._now_us()
            try:
                yield
            finally:
                ev = {"name": name, "cat": cat, "ph": "X", "ts": t0,
                      "dur": self._now_us() - t0, "pid": self.pid, "tid": tid}
                if args:
                    ev["args"] = dict(args)
                self._append(ev)

    def instant(self, name: str, cat: str = "event", tid: int = 0, **args):
        if not self.enabled:
            return
        ev = {"name": name, "cat": cat, "ph": "i", "ts": self._now_us(),
              "s": "p", "pid": self.pid, "tid": tid}
        if args:
            ev["args"] = dict(args)
        self._append(ev)

    def counter(self, name: str, values: Dict[str, float], tid: int = 0):
        if not self.enabled:
            return
        self._append({"name": name, "cat": "counter", "ph": "C",
                      "ts": self._now_us(), "pid": self.pid, "tid": tid,
                      "args": {k: float(v) for k, v in values.items()}})

    # -- inspection / output ------------------------------------------------
    def events(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._events)

    def span_names(self) -> set:
        return {e["name"] for e in self.events() if e.get("ph") == "X"}

    def span_stats(self) -> Dict[str, Dict[str, float]]:
        """Per-span-name {count, total_us, mean_us} summary of 'X' events."""
        out: Dict[str, Dict[str, float]] = {}
        for e in self.events():
            if e.get("ph") != "X":
                continue
            s = out.setdefault(e["name"], {"count": 0, "total_us": 0.0})
            s["count"] += 1
            s["total_us"] += float(e.get("dur", 0.0))
        for s in out.values():
            s["mean_us"] = s["total_us"] / max(s["count"], 1)
        return out

    def to_json(self) -> Dict[str, Any]:
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        doc = self.to_json()
        problems = validate_trace(doc)
        if problems:  # never emit a file Perfetto would reject
            raise ValueError(f"refusing to write invalid trace: {problems}")
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f)
        return path


def validate_trace(doc: Any) -> List[str]:
    """Check a trace document against the Chrome trace-event schema (the JSON
    object form). Returns a list of problems — empty means valid."""
    problems: List[str] = []
    if not isinstance(doc, dict):
        return [f"top level must be an object, got {type(doc).__name__}"]
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        return ["missing/non-list 'traceEvents'"]
    for i, e in enumerate(events):
        if not isinstance(e, dict):
            problems.append(f"event {i}: not an object")
            continue
        missing = _REQUIRED_PHASE_FIELDS - set(e)
        if missing:
            problems.append(f"event {i} ({e.get('name')!r}): missing {sorted(missing)}")
            continue
        if not isinstance(e["name"], str) or not isinstance(e["ph"], str):
            problems.append(f"event {i}: name/ph must be strings")
        if not isinstance(e["ts"], (int, float)):
            problems.append(f"event {i}: ts must be numeric")
        if e["ph"] == "X" and not isinstance(e.get("dur"), (int, float)):
            problems.append(f"event {i} ({e['name']!r}): 'X' span without numeric dur")
        if "args" in e and not isinstance(e["args"], dict):
            problems.append(f"event {i}: args must be an object")
    return problems


# Module-global tracer: disabled by default, swapped by repro.obs.configure.
_TRACER = Tracer(enabled=False)


def get_tracer() -> Tracer:
    return _TRACER


def set_tracer(tracer: Tracer) -> Tracer:
    global _TRACER
    _TRACER = tracer
    return tracer
