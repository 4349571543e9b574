"""repro.obs — unified telemetry (DESIGN.md §11).

Four pieces, one switch (``RunConfig.obs`` / ``ObsConfig``):

* jit-safe step metrics (``repro.obs.metrics``) — ``obs/*`` f32 scalars merged
  into the step factories' metrics output; zero fingerprint/RNG impact;
* trace spans (``repro.obs.trace``) — Chrome/Perfetto ``trace.json``, each
  span also on the host plane of any profiler trace; the fused step's stages
  carry named scopes instead (``repro.obs.scopes``), which a profiler trace
  sums by stage;
* runtime event log (``repro.obs.events``) — one ``EventBus``, ``events.jsonl``;
* exporters (``repro.obs.exporters``) — Prometheus text endpoint +
  ``MetricsWriter`` folding step metrics into fit() history / BENCH payloads.

The module-global tracer and event bus start disabled (no-ops); ``configure``
swaps in live ones and ``shutdown`` writes the artifacts:

    from repro import obs
    obs.configure("obs_out")          # -> obs_out/{trace.json,events.jsonl}
    ...                               # spans/events accumulate
    obs.shutdown()                    # write trace.json, close events.jsonl
"""
from __future__ import annotations

import os
from typing import Optional

from repro.obs import exporters, metrics
from repro.obs.events import EventBus, get_event_bus, read_events, set_event_bus
from repro.obs.exporters import (
    MetricsRegistry,
    MetricsWriter,
    start_metrics_server,
)
from repro.obs.metrics import estimate_obs_cost, obs_keys, step_metrics
from repro.obs.scopes import STEP_SCOPES, scope_of
from repro.obs.trace import Tracer, get_tracer, set_tracer, validate_trace

_STATE = {"dir": None}


def configure(directory: Optional[str] = None, enabled: bool = True,
              rank: Optional[int] = None):
    """Install a live tracer + event bus. ``directory`` (optional) is where
    ``shutdown``/``flush`` write ``trace.json`` and where ``events.jsonl``
    streams; rank > 0 gets per-rank filenames so an N-process mesh doesn't
    clobber itself. Returns ``(tracer, bus)``."""
    if rank is None:
        rank = int(os.environ.get("REPRO_MP_PID", "0") or 0)
    events_path = None
    if directory is not None and enabled:
        suffix = "" if rank == 0 else f".rank{rank}"
        events_path = os.path.join(directory, f"events{suffix}.jsonl")
    _STATE["dir"] = directory if enabled else None
    tracer = set_tracer(Tracer(enabled=enabled, pid=rank))
    bus = set_event_bus(EventBus(enabled=enabled, path=events_path, rank=rank))
    return tracer, bus


def flush() -> Optional[str]:
    """Write ``trace.json`` into the configured directory (None if no dir)."""
    directory = _STATE["dir"]
    tracer = get_tracer()
    if directory is None or not tracer.enabled:
        return None
    suffix = "" if tracer.pid == 0 else f".rank{tracer.pid}"
    return tracer.save(os.path.join(directory, f"trace{suffix}.json"))


def shutdown() -> Optional[str]:
    """Flush the trace, close the event sink, and disable both globals."""
    path = flush()
    get_event_bus().close()
    set_tracer(Tracer(enabled=False))
    set_event_bus(EventBus(enabled=False))
    _STATE["dir"] = None
    return path


__all__ = [
    "EventBus", "MetricsRegistry", "MetricsWriter", "STEP_SCOPES", "Tracer", "configure", "estimate_obs_cost", "exporters", "flush",
    "get_event_bus", "get_tracer", "metrics", "obs_keys", "read_events",
    "scope_of", "set_event_bus", "set_tracer", "shutdown",
    "start_metrics_server", "step_metrics", "validate_trace",
]
