"""Reduction of a profiler trace by what the program itself marks: the named
scopes of its fused step (``repro.obs.scopes``) and the spans of its input
thread (``data.pipeline.Prefetcher``), beside the reduction of ``trace.py``.

- Device time by scope: each event of the ``XLA Ops`` line is joined to its
  stage by its HLO ``op_name``. A TPU v5e trace's op events carry no such
  name, so the event's instruction (the head of its name) is looked up in
  the compiled step's HLO text, for the events that run inside that module's
  execution (the ``XLA Modules`` line) only. Nested events (a
  ``while`` around its body) are split so that each instant counts once, for
  the innermost event: the scopes and ``unscoped`` add up to the busy time.
- Device time by collective: the same join gives each event its HLO opcode;
  the cross-chip collectives (``COLLECTIVES``, with their ``-start`` and
  ``-done`` forms) are summed under the collective's name. A TPU names an
  instruction after its ``op_name``, not its opcode, so the name alone does
  not tell a collective.
- Idle gaps by program span: the gaps of ``trace.py``'s ``idle_gaps``,
  labelled by the program span that covers most of each, on any host thread.
- Input counters: ``Prefetcher.counters()`` read before and after a stretch.
- ``require_scopes``: a compiled step's text must carry its stages' scopes.
  JAX's persistent cache leaves metadata out of its key, so a step compiled
  without scopes (by an older program) is served with its old text, and
  every op would read ``unscoped``; ``runner.enable_cache`` puts metadata in
  the key, and ``trace_program.py`` refuses such a text.

The reductions do not raise for want of what they read: a trace without
scopes or input spans gives ``None`` or empty readings.
"""
from __future__ import annotations

import bisect
import glob
import heapq
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional

from chipbench import trace as trace_mod

PROGRAM_SPANS = ("input.fetch", "input.convert", "input.wait", "input.place")
SAMPLE_SCOPES = ("buffer_sample", "exchange", "augment")
UNSCOPED = "unscoped"
COLLECTIVES = ("all-reduce", "all-to-all", "all-gather", "reduce-scatter",
               "collective-permute")

_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name="([^"]*)"')
# the opcode is the first word after the shape that opens an operand list; a
# shape's layout ``{0:T(8,128)}`` holds parentheses, but none after a space
_OPCODE = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s.*?\s([a-z][\w\-]*)\(')
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")


def hlo_op_names(hlo_text: Optional[str]):
    """(module name, {instruction: op_name}) of a compiled module's text."""
    if not hlo_text:
        return None, {}
    names, module = {}, None
    for line in hlo_text.splitlines():
        if module is None:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        m = _INSTR.match(line)
        if m:
            names[m.group(1)] = m.group(2)
    return module, names


def hlo_collectives(hlo_text: Optional[str]) -> Dict[str, str]:
    """{instruction: collective} of a compiled module's text, for the
    instructions whose opcode is one of ``COLLECTIVES`` or its ``-start`` or
    ``-done`` form."""
    out = {}
    for line in (hlo_text or "").splitlines():
        m = _OPCODE.match(line)
        if m:
            op = re.sub(r"-(start|done)$", "", m.group(2))
            if op in COLLECTIVES:
                out[m.group(1)] = op
    return out


def step_hlo(ent, batch, key) -> str:
    """The compiled text of the entry's step (its program as it runs): a
    read of the compile cache where the step has run in this checkout."""
    if hasattr(ent, "step_fn"):
        lowered = ent.step_fn.lower(ent.carry, batch, key)
    else:
        lowered = ent.built.fn.lower(*ent.state, batch, key)
    return lowered.compile().as_text()


def _instruction(name: str) -> str:
    """``%fusion.15 = bf16[...] fusion(...)`` -> ``fusion.15``."""
    return name.lstrip("%").split(" ", 1)[0].split("=", 1)[0]


def _module_name(name: str) -> str:
    """``jit_step(123)`` -> ``jit_step``."""
    return name.split("(", 1)[0]


def newest_xplane(path: str) -> str:
    """``path``, or the newest ``*.xplane.pb`` under it if it is a directory."""
    if not os.path.isdir(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def read_xplane(path: str):
    """(device ops {plane: [(start_ns, end_ns, instruction, module, name)]},
    host events [(name, start_ns, end_ns)] of the benchmark's and the
    program's spans) of a trace file, or of the newest ``*.xplane.pb`` under
    a directory. ``module`` is the program whose execution holds the op, or
    None."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(newest_xplane(path))
    names = set(trace_mod.HOST_SPANS) | set(PROGRAM_SPANS)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            modules, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules += [(e.start_ns, e.start_ns + e.duration_ns,
                                 _module_name(e.name)) for e in line.events]
                elif line.name == trace_mod.OPS_LINE:
                    ops += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                            for e in line.events]
            if ops:
                devices[plane.name] = _with_modules(ops, modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.name in names]
    return devices, host


def _with_modules(ops, modules):
    """Each (start, end, name) op as (start, end, instruction, module, name),
    its module the one whose execution event covers its start."""
    modules.sort()
    starts = [m[0] for m in modules]
    out = []
    for a, b, name in ops:
        i = bisect.bisect_right(starts, a) - 1
        module = modules[i][2] if i >= 0 and modules[i][1] >= a else None
        out.append((a, b, _instruction(name), module, name[:trace_mod.NAME_CHARS]))
    return out


def op_scopes(ops, hlo_text: Optional[str] = None) -> List[str]:
    """The stage of each op (``UNSCOPED`` where none is found)."""
    from repro.obs.scopes import scope_of

    module, names = hlo_op_names(hlo_text)
    out = []
    for _, _, instr, op_module, _ in ops:
        op_name = names.get(instr) if op_module in (None, module) else None
        s = scope_of(op_name) if op_name else None
        out.append(s or UNSCOPED)
    return out


def op_collectives(ops, hlo_text: Optional[str] = None) -> List[Optional[str]]:
    """The collective each op is (None for the others), by its instruction's
    opcode in the step's text."""
    module, _ = hlo_op_names(hlo_text)
    coll = hlo_collectives(hlo_text)
    return [coll.get(instr) if op_module in (None, module) else None
            for _, _, instr, op_module, _ in ops]


def exclusive_times(intervals, keys, lo: float, hi: float) -> Dict[str, float]:
    """Seconds per key over [lo, hi], each instant given to the innermost
    (latest started) interval open at it: the values add up to the length of
    the union of the intervals."""
    points = []
    for i, (a, b) in enumerate(intervals):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            points.append((a, 1, i))
            points.append((b, 0, i))
    points.sort()
    out = defaultdict(float)
    open_, closed, t = [], set(), None
    for x, kind, i in points:
        while open_ and open_[0][1] in closed:
            heapq.heappop(open_)
        if open_ and t is not None and x > t:
            out[keys[open_[0][1]]] += (x - t) / 1e9
        t = x
        if kind == 1:
            heapq.heappush(open_, (-intervals[i][0], i))
        else:
            closed.add(i)
    return dict(out)


def _gaps(ops, lo, hi):
    """The idle gaps of one device in [lo, hi], as ``trace.reduce_events``
    finds them."""
    merged = trace_mod._union([(op[0], op[1]) for op in ops], lo, hi)
    gaps, edge = [], lo
    for a, b in merged + [[hi, hi]]:
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    return gaps


def reduce(devices, host, steps: int, hlo_text: Optional[str] = None, top: int = 10):
    """``trace.py``'s reduction of the same trace, plus ``device_scopes``
    ([[scope, ms per step]], mean over devices), ``scopes_busy_s`` (their
    sum, in seconds), ``device_collectives`` ([[collective, ms per step]],
    mean over devices, each instant given to the innermost op as for the
    scopes), ``idle_gaps_program`` ([[program span, seconds]]),
    ``device_ops_scoped`` (``device_ops`` with each op's scope) and
    ``program_span_s`` (seconds in each program span, all threads). None
    where ``trace.py`` gives None."""
    bench = [h for h in host if h[0] in trace_mod.HOST_SPANS]
    program = [h for h in host if h[0] in PROGRAM_SPANS]
    base = trace_mod.reduce_events(
        {k: [(name, a, b) for a, b, _, _, name in v] for k, v in devices.items()},
        bench, top=top)
    if base is None:
        return None
    lo = min(a for _, a, _ in bench)
    hi = max(b for _, _, b in bench)
    n = len(devices)
    per_scope, per_coll, op_scope = defaultdict(float), defaultdict(float), {}
    for ops in devices.values():
        scopes = op_scopes(ops, hlo_text)
        keys = list(zip(scopes, op_collectives(ops, hlo_text)))
        for (scope, coll), v in exclusive_times([op[:2] for op in ops], keys,
                                                lo, hi).items():
            per_scope[scope] += v / n
            if coll is not None:
                per_coll[coll] += v / n
        op_scope.update((op[4], k) for op, k in zip(ops, scopes))
    first = sorted(devices)[0]
    labelled = []
    for a, b in _gaps(devices[first], lo, hi):
        cover = defaultdict(float)
        for name, s, e in program:
            o = min(b, e) - max(a, s)
            if o > 0:
                cover[name] += o
        labelled.append([max(cover, key=cover.get) if cover else "none",
                         (b - a) / 1e9])
    labelled.sort(key=lambda x: -x[1])
    steps = max(steps, 1)
    base.update(
        device_scopes=sorted(([k, 1000.0 * v / steps] for k, v in per_scope.items()),
                             key=lambda x: -x[1]),
        scopes_busy_s=sum(per_scope.values()),
        device_collectives=sorted(([k, 1000.0 * v / steps] for k, v in per_coll.items()),
                                  key=lambda x: -x[1]),
        idle_gaps_program=labelled[:top],
        device_ops_scoped=[[name, op_scope[name], v] for name, v in base["device_ops"]],
        program_span_s={name: sum(e - s for nm, s, e in program if nm == name) / 1e9
                        for name in PROGRAM_SPANS})
    return base


def scope_ms(red, names) -> Optional[float]:
    """ms per step of the ops under any of ``names``; None where none ran."""
    if red is None:
        return None
    got = [v for k, v in red["device_scopes"] if k in names]
    return sum(got) if got else None


def unscoped_pct(red) -> Optional[float]:
    """Share of device busy time in ops under no program scope."""
    if red is None or red["scopes_busy_s"] <= 0:
        return None
    if not any(k != UNSCOPED for k, _ in red["device_scopes"]):
        return None  # a program without scopes: nothing to tell apart
    un = sum(v for k, v in red["device_scopes"] if k == UNSCOPED)
    return 100.0 * un / sum(v for _, v in red["device_scopes"])


def input_readings(before: Optional[dict], after: Optional[dict], window_s: float):
    """Readings of a stretch of ``window_s`` seconds from two
    ``Prefetcher.counters()`` snapshots; {} without counters.
    ``input_not_ready_pct``: share of the batches handed out with their copy
    in flight; ``input_convert_ms``: mean host ms a batch in ``input.convert``;
    ``input_wait_program_pct``: share of the stretch that ``next()`` spent
    blocked in ``input.wait`` (what ``input_wait_pct`` reads from the
    benchmark's ``fetch`` span, taken inside the program)."""
    if not before or not after:
        return {}
    n = after["batches"] - before["batches"]
    if n <= 0:
        return {}
    out = {"input_not_ready_pct": 100.0 * (after["not_ready"] - before["not_ready"]) / n,
           "input_convert_ms": 1000.0 * (after["convert_s"] - before["convert_s"]) / n}
    if window_s > 0:
        out["input_wait_program_pct"] = (
            100.0 * (after["wait_s"] - before["wait_s"]) / window_s)
    return out


class MissingScopes(RuntimeError):
    """A compiled step's text lacks the scopes its stages open."""


def require_scopes(hlo_text: str, rehearsal: bool):
    """Raise ``MissingScopes`` unless the compiled step's text carries
    ``train``, and ``buffer_update`` and ``buffer_sample`` where the cell
    rehearses."""
    from repro.obs.scopes import scopes_in_hlo

    want = {"train"} | ({"buffer_update", "buffer_sample"} if rehearsal else set())
    missing = sorted(want - scopes_in_hlo(hlo_text))
    if missing:
        raise MissingScopes(
            f"the compiled step carries no {', '.join(missing)} scope: its text "
            "comes from a program without them, or from a compile cache entry "
            "made before them (JAX leaves metadata out of the cache key unless "
            "jax_compilation_cache_include_metadata_in_key is set)")
