"""Rehearsal sample and exchange: device ms a step under the step's
``buffer_sample``, ``exchange`` and ``augment`` scopes, from the profiler
trace of the traced sub-window joined to the step's compiled text."""

from chipbench.program_trace import SAMPLE_SCOPES


def read(run):
    got = [run["scopes"][k] for k in SAMPLE_SCOPES if k in run["scopes"]]
    return sum(got) if got else None
