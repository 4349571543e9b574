"""Continual step: the share of device busy time in operations under no
scope of the step (relayouts, copies, what the compiler adds), from the
profiler trace of the traced sub-window joined to the step's compiled text.
A step whose text carries no scope reads nothing."""

from chipbench.program_trace import UNSCOPED


def read(run):
    scopes = run["scopes"]
    busy = sum(scopes.values())
    if busy <= 0 or not any(k != UNSCOPED for k in scopes):
        return None
    return 100.0 * scopes.get(UNSCOPED, 0.0) / busy
