"""Rehearsal buffer update: device ms a step under the step's
``buffer_update`` scope (``buffer/api.buffer_update``), from the profiler
trace of the traced sub-window joined to the step's compiled text."""


def read(run):
    return run["scopes"].get("buffer_update")
