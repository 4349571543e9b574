"""Cross-chip collectives: device ms a step in the step's all-reduce,
all-to-all, all-gather, reduce-scatter and collective-permute operations
(their ``-start`` and ``-done`` forms too), mean over the chips, from the
profiler trace of the traced sub-window joined to the step's compiled text.
A step with no collective reads nothing."""


def read(run):
    got = run["collectives"]
    return sum(got.values()) if got else None
