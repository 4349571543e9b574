"""Device: 1 - (union of device-operation intervals) / traced window, from
the profiler trace of the traced sub-window, averaged over the chips."""


def read(run):
    tr = run.get("trace")
    return None if tr is None else tr["idle_pct"]
