"""Trainer input (``data.pipeline.Prefetcher``): mean host ms a batch spends
in its ``convert`` call (the host-to-device copy's issue) on the input
thread, from the ``Prefetcher``'s counters over the untraced part of the
traced run."""


def read(run):
    c = run["counters"]["input"]
    if not c or c["batches"] <= 0:
        return None
    return 1000.0 * c["convert_s"] / c["batches"]
