"""Trainer input (``data.pipeline.Prefetcher``): the share of the batches
handed out with their host-to-device copy still in flight, from the
``Prefetcher``'s counters over the untraced part of the traced run."""


def read(run):
    c = run["counters"]["input"]
    if not c or c["batches"] <= 0:
        return None
    return 100.0 * c["not_ready"] / c["batches"]
