"""Trainer input (``data.pipeline.Prefetcher``): the share of the window the
loop spends in ``Prefetcher.next()`` (the benchmark's ``fetch`` span)."""


def read(run):
    if run["window_s"] <= 0 or not run["spans"]["fetch"]:
        return None
    return 100.0 * sum(run["spans"]["fetch"]) / run["window_s"]
