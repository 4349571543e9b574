"""Trainer host loop: mean host time of one step call, up to its return (the
benchmark's ``dispatch`` span)."""


def read(run):
    d = run["spans"]["dispatch"]
    return 1000.0 * sum(d) / len(d) if d else None
