"""Cluster scheduler: pool changes (prefill to decode or back) that the
policy made inside the window."""


def read(run):
    return float(run.flips)
