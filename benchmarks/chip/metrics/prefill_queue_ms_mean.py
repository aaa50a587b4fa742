"""Cluster scheduler: how long a request waited for the prefill pool. The
mean, over the window's requests whose prefill began, of the request's
``prefill_start_time`` (its first chunk dispatched, on the cluster clock)
less its ``arrival``, in ms. A program without the stamp gives nothing."""


def read(run):
    waits = []
    for lg in run.reqs:
        req = getattr(lg.handle, "req", None)
        start = getattr(req, "prefill_start_time", None)
        if start is not None:
            waits.append((start - req.arrival) * 1e3)
    return sum(waits) / len(waits) if waits else None
