"""Cluster scheduler: how long a migrated request waited for the decode
pool. The mean, over the window's requests whose state was moved, of the
request's ``migrate_start_time`` (its transfer begun, on the cluster
clock) less its ``first_token_time``, in ms. A program without the stamp
gives nothing."""


def read(run):
    waits = []
    for lg in run.reqs:
        req = getattr(lg.handle, "req", None)
        start = getattr(req, "migrate_start_time", None)
        if start is not None and req.first_token_time is not None:
            waits.append((start - req.first_token_time) * 1e3)
    return sum(waits) / len(waits) if waits else None
