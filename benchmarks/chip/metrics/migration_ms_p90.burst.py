"""Migration: the 90th percentile of the time one decode-state transfer
took (the ``bench.migrate`` span: landing both endpoints' steps, export,
checksum, import), over the transfers begun in the window, in ms."""
import numpy as np


def read(run):
    ms = [(b - a) * 1e3 for a, b, _ in run.migrations if run.in_window(a)]
    return float(np.percentile(ms, 90)) if ms else None
