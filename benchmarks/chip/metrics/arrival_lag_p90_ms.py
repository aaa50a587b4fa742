"""Load generator: how late requests were submitted. The 90th percentile,
over the window's requests, of the time the generator called ``submit``
less the time the request fell due, in milliseconds."""
import numpy as np


def read(run):
    lags = [(lg.submitted - lg.due) * 1e3 for lg in run.reqs
            if lg.submitted == lg.submitted]
    return float(np.percentile(lags, 90)) if lags else None
