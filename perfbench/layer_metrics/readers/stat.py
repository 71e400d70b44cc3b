"""A statistic of raw samples the run observed: ``median``, ``mean``,
``max`` or ``p<q>`` of ``obs[samples]``, times ``scale``."""

import numpy as np


def read(obs, samples, stat, scale=1.0):
    values = obs.get(samples)
    if values is None or not len(values):
        return None
    v = np.asarray(values, np.float64)
    if stat == "median":
        out = np.median(v)
    elif stat == "mean":
        out = v.mean()
    elif stat == "max":
        out = v.max()
    elif stat.startswith("p"):
        out = np.percentile(v, float(stat[1:]))
    else:
        raise ValueError("unknown statistic %r" % (stat,))
    return float(out) * scale
