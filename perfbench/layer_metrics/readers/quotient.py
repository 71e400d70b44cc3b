"""One observed number over another, times ``scale`` (window seconds
over engine steps: the mean time of a step)."""


def read(obs, numerator, denominator, scale=1.0):
    n, d = obs.get(numerator), obs.get(denominator)
    if n is None or not d:
        return None
    return float(n) / float(d) * scale
