"""Runners, found by a configuration's ``runner``. Each has
``run(ctx, **hooks)`` and returns the run's end-to-end numbers, its
observations for the per-layer readers, its checks, ``attempted``,
``failed`` and ``memory_peak_bytes``. What they share is here."""

import numpy as np


def check(name, value, limit, **extra):
    """One number compared, beside its limit."""
    return dict(name=name, value=value, limit=limit,
                ok=bool(np.isfinite(value) and value <= limit), **extra)


def counter_value(name):
    """A counter of the program's own registry (they only move while
    its metrics are enabled: the traced run)."""
    from paddle_tpu.observability import metrics

    return metrics.registry().counter(name).value


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest chip, as the runtime reports."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

