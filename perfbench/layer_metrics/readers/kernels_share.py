"""Several named kernels' device seconds in the traced window, together,
as a share of the window's busy time (``kernel_share`` for more than
one kernel): 100 x the sum of their ``all_s`` over the trace
reduction's ``busy_s``. Nothing where the run kept no such numbers."""


def read(obs, kernels, trace="kernel_trace"):
    seen = (obs.get(trace) or {}).get("kernels", {})
    busy = (obs.get("trace") or {}).get("busy_s")
    found = [seen[k]["all_s"] for k in kernels if k in seen]
    if not found or not busy:
        return None
    return 100.0 * sum(found) / busy
