"""Compiled-step cost accounting and MFU (model-FLOPs utilization).

On every compile-cache miss the executor's AOT path and the serving
model's step builders hand their freshly compiled executable here; XLA's
per-executable ``cost_analysis()``/``memory_analysis()`` become gauges:

  exec/step_flops           FLOPs of one compiled step
  exec/step_bytes_accessed  bytes read+written per step (memory traffic)
  exec/peak_hbm_bytes       argument+output+temp buffer footprint

``mfu_pct`` is ``step_flops * steps_per_sec / peak_flops``. The peak
comes from ONE table keyed by ``device_kind`` as JAX reports it, each
entry with its source. A device that is not in the table has no peak:
``peak_flops`` raises and ``mfu_pct`` publishes nothing. There is no
CPU row and no default, so a run that found no chip cannot print a
utilization. bench.py publishes the ``bench/mfu_pct`` gauge and per-leg
receipts from these numbers.
"""

from ..core import device as _device

__all__ = ["publish", "analyze", "peak_flops", "mfu_pct",
           "DEVICE_PEAK_FLOPS"]

# dense bf16 peak FLOP/s of ONE chip, keyed by jax's device_kind
DEVICE_PEAK_FLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    # (393 TOP/s int8, 16 GB HBM at 819 GB/s)
    "TPU v5 lite": 197e12,
}


def peak_flops(device_kind=None):
    """The table entry for `device_kind` (default: the current device,
    core.device.identity()). A device not in the table raises."""
    if device_kind is None:
        device_kind = _device.identity().kind
    try:
        return DEVICE_PEAK_FLOPS[device_kind]
    except KeyError:
        raise KeyError(
            "no peak FLOP/s on record for device_kind %r (known: %s); "
            "add it to observability/cost.py with its source"
            % (device_kind, sorted(DEVICE_PEAK_FLOPS))) from None


def analyze(compiled):
    """{step_flops, step_bytes_accessed, peak_hbm_bytes} for one
    compiled executable — only the keys the backend actually reports."""
    out = {}
    ca = compiled.cost_analysis()
    if ca:
        if "flops" in ca:
            out["step_flops"] = float(ca["flops"])
        if "bytes accessed" in ca:
            out["step_bytes_accessed"] = float(ca["bytes accessed"])
    ma = compiled.memory_analysis()
    if ma is not None:
        out["peak_hbm_bytes"] = float(
            ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    return out


def publish(compiled):
    """Publish the exec/* gauges for `compiled` into the process-wide
    registry (last compile wins — on a steady-state engine that is THE
    step) and return the analysis dict. Callers gate on
    metrics.enabled(); a backend reporting nothing publishes nothing."""
    vals = analyze(compiled)
    if not vals:
        return vals
    from . import metrics as _metrics

    reg = _metrics.registry()
    if "step_flops" in vals:
        reg.gauge("exec/step_flops").set(vals["step_flops"])
    if "step_bytes_accessed" in vals:
        reg.gauge("exec/step_bytes_accessed").set(
            vals["step_bytes_accessed"])
    if "peak_hbm_bytes" in vals:
        reg.gauge("exec/peak_hbm_bytes").set(vals["peak_hbm_bytes"])
    return vals


def mfu_pct(step_flops, steps_per_sec, device_kind=None):
    """Model-FLOPs utilization percent against the device's peak, or
    None when there is nothing to divide or the device has no peak on
    record (an unknown device publishes no MFU)."""
    if not step_flops or not steps_per_sec:
        return None
    try:
        peak = peak_flops(device_kind)
    except KeyError:
        return None
    return 100.0 * float(step_flops) * float(steps_per_sec) / peak
