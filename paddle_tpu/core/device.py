"""Device identity: the one answer to "which device is this computation
for" (platform, device_kind, count).

Every place that used to compare ``jax.default_backend()`` with a string
asks here instead: the Pallas kernels (compiled on TPU, interpreted
everywhere else), the kernel registry's platform policy, the MFU peak
table, and whatever prints a result. The answer follows the device the
computation is being traced for, which is not always the process
default: ``Executor(CPUPlace())`` on a TPU host traces under
``jax.default_device(cpu)`` and must get the CPU answer.

There is no fallback in here. A process whose backend cannot start
raises from ``jax.devices()``; nothing turns that into "cpu".
"""

import collections
import contextlib
import threading

__all__ = ["DeviceIdentity", "identity", "on_tpu", "pallas_interpret",
           "require_tpu", "compiling_for"]

DeviceIdentity = collections.namedtuple(
    "DeviceIdentity", ["platform", "kind", "count"])

_target = threading.local()


def _of(device, count):
    return DeviceIdentity(device.platform, device.device_kind, count)


def identity():
    """The device the current trace (or eager call) targets: an active
    :func:`compiling_for` target, else ``jax.default_device`` when one
    is set (the Executor's place), else the process default device.
    ``count`` is the number of devices of that platform."""
    target = getattr(_target, "identity", None)
    if target is not None:
        return target
    import jax

    dev = jax.config.jax_default_device  # None, a platform name or a Device
    if dev is None:
        dev = jax.devices()[0]
    elif isinstance(dev, str):
        dev = jax.devices(dev)[0]
    return _of(dev, len(jax.devices(dev.platform)))


def on_tpu():
    return identity().platform == "tpu"


def pallas_interpret():
    """``interpret=`` for every ``pallas_call`` in the tree. Off-TPU the
    kernels run in the Pallas interpreter (the CPU test mesh exercises
    the same kernel bodies); on TPU they compile, and a kernel Mosaic
    refuses raises instead of degrading."""
    return not on_tpu()


def require_tpu(what):
    """Fail unless the current device is a TPU. Measurement entry points
    call this first, so a run that found no chip cannot print a device
    metric."""
    ident = identity()
    if ident.platform != "tpu":
        raise RuntimeError(
            "%s needs a TPU; jax found platform %r (%s, %d device(s))"
            % (what, ident.platform, ident.kind, ident.count))
    return ident


@contextlib.contextmanager
def compiling_for(device, count=1):
    """Trace for a device this process does not hold: a
    ``jax.experimental.topologies`` device (the sandbox pre-flight
    compiles for the v5e without one) or a :class:`DeviceIdentity` for a
    ``lowering_platforms=("tpu",)`` cross-lowering. Thread-local; only
    ahead-of-time lowering uses it, nothing on an execution path."""
    ident = (device if isinstance(device, DeviceIdentity)
             else _of(device, count))
    prev = getattr(_target, "identity", None)
    _target.identity = ident
    try:
        yield ident
    finally:
        _target.identity = prev
