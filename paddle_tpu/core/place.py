"""Device places (parity: paddle/fluid/platform/place.h, bound at
pybind/pybind.cc:886-963).

TPU-native: a Place names a JAX device set, not a CUDA ordinal. TPUPlace is
the accelerator place; CUDAPlace is accepted as an alias so Fluid-style
scripts run unchanged. `CUDAPinnedPlace` maps to host-committed memory used
for async feeds.
"""


class Place:
    _kind = "base"

    def __eq__(self, other):
        return type(self) is type(other) and getattr(self, "device_id", 0) == getattr(
            other, "device_id", 0
        )

    def __hash__(self):
        return hash((self._kind, getattr(self, "device_id", 0)))

    def __repr__(self):
        if hasattr(self, "device_id"):
            return "%s(%d)" % (type(self).__name__, self.device_id)
        return "%s()" % type(self).__name__


class CPUPlace(Place):
    _kind = "cpu"

    def jax_device(self):
        import jax

        # local (addressable) devices: under a multi-process DCN runtime
        # jax.devices() is global and rank>0 must not target rank 0's
        # device. Raises when jax was started without the cpu platform
        # (JAX_PLATFORMS=tpu): a CPU place never means "whatever is there"
        return jax.local_devices(backend="cpu")[0]


def local_chips():
    """This process's TPU devices; raises on a process that has none."""
    import jax

    try:
        return jax.local_devices(backend="tpu")
    except RuntimeError as e:
        raise RuntimeError(
            "this process has no TPU (jax default platform %r); use "
            "CPUPlace(), or Executor() for the default device"
            % jax.default_backend()) from e


class TPUPlace(Place):
    """The accelerator place. device_id indexes this process's TPU chips."""

    _kind = "tpu"

    def __init__(self, device_id=0):
        self.device_id = device_id

    def jax_device(self):
        """The chip itself. Raises on a process with no TPU and on an
        index past the local chips: a place that named a chip and ran
        somewhere else would hide the device from whoever reads the
        result."""
        chips = local_chips()
        if not 0 <= self.device_id < len(chips):
            raise RuntimeError("%r: this process holds %d TPU chip(s)"
                               % (self, len(chips)))
        return chips[self.device_id]


class CUDAPlace(TPUPlace):
    """Alias of TPUPlace for Fluid source compatibility (place.h CUDAPlace)."""

    _kind = "tpu"


class CUDAPinnedPlace(CPUPlace):
    _kind = "pinned"


def default_place():
    """Accelerator if present, else CPU."""
    import jax

    d = jax.devices()[0]
    if d.platform == "cpu":
        return CPUPlace()
    return TPUPlace(0)
