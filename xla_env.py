"""The virtual host-device mesh: N CPU devices in one process, for tests
and multi-chip dry runs that must never touch the chip.

Both settings are read ONCE, when jax first initializes its backends, so
`use_host_mesh` must run before the first device query. A chip belongs
to one process at a time and a device query takes it; a dry run that
asked for devices first and switched platform afterwards would hold the
chip for nothing. Shared by tests/conftest.py, __graft_entry__.py,
bench.py and the examples so the flag set cannot drift between them.
"""

import os
import re


def stage_host_mesh_flags(n_devices=8):
    """Ensure XLA_FLAGS requests `n_devices` virtual CPU devices and
    relaxes the CPU collective rendezvous deadline.

    The virtual devices share however few physical cores the box has;
    XLA:CPU's default 20s-warn / 40s-abort rendezvous deadline then fires
    spuriously under scheduling pressure (observed on a 1-core runner with
    the 1F1B pipeline step's collective-dense scan — and still observed,
    rarely, at a 180s bound when background load coincides with the
    longest steps). The 60s warning keeps stuck collectives visible in
    the log; 600s makes a REAL deadlock abort with stacks well before any
    harness-level timeout, without spuriously killing a loaded-but-live
    suite.
    """
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
    if m is None:
        flags = (flags +
                 " --xla_force_host_platform_device_count=%d" % n_devices)
    elif int(m.group(1)) < n_devices:
        flags = (flags[:m.start()] +
                 "--xla_force_host_platform_device_count=%d" % n_devices +
                 flags[m.end():])
    if "collective_call_warn_stuck_timeout" not in flags:
        flags += " --xla_cpu_collective_call_warn_stuck_timeout_seconds=60"
    if "collective_call_terminate_timeout" not in flags:
        flags += " --xla_cpu_collective_call_terminate_timeout_seconds=600"
    os.environ["XLA_FLAGS"] = flags.strip()


def use_host_mesh(n_devices=8):
    """Make this process a virtual `n_devices` CPU mesh: stage XLA_FLAGS,
    pin jax to the CPU platform, then check that is what came up. Raises
    when jax had already initialized another backend (a device query ran
    first), because then the pin no longer takes effect."""
    stage_host_mesh_flags(n_devices)
    import jax

    jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    if devs[0].platform != "cpu" or len(devs) < n_devices:
        raise RuntimeError(
            "use_host_mesh(%d) must run before the first jax device "
            "query; this process already holds %r" % (n_devices, devs))
    return jax
