"""Shared example bootstrap for the multi-device examples: they are dry
runs on a virtual CPU mesh and never touch the chip. Import this FIRST:
the mesh must be set up before jax's first device query (xla_env).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from xla_env import use_host_mesh  # noqa: E402


def ensure_devices(n=8):
    return use_host_mesh(n)
