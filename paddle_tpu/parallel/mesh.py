"""Device mesh management (the TPU-native replacement for
platform/nccl_helper.h NCCLContextMap — topology comes from the runtime,
no communicator init).
"""

import numpy as np

import jax
from jax.sharding import Mesh

_default_mesh = [None]


def get_mesh(axis_names=("dp",), shape=None, devices=None):
    """Build (and cache the default) Mesh. With shape=None all devices go on
    the first axis."""
    devices = devices if devices is not None else jax.devices()
    if shape is None:
        shape = (len(devices),) + (1,) * (len(axis_names) - 1)
    arr = np.array(devices).reshape(shape)
    return Mesh(arr, axis_names=axis_names)


def default_mesh():
    if _default_mesh[0] is None:
        _default_mesh[0] = get_mesh()
    return _default_mesh[0]


def set_default_mesh(mesh):
    _default_mesh[0] = mesh


def mesh_axis_sizes(mesh):
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def current_abstract_mesh(fallback):
    """The mesh shardings must bind to INSIDE a (partial-)manual
    shard_map region: the context abstract mesh carries the Manual axis
    types — a concrete-mesh NamedSharding there poisons downstream avals
    with a mismatched all-Auto mesh. Outside any region, `fallback`."""
    cmesh = jax.sharding.get_abstract_mesh()
    return fallback if cmesh.empty else cmesh
