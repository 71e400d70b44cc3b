"""paddle_tpu — a TPU-native deep-learning framework with the capabilities
of PaddlePaddle Fluid (reference @ /root/reference, see SURVEY.md).

The public surface mirrors `paddle.fluid` (API.spec parity, SURVEY Appendix
B): Program/Executor/layers/optimizer/io/..., but the implementation is
JAX/XLA-first — programs lower to single jitted XLA computations, parallelism
is jax.sharding over device meshes, kernels are JAX/Pallas.

Typical use (identical shape to fluid):

    import paddle_tpu as fluid
    x = fluid.layers.data(name="x", shape=[13])
    y = fluid.layers.data(name="y", shape=[1])
    pred = fluid.layers.fc(input=x, size=1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(0.01).minimize(loss)
    exe = fluid.Executor(fluid.TPUPlace(0))
    exe.run(fluid.default_startup_program())
    exe.run(feed={...}, fetch_list=[loss])
"""

from . import ops  # registers the op corpus
from . import framework
from .framework import (
    Program,
    Variable,
    Parameter,
    default_main_program,
    default_startup_program,
    program_guard,
    name_scope,
    pipeline_stage,
    in_dygraph_mode,
    CPUPlace,
    TPUPlace,
    CUDAPlace,
    CUDAPinnedPlace,
)
from .core.scope import Scope, global_scope, scope_guard
from .executor import Executor, as_numpy  # noqa: F401
from . import async_engine
from .compiler import CompiledProgram, ExecutionStrategy, BuildStrategy
from .backward import append_backward, gradients
from .param_attr import ParamAttr, WeightNormParamAttr
from . import layers
from . import initializer
from . import optimizer
from . import regularizer
from . import clip
from . import unique_name
from . import io
from .io import save_inference_model, load_inference_model  # noqa: F401
from . import metrics
from . import nets
from . import observability
from . import profiler
from . import reader
from . import dataset
from . import recordio_writer
from .recordio_writer import convert_reader_to_recordio_file  # noqa: F401
from .dataset_api import DatasetFactory, InMemoryDataset, QueueDataset  # noqa
from . import dygraph
from .dygraph.base import enable_dygraph, disable_dygraph  # noqa: F401
from . import parallel
from .parallel import ParallelExecutor  # noqa: F401
from .initializer import Constant, Uniform, Normal, Xavier, MSRA  # noqa
from .data_feeder import DataFeeder, DataFeedDesc  # noqa: F401
from .flags import set_flags, get_flags  # noqa: F401
from .core.tensor import LoDTensor, LoDTensorArray  # noqa: F401
from .core.tensor import create_lod_tensor, create_random_int_lodtensor  # noqa: F401,E501
from . import ir  # noqa: F401
from . import amp  # noqa: F401  (registers the amp_rewrite pass)
from . import quant  # noqa: F401  (registers the quant_rewrite pass)
from . import analysis  # noqa: F401  (Program IR verifier + infer_meta)
from . import flags  # noqa: F401  (the PTPU_* env-flag registry)
from . import communicator  # noqa: F401
from . import debugger  # noqa: F401
from . import install_check  # noqa: F401
from . import checkpoint  # noqa: F401
from . import resilience  # noqa: F401
from .resilience import ResilientTrainer  # noqa: F401
from . import data_plane  # noqa: F401  (fault-tolerant streaming ingestion)
from .data_plane import DatasetCursor  # noqa: F401
from .reader import batch  # noqa: F401  (top-level paddle.batch parity)


def cuda_places(device_ids=None):
    """Alias: accelerator places (parity: framework.py cuda_places) —
    one TPUPlace per local chip. Raises on a process with no TPU."""
    from .core.place import local_chips

    if device_ids is None:
        device_ids = range(len(local_chips()))
    return [TPUPlace(i) for i in device_ids]


tpu_places = cuda_places


def cpu_places(device_count=None):
    import os

    n = device_count or int(os.environ.get("CPU_NUM", 1))
    return [CPUPlace() for _ in range(n)]


def cuda_pinned_places(device_count=None):
    return [CUDAPinnedPlace() for _ in range(device_count or 1)]


# real lifetime-analysis implementations live in the transpiler package
from .transpiler import memory_optimize, release_memory  # noqa: F401,E402
from .transpiler import DistributeTranspiler, DistributeTranspilerConfig  # noqa: F401,E402
from . import transpiler  # noqa: F401,E402
from . import contrib  # noqa: F401,E402


__version__ = "0.1.0"
