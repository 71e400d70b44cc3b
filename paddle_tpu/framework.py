"""Program IR: Program / Block / Operator / Variable / Parameter.

Parity target: python/paddle/fluid/framework.py (Program :2704, Block :1369,
Operator :924, Variable :366, Parameter :3476) and the C++ descriptor layer
(paddle/fluid/framework/framework.proto:43-188).

TPU-native design: unlike Fluid, the program is NOT interpreted op-by-op over
mutable scopes. It is a lightweight, serializable graph that the executor
lowers to a single pure JAX function (feeds, params, step) -> (fetches,
updated state), jit-compiled by XLA once per (program fingerprint, feed
signature). Ops carry named input/output slots and attrs exactly like
Fluid's OpDesc so the frontend layers DSL and program transforms
(append_backward, transpilers, pruning) keep the same shape, but kernels are
JAX-lowered functions (paddle_tpu/ops/registry.py) and gradients come from
per-op `jax.vjp` at lowering time rather than hand-written grad kernels.
"""

import contextlib
import json

import numpy as np

from . import unique_name
from .core.place import CPUPlace, TPUPlace, CUDAPlace, CUDAPinnedPlace  # noqa: F401

__all__ = [
    "Program",
    "Block",
    "Operator",
    "Variable",
    "Parameter",
    "default_startup_program",
    "default_main_program",
    "program_guard",
    "name_scope",
    "grad_var_name",
    "in_dygraph_mode",
]

GRAD_VAR_SUFFIX = "@GRAD"
ZERO_VAR_SUFFIX = "@ZERO"


def grad_var_name(var_name):
    return var_name + GRAD_VAR_SUFFIX


# ---------------------------------------------------------------------------
# dtype handling: we use numpy dtypes as the canonical representation, with
# string aliases accepted everywhere ("float32", "bf16", ...).
# ---------------------------------------------------------------------------

_DTYPE_ALIASES = {
    "float16": "float16",
    "fp16": "float16",
    "bfloat16": "bfloat16",
    "bf16": "bfloat16",
    "float32": "float32",
    "fp32": "float32",
    "float64": "float64",
    "fp64": "float64",
    "int8": "int8",
    "uint8": "uint8",
    "int16": "int16",
    "int32": "int32",
    "int64": "int64",
    "bool": "bool",
}


def convert_dtype(dtype):
    """Normalize a user-provided dtype to a canonical string name."""
    if dtype is None:
        return "float32"
    if isinstance(dtype, str):
        if dtype in _DTYPE_ALIASES:
            return _DTYPE_ALIASES[dtype]
        return np.dtype(dtype).name
    try:
        import jax.numpy as jnp

        if dtype == jnp.bfloat16:
            return "bfloat16"
    except Exception:
        pass
    return np.dtype(dtype).name


def dtype_to_np(dtype):
    name = convert_dtype(dtype)
    if name == "bfloat16":
        import jax.numpy as jnp

        return jnp.bfloat16
    return np.dtype(name)


# ---------------------------------------------------------------------------
# Variable
# ---------------------------------------------------------------------------


class Variable:
    """A named symbolic value in a Block (parity: framework.py:366 / VarDesc
    framework.proto:166).

    `shape` may contain -1 for dimensions unknown at graph-build time (batch
    dim); the concrete shape is bound at executor lowering from the feed.
    `lod_level` is kept for API parity; ragged sequences are represented as
    padded dense tensors plus explicit length tensors (SURVEY §5.7 mapping).
    """

    def __init__(
        self,
        block,
        name=None,
        shape=None,
        dtype="float32",
        lod_level=0,
        persistable=False,
        stop_gradient=False,
        is_data=False,
        need_check_feed=False,
        type=None,
        initializer=None,
        **kwargs,
    ):
        self.block = block
        if name is None:
            name = unique_name.generate("_generated_var")
        self.name = name
        self.shape = tuple(shape) if shape is not None else None
        self.dtype = convert_dtype(dtype)
        self.lod_level = lod_level
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.is_data = is_data
        self.type = type or "LOD_TENSOR"
        # op that produced this var (filled in by append_op)
        self.op = None
        self.initializer = initializer

    # -- numpy-ish sugar on graph vars -------------------------------------
    def astype(self, dtype):
        from .layers import tensor as tensor_layers

        return tensor_layers.cast(self, dtype)

    def _binary(self, other, op, reverse=False):
        from .layers import nn as nn_layers

        fn = getattr(nn_layers, op)
        if reverse:
            return fn(_to_var(other, self.block, self.dtype), self)
        return fn(self, _to_var(other, self.block, self.dtype))

    def __add__(self, other):
        return self._binary(other, "elementwise_add")

    __radd__ = __add__

    def __sub__(self, other):
        return self._binary(other, "elementwise_sub")

    def __rsub__(self, other):
        return self._binary(other, "elementwise_sub", reverse=True)

    def __mul__(self, other):
        return self._binary(other, "elementwise_mul")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._binary(other, "elementwise_div")

    def __neg__(self):
        from .layers import nn as nn_layers

        return nn_layers.scale(self, scale=-1.0)

    def __repr__(self):
        return "Variable(name=%s, shape=%s, dtype=%s%s)" % (
            self.name,
            self.shape,
            self.dtype,
            ", persistable" if self.persistable else "",
        )

    __str__ = __repr__

    def to_desc(self):
        return {
            "name": self.name,
            "shape": list(self.shape) if self.shape is not None else None,
            "dtype": self.dtype,
            "lod_level": self.lod_level,
            "persistable": self.persistable,
            "stop_gradient": self.stop_gradient,
            "is_data": self.is_data,
            "type": self.type,
            "is_parameter": isinstance(self, Parameter),
            "trainable": getattr(self, "trainable", False),
        }


def _to_var(value, block, dtype):
    """Promote a python scalar / numpy array to a graph Variable."""
    if isinstance(value, Variable):
        return value
    from .layers import tensor as tensor_layers

    if np.isscalar(value):
        return tensor_layers.fill_constant(
            shape=[1], dtype=dtype, value=float(value)
        )
    raise TypeError("cannot promote %r to Variable" % (value,))


class Parameter(Variable):
    """A persistable, trainable Variable (parity: framework.py:3476)."""

    def __init__(self, block, shape, dtype, **kwargs):
        kwargs.setdefault("persistable", True)
        self.trainable = kwargs.pop("trainable", True)
        self.optimize_attr = kwargs.pop("optimize_attr", {"learning_rate": 1.0})
        self.regularizer = kwargs.pop("regularizer", None)
        self.gradient_clip_attr = kwargs.pop("gradient_clip_attr", None)
        self.do_model_average = kwargs.pop("do_model_average", None)
        self.is_distributed = kwargs.pop("is_distributed", False)
        self.shard_spec = kwargs.pop("shard_spec", None)
        super().__init__(block, shape=shape, dtype=dtype, **kwargs)


# ---------------------------------------------------------------------------
# Operator
# ---------------------------------------------------------------------------


class Operator:
    """One op in a Block (parity: framework.py:924 / OpDesc framework.proto:43).

    inputs/outputs: dict slot-name -> list of Variable. attrs: plain dict of
    JSON-serializable values (sub-Block references are stored as block ids).
    """

    def __init__(self, block, type, inputs=None, outputs=None, attrs=None):
        self.block = block
        self.type = type
        self.inputs = {k: _as_var_list(v) for k, v in (inputs or {}).items()}
        self.outputs = {k: _as_var_list(v) for k, v in (outputs or {}).items()}
        self.attrs = dict(attrs or {})

    def input_names(self, slot=None):
        if slot is not None:
            return [v.name for v in self.inputs.get(slot, [])]
        return [v.name for vs in self.inputs.values() for v in vs]

    def output_names(self, slot=None):
        if slot is not None:
            return [v.name for v in self.outputs.get(slot, [])]
        return [v.name for vs in self.outputs.values() for v in vs]

    def input(self, slot):
        return self.input_names(slot)

    def output(self, slot):
        return self.output_names(slot)

    @property
    def input_arg_names(self):
        return self.input_names()

    @property
    def output_arg_names(self):
        return self.output_names()

    def has_attr(self, name):
        return name in self.attrs

    def attr(self, name):
        return self.attrs[name]

    def _set_attr(self, name, val):
        self.attrs[name] = val
        self.block.program._bump_version()

    def __repr__(self):
        return "Operator(type=%s, inputs=%s, outputs=%s)" % (
            self.type,
            {k: [v.name for v in vs] for k, vs in self.inputs.items()},
            {k: [v.name for v in vs] for k, vs in self.outputs.items()},
        )

    def to_desc(self):
        def _ser_attr(v):
            if isinstance(v, Block):
                return {"__block__": v.idx}
            if isinstance(v, Operator):
                # grad ops reference their forward op (__fwd_op__); persist
                # as (block idx, op index) and re-link on load (serde)
                return {"__op_index__": v.block.ops.index(v),
                        "__op_block__": v.block.idx}
            if isinstance(v, np.ndarray):
                return {"__ndarray__": v.tolist(), "dtype": str(v.dtype)}
            return v

        return {
            "type": self.type,
            "inputs": {k: [v.name for v in vs] for k, vs in self.inputs.items()},
            "outputs": {k: [v.name for v in vs] for k, vs in self.outputs.items()},
            "attrs": {k: _ser_attr(v) for k, v in self.attrs.items()},
        }


def _as_var_list(v):
    if v is None:
        return []
    if isinstance(v, (list, tuple)):
        return list(v)
    return [v]


# ---------------------------------------------------------------------------
# Block
# ---------------------------------------------------------------------------


class Block:
    """An ordered op list + var map, possibly nested (parity: framework.py:1369
    / BlockDesc framework.proto:173 with parent_idx)."""

    def __init__(self, program, idx, parent_idx=-1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars = {}
        self.ops = []

    @property
    def parent_block(self):
        if self.parent_idx < 0:
            return None
        return self.program.blocks[self.parent_idx]

    def var(self, name):
        v = self._find_var_recursive(name)
        if v is None:
            raise ValueError("Variable %r not found in block %d" % (name, self.idx))
        return v

    def has_var(self, name):
        return self._find_var_recursive(name) is not None

    def _find_var_recursive(self, name):
        blk = self
        while blk is not None:
            if name in blk.vars:
                return blk.vars[name]
            blk = blk.parent_block
        return None

    def create_var(self, *args, **kwargs):
        v = Variable(self, *args, **kwargs)
        self.vars[v.name] = v
        self.program._bump_version()
        return v

    def create_parameter(self, *args, **kwargs):
        p = Parameter(self, *args, **kwargs)
        # parameters always live in the outermost (global) block
        gb = self.program.global_block()
        gb.vars[p.name] = p
        p.block = gb
        self.program._bump_version()
        return p

    def all_parameters(self):
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    def append_op(self, type, inputs=None, outputs=None, attrs=None):
        op = Operator(self, type, inputs, outputs, attrs)
        if _current_pipeline_stage[0] is not None \
                and "__pipeline_stage__" not in op.attrs:
            op.attrs["__pipeline_stage__"] = _current_pipeline_stage[0]
        self.ops.append(op)
        for vs in op.outputs.values():
            for v in vs:
                v.op = op
        self.program._bump_version()
        return op

    def prepend_op(self, type, inputs=None, outputs=None, attrs=None):
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.insert(0, op)
        for vs in op.outputs.values():
            for v in vs:
                v.op = op
        self.program._bump_version()
        return op

    def _insert_op(self, index, type, inputs=None, outputs=None, attrs=None):
        op = Operator(self, type, inputs, outputs, attrs)
        self.ops.insert(index, op)
        self.program._bump_version()
        return op

    def _remove_op(self, index):
        del self.ops[index]
        self.program._bump_version()

    def to_desc(self):
        return {
            "idx": self.idx,
            "parent_idx": self.parent_idx,
            "vars": [v.to_desc() for v in self.vars.values()],
            "ops": [op.to_desc() for op in self.ops],
        }


# ---------------------------------------------------------------------------
# Program
# ---------------------------------------------------------------------------


class Program:
    """A whole computation: list of Blocks, block 0 is global (parity:
    framework.py:2704 / ProgramDesc framework.proto:182)."""

    def __init__(self):
        self.blocks = [Block(self, 0)]
        self.current_block_idx = 0
        # fingerprint for the executor's compile cache; bumped on any mutation
        self._version = 0
        self._seed = 0
        self.random_seed = 0
        # populated by append_backward: param name -> grad var name
        self.param_grad_map = {}
        self._op_role = "forward"
        self._appending_grad_times = 0

    # -- structure ---------------------------------------------------------
    def global_block(self):
        return self.blocks[0]

    def current_block(self):
        return self.blocks[self.current_block_idx]

    def block(self, idx):
        return self.blocks[idx]

    @property
    def num_blocks(self):
        return len(self.blocks)

    def _create_block(self, parent_idx=None):
        parent_idx = (
            self.current_block_idx if parent_idx is None else parent_idx
        )
        b = Block(self, len(self.blocks), parent_idx)
        self.blocks.append(b)
        self.current_block_idx = b.idx
        self._bump_version()
        return b

    def _rollback(self):
        self.current_block_idx = self.current_block().parent_idx

    def _bump_version(self):
        self._version += 1

    @property
    def version(self):
        return self._version

    # -- queries -----------------------------------------------------------
    def all_parameters(self):
        return self.global_block().all_parameters()

    def list_vars(self):
        for blk in self.blocks:
            for v in blk.vars.values():
                yield v

    # -- cloning / serialization -------------------------------------------
    @staticmethod
    def _is_train_only_op(op):
        """Backward + optimizer ops, pruned by clone(for_test=True) the way
        the reference prunes OpRole.Backward/Optimize ops."""
        if "__fwd_op__" in op.attrs or op.type.endswith("_grad"):
            return True
        if op.type in _OPTIMIZER_OP_TYPES or op.type in _AMP_STATE_OP_TYPES:
            return True
        if op.attrs.get("__amp_state__"):
            # AMP bookkeeping built from generic ops (master-weight
            # re-derive cast, overflow-step counter) — train-only
            return True
        # the loss-grad seed: fill op writing only @GRAD outputs
        outs = op.output_names()
        return bool(outs) and all(n.endswith("@GRAD") for n in outs)

    def clone(self, for_test=False):
        """Deep-copy the program. With for_test=True, switch train-only op
        behavior (dropout, batch_norm) to inference mode and prune
        backward/optimizer ops (parity: framework.py Program.clone)."""
        p = Program()
        p.random_seed = self.random_seed
        p.blocks = []
        for blk in self.blocks:
            nb = Block(p, blk.idx, blk.parent_idx)
            p.blocks.append(nb)
        for blk, nb in zip(self.blocks, p.blocks):
            for name, v in blk.vars.items():
                if isinstance(v, Parameter):
                    nv = Parameter(
                        nb,
                        shape=v.shape,
                        dtype=v.dtype,
                        name=v.name,
                        trainable=v.trainable,
                        lod_level=v.lod_level,
                        stop_gradient=v.stop_gradient,
                        shard_spec=v.shard_spec,
                        is_distributed=v.is_distributed,
                    )
                    nv.initializer = v.initializer
                    nv.regularizer = v.regularizer
                    nv.optimize_attr = dict(v.optimize_attr)
                    nv.gradient_clip_attr = v.gradient_clip_attr
                    nv.do_model_average = v.do_model_average
                else:
                    nv = Variable(
                        nb,
                        name=v.name,
                        shape=v.shape,
                        dtype=v.dtype,
                        lod_level=v.lod_level,
                        persistable=v.persistable,
                        stop_gradient=v.stop_gradient,
                        is_data=v.is_data,
                        type=v.type,
                    )
                    nv.initializer = v.initializer
                if getattr(v, "is_tensor_array", False):
                    # ad-hoc flag from layers.create_array: the lowering
                    # treats a first mention with no producer as the
                    # empty array, keyed off this attribute
                    nv.is_tensor_array = True
                nb.vars[name] = nv
        op_map = {}  # original Operator -> cloned Operator (by identity)
        for blk, nb in zip(self.blocks, p.blocks):
            for op in blk.ops:
                if for_test and self._is_train_only_op(op):
                    continue
                attrs = dict(op.attrs)
                if for_test and "is_test" in _TEST_MODE_OPS.get(op.type, ()):
                    attrs["is_test"] = True
                # remap sub-block attr references
                for k, v in attrs.items():
                    if isinstance(v, Block):
                        attrs[k] = p.blocks[v.idx]
                op_map[id(op)] = nb.append_op(
                    type=op.type,
                    inputs={
                        k: [nb.var(v.name) for v in vs]
                        for k, vs in op.inputs.items()
                    },
                    outputs={
                        k: [nb.var(v.name) for v in vs]
                        for k, vs in op.outputs.items()
                    },
                    attrs=attrs,
                )
        # grad ops reference their forward op by OBJECT (__fwd_op__);
        # rewire those references onto the cloned ops so the clone's
        # execution snapshots and serialized desc are self-contained
        # (a clone pointing into the source program breaks both)
        for nb in p.blocks:
            for op in nb.ops:
                for k, v in op.attrs.items():
                    if isinstance(v, Operator) and id(v) in op_map:
                        op.attrs[k] = op_map[id(v)]
        p.param_grad_map = dict(self.param_grad_map)
        if getattr(self, "_amp_config", None) is not None:
            # AMP decoration travels with the program: the compile-time
            # clone (and a user's clone) keeps the dtype-rewrite policy
            p._amp_config = self._amp_config
        if getattr(self, "_quant_config", None) is not None:
            # quantization decoration travels the same way (quant.py)
            p._quant_config = self._quant_config
        if getattr(self, "_embed_config", None) is not None:
            # embedding-prefetch decoration too: the compile clone is
            # what the embed_prefetch_rewrite pass sees
            # (parallel/embedding_pipeline.py)
            p._embed_config = self._embed_config
        p.current_block_idx = 0
        return p

    def to_json(self):
        return json.dumps(
            {
                "version": 1,
                "random_seed": self.random_seed,
                "blocks": [b.to_desc() for b in self.blocks],
            }
        )

    @staticmethod
    def from_json(s):
        from .core import serde

        return serde.program_from_json(s)

    def to_string(self, throw_on_error, with_details=False):
        """Debug string (parity: framework.py:2901 Program.to_string).
        With with_details, every var's persistable/trainable/shape is
        listed; throw_on_error raises on vars missing shape/dtype the way
        the reference raises on uninitialized protos."""
        lines = []
        for blk in self.blocks:
            lines.append("-- block %d (parent %d) --"
                         % (blk.idx, blk.parent_idx))
            for v in blk.vars.values():
                if throw_on_error and (v.shape is None or v.dtype is None):
                    raise ValueError(
                        "var %r has no shape/dtype set" % v.name)
                if with_details:
                    lines.append(
                        "  var %s: shape=%r dtype=%s persistable=%r%s"
                        % (v.name, v.shape, v.dtype, v.persistable,
                           " trainable=%r" % v.trainable
                           if isinstance(v, Parameter) else ""))
                else:
                    lines.append("  var %s" % v.name)
            for op in blk.ops:
                lines.append("  " + repr(op))
        return "\n".join(lines)

    @staticmethod
    def parse_from_string(binary_str):
        """Rebuild a Program from its serialized desc (parity:
        framework.py:3211 Program.parse_from_string over protobuf; the
        TPU-native wire format is the versioned JSON desc produced by
        `Program.to_json` / `io.save_inference_model`)."""
        if isinstance(binary_str, bytes):
            binary_str = binary_str.decode("utf-8")
        return Program.from_json(binary_str)

    def __repr__(self):
        lines = []
        for blk in self.blocks:
            lines.append("-- block %d (parent %d) --" % (blk.idx, blk.parent_idx))
            for op in blk.ops:
                lines.append("  " + repr(op))
        return "\n".join(lines)


# ops whose attrs contain an `is_test` switch flipped by clone(for_test=True)
_TEST_MODE_OPS = {
    "dropout": ("is_test",),
    "batch_norm": ("is_test",),
    "layer_norm": (),
}

# parameter-update op types (mirrors transpiler OPTIMIZE_OP_TYPES; kept here
# to avoid a framework -> transpiler import cycle)
_OPTIMIZER_OP_TYPES = frozenset([
    "sgd", "momentum", "lars_momentum", "adagrad", "adam", "adamax",
    "adadelta", "decayed_adagrad", "rmsprop", "ftrl", "lamb",
    "dgc_momentum", "proximal_gd", "proximal_adagrad",
])

# AMP loss-scaling machinery (contrib/mixed_precision): reads @GRAD vars and
# mutates persistent scaling state — train-only, pruned with the backward ops
_AMP_STATE_OP_TYPES = frozenset([
    "check_finite_and_unscale", "update_loss_scaling",
])


# ---------------------------------------------------------------------------
# default program singletons + guards (parity: framework.py:3569-3728)
# ---------------------------------------------------------------------------

_main_program_ = Program()
_startup_program_ = Program()


def default_startup_program():
    return _startup_program_


def default_main_program():
    return _main_program_


def switch_main_program(program):
    global _main_program_
    prev = _main_program_
    _main_program_ = program
    return prev


def switch_startup_program(program):
    global _startup_program_
    prev = _startup_program_
    _startup_program_ = program
    return prev


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    prev_main = switch_main_program(main_program)
    prev_startup = None
    if startup_program is not None:
        prev_startup = switch_startup_program(startup_program)
    try:
        yield
    finally:
        switch_main_program(prev_main)
        if prev_startup is not None:
            switch_startup_program(prev_startup)


_current_pipeline_stage = [None]


@contextlib.contextmanager
def pipeline_stage(idx):
    """Annotate ops built in this scope with pipeline stage `idx` (used by
    BuildStrategy.pipeline_stages — parallel/pipeline_program.py). The
    TPU-native analogue of the reference's later device_guard/section
    pipeline placement: stages must be non-decreasing in program order.

        with fluid.pipeline_stage(0):
            h = embed_and_first_layers(tokens)
        with fluid.pipeline_stage(1):
            loss = rest_of_model(h, labels)
    """
    prev = _current_pipeline_stage[0]
    _current_pipeline_stage[0] = int(idx)
    try:
        yield
    finally:
        _current_pipeline_stage[0] = prev


_name_scope_stack = []


@contextlib.contextmanager
def name_scope(prefix=None):
    """Structural name scope for debugging/visualization (parity:
    framework.py name_scope)."""
    _name_scope_stack.append(prefix or "")
    try:
        yield
    finally:
        _name_scope_stack.pop()


_dygraph_tracer_ = None


def in_dygraph_mode():
    return _dygraph_tracer_ is not None


def _dygraph_tracer():
    return _dygraph_tracer_
