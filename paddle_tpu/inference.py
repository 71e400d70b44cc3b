"""Inference engine (parity: paddle/fluid/inference/ C23 —
`AnalysisConfig` analysis_config.cc, `AnalysisPredictor`
api/analysis_predictor.h:46, `CreatePaddlePredictor`
analysis_predictor.cc:884).

TPU-native: `OptimizeInferenceProgram`'s ~30 IR fuse passes (fc_fuse,
conv_bn_fuse, trt subgraph …) are subsumed by XLA — the loaded program
lowers to one jitted computation and XLA performs the fusions the pass
pipeline hand-coded. What remains, and is implemented here, is the
predictor lifecycle: load → (optionally) AOT-compile for pinned shapes →
zero-overhead repeated `run` with its own scope (PrepareExecutor
analysis_predictor.cc:179 → NaiveExecutor parity: no GC, pre-bound
executable).
"""

import numpy as np

from . import framework, io
from .core.place import CPUPlace, TPUPlace, default_place
from .core.scope import Scope
from .executor import Executor

__all__ = ["AnalysisConfig", "AnalysisPredictor", "create_paddle_predictor",
           "PaddleTensor", "export_serving_model", "load_serving_model",
           "ServingPredictor", "export_generation_model",
           "load_generation_model"]


class PaddleTensor:
    """Named input/output tensor (inference/api paddle_api.h PaddleTensor)."""

    def __init__(self, data=None, name=None, lod=None):
        self.name = name
        self.data = np.asarray(data) if data is not None else None
        self.lod = lod or []
        self.shape = tuple(self.data.shape) if data is not None else None

    def as_ndarray(self):
        return self.data


class AnalysisConfig:
    """Predictor configuration (analysis_config.cc). GPU/MKLDNN/TensorRT
    toggles are accepted for API parity; device selection maps to
    CPUPlace/TPUPlace and subgraph engines are subsumed by XLA."""

    def __init__(self, model_dir=None, params_file=None):
        self.model_dir = model_dir
        self.prog_file = None
        self.params_file = params_file
        # None = the default device (the chip when there is one);
        # enable_use_tpu() insists on the chip, disable_gpu() on the host
        self._use_accelerator = None
        self._ir_optim = True
        self._aot_shapes = None
        self._quant_mode = None
        self._quant_table = None
        self._quant_blacklist = None

    def set_model(self, model_dir, params_file=None):
        self.model_dir = model_dir
        self.params_file = params_file

    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        self._use_accelerator = True

    enable_use_tpu = enable_use_gpu

    def disable_gpu(self):
        self._use_accelerator = False

    def switch_ir_optim(self, x=True):
        self._ir_optim = x

    def enable_tensorrt_engine(self, *a, **k):
        pass  # subgraph offload is native under XLA

    def enable_mkldnn(self):
        pass

    def set_aot_shapes(self, feed_shapes):
        """Pin feed shapes {name: shape} for ahead-of-time compilation at
        predictor creation (jax.jit lower/compile — the XLA-native
        equivalent of TRT engine build at load time)."""
        self._aot_shapes = dict(feed_shapes)

    def enable_quantize(self, mode="weight_only", calibration_table=None,
                        blacklist=None):
        """Quantize the loaded model at predictor creation
        (docs/QUANTIZATION.md). ``weight_only`` stores the weights int8
        in the predictor's private scope (``QuantizeTranspiler.
        convert_to_int8`` — the weight store genuinely shrinks 4x) with
        dequantize-on-use; ``full_int8`` additionally rewrites the
        matmul/conv compute to int8×int8→int32 via the `quant_rewrite`
        pass and needs `calibration_table` (a ``quant.CalibrationTable``,
        a dict, or a saved-table JSON path) for the activation ranges.
        Honors ``switch_ir_optim``: with IR optimization off the model
        loads exactly as saved, un-quantized."""
        self._quant_mode = mode
        self._quant_table = calibration_table
        self._quant_blacklist = blacklist


def _resolve_feed(inputs, feed_names):
    """Positional-or-named PaddleTensor list -> {name: array} feed dict
    (shared by AnalysisPredictor and ServingPredictor)."""
    feed = {}
    for i, t in enumerate(inputs):
        name = t.name if getattr(t, "name", None) else feed_names[i]
        feed[name] = t.data if isinstance(t, PaddleTensor) else t
    return feed


class AnalysisPredictor:
    """Load + optimize + execute a saved inference program
    (analysis_predictor.cc: ctor → LoadProgramDesc + OptimizeInferenceProgram
    :427 + PrepareExecutor :179; Run :196)."""

    def __init__(self, config: AnalysisConfig):
        self._config = config
        self._scope = Scope()
        if config._use_accelerator is None:
            place = default_place()
        else:
            place = TPUPlace(0) if config._use_accelerator else CPUPlace()
        self._exe = Executor(place)
        from .core.scope import scope_guard

        with scope_guard(self._scope):
            self._program, self._feed_names, self._fetch_vars = \
                io.load_inference_model(config.model_dir, self._exe,
                                        model_filename=config.prog_file,
                                        params_filename=config.params_file)
        if config._ir_optim:
            # OptimizeInferenceProgram parity: the registered inference
            # passes run once at load time. The predictor owns a private
            # scope and a freshly loaded program, so the weight-editing
            # conv_bn fold is safe here (the generic compile-time
            # pipeline — DCE/CSE/folding — runs per compile in the
            # executor; docs/COMPILER_PASSES.md).
            from . import ir

            # pin the fetch targets so the passes' rewrites can never
            # orphan an output the predictor will fetch
            self._program._opt_fetch_targets = tuple(
                v.name for v in self._fetch_vars)
            ir.apply_passes(
                self._program,
                ["conv_bn_fold", "dropout_remove",
                 "conv_elementwise_add_fuse"],
                self._scope)
        if config._quant_mode and config._ir_optim:
            # post-training quantization at load time (docs/
            # QUANTIZATION.md): the predictor owns the program AND the
            # scope, so the weight_only int8 conversion may edit weights
            # destructively (the conv_bn_fold argument); full_int8 rides
            # the compile pipeline's quant_rewrite pass. Gated on
            # switch_ir_optim like the other load-time transforms.
            from . import quant

            quant.quantize_predictor_program(
                self._program, self._scope, mode=config._quant_mode,
                table=config._quant_table,
                blacklist=config._quant_blacklist)
        if config._aot_shapes:
            self._warmup(config._aot_shapes)

    def _warmup(self, shapes):
        feed = {}
        block = self._program.global_block()
        for name in self._feed_names:
            v = block.var(name)
            dt = framework.dtype_to_np(v.dtype)
            feed[name] = np.zeros(shapes[name], dt)
        self.run_dict(feed)  # traces + compiles; cached by signature

    def get_input_names(self):
        return list(self._feed_names)

    def get_output_names(self):
        return [v.name for v in self._fetch_vars]

    def run_dict(self, feed):
        from .core.scope import scope_guard

        with scope_guard(self._scope):
            return self._exe.run(self._program, feed=feed,
                                 fetch_list=self._fetch_vars)

    def run(self, inputs):
        """inputs: list of PaddleTensor (positional or named); returns
        list of PaddleTensor (analysis_predictor.cc:196)."""
        outs = self.run_dict(_resolve_feed(inputs, self._feed_names))
        return [PaddleTensor(o, name=v.name)
                for o, v in zip(outs, self._fetch_vars)]


def create_paddle_predictor(config):
    """CreatePaddlePredictor parity (analysis_predictor.cc:884)."""
    return AnalysisPredictor(config)


# ---------------------------------------------------------------------------
# AOT serving artifacts (the §7 design mapping's "AnalysisPredictor →
# AOT-compiled serving path (jax.export / XLA AOT)"): the loaded program is
# lowered once at pinned shapes, weights baked in as constants, and the
# result serialized as a portable StableHLO artifact. A fresh process can
# serve it with `load_serving_model` — no program descriptor, no op
# registry, no retracing (TensorRT engine-file capability parity, but the
# engine is XLA itself).
# ---------------------------------------------------------------------------

_SERVING_BIN = "__serving__.stablehlo"
_SERVING_META = "__serving_meta__.json"


def export_serving_model(dirname, predictor, feed_shapes,
                         platforms=("cpu", "tpu")):
    """Serialize `predictor`'s program at pinned `feed_shapes`
    ({name: shape}) into `dirname` (the save_inference_model convention:
    dirname is the output directory). The artifact is lowered for every
    platform in `platforms` so one file serves both the TPU fleet and CPU
    canaries."""
    import json
    import os

    import jax
    import jax.numpy as jnp
    from jax import export as jexport

    from .core.lowering import LoweringContext, execute_block

    program = predictor._program
    block = program.global_block()
    scope = predictor._scope

    consts = {}
    for name, v in block.vars.items():
        if v.persistable:
            val = scope.get(name)
            if val is not None:
                consts[name] = jnp.asarray(val)

    feed_names = list(predictor._feed_names)
    fetch_names = [v.name for v in predictor._fetch_vars]

    def fn(feeds):
        env = dict(consts)
        env.update(feeds)
        ctx = LoweringContext(base_key=jax.random.PRNGKey(0), is_test=True)
        execute_block(block, env, ctx)
        return [env[n] for n in fetch_names]

    arg_spec = {}
    for name in feed_names:
        v = block.var(name)
        dt = framework.dtype_to_np(v.dtype)
        arg_spec[name] = jax.ShapeDtypeStruct(tuple(feed_shapes[name]), dt)

    exported = jexport.export(jax.jit(fn),
                              platforms=list(platforms))(arg_spec)
    blob = bytes(exported.serialize())

    os.makedirs(dirname, exist_ok=True)
    with open(os.path.join(dirname, _SERVING_BIN), "wb") as f:
        f.write(blob)
    meta = {
        "feed_names": feed_names,
        "feed_shapes": {n: list(feed_shapes[n]) for n in feed_names},
        "feed_dtypes": {n: str(arg_spec[n].dtype) for n in feed_names},
        "fetch_names": fetch_names,
    }
    with open(os.path.join(dirname, _SERVING_META), "w") as f:
        json.dump(meta, f)

    # ---- Python-free companion artifact (native/serve.cc) ----------
    # One RAW StableHLO module per platform (a multi-platform jax.export
    # module takes a platform-index argument — a per-platform export
    # keeps the PJRT calling convention plain), plus a line-based
    # manifest so the C++ loader needs no JSON/protobuf. Arguments ride
    # in jax's dict-flatten order (sorted feed names).
    lines = []
    for p in platforms:
        single = jexport.export(jax.jit(fn), platforms=[p])(arg_spec)
        mod_name = "__serving__.%s.mlirbc" % p
        with open(os.path.join(dirname, mod_name), "wb") as f:
            f.write(single.mlir_module_serialized)
        lines.append("module %s %s" % (p, mod_name))
    for name in sorted(feed_names):
        lines.append("input %s %s" % (name, np.dtype(
            arg_spec[name].dtype).str))
    for name in fetch_names:
        lines.append("output %s" % name)
    with open(os.path.join(dirname, "__serving_native__.txt"), "w") as f:
        f.write("\n".join(lines) + "\n")
    return os.path.join(dirname, _SERVING_BIN)


def export_native_train_step(dirname, program, feed_shapes, scope=None,
                             fetch_names=(), platforms=("cpu", "tpu")):
    """Export one full TRAINING step (forward + backward + optimizer) as
    a raw StableHLO module `native_serve --train-loop` can iterate with
    NO Python in the process (train/demo_trainer.cc parity with XLA as
    the engine; closes the CPython embed native/trainer.cc carries).

    Calling convention (written to __train_native__.txt): arguments =
    [state_0..state_{k-1}, counter, feeds...(sorted)], results =
    [new_state_0..new_state_{k-1}, counter+1, fetches...] — state slots
    pair positionally, so the C++ loop just feeds each iteration's state
    outputs back in. State = the program's mutable persistables (params,
    optimizer accumulators), captured from `scope`; read-only
    persistables bake in as constants."""
    import json as _json
    import os

    import jax
    import jax.numpy as jnp
    from jax import export as jexport

    from .compiler import classify_persistable_state
    from .core.lowering import LoweringContext, execute_block
    from .core.scope import global_scope

    scope = scope if scope is not None else global_scope()
    block = program.global_block()
    fetch_names = list(fetch_names)
    mut_names, const_names, state_out = classify_persistable_state(
        block, fetch_names)
    # every written persistable is carried (a write-only accumulator
    # still needs a slot for the next iteration to read)
    state_names = sorted(set(mut_names) | set(state_out))
    consts = {}
    for name in const_names:
        val = scope.get(name)
        if val is None:
            raise RuntimeError(
                "persistable %r has no value — run the startup program"
                % name)
        consts[name] = jnp.asarray(val)
    state0 = {}
    for name in state_names:
        val = scope.get(name)
        if val is None:
            raise RuntimeError(
                "state var %r has no value — run the startup program"
                % name)
        state0[name] = jnp.asarray(val)

    feed_names = sorted(feed_shapes)
    seed = program.random_seed or 0

    def train_step(*flat):
        k = len(state_names)
        env = dict(consts)
        env.update(zip(state_names, flat[:k]))
        counter = flat[k]
        env.update(zip(feed_names, flat[k + 1:]))
        ctx = LoweringContext(base_key=jax.random.fold_in(
            jax.random.PRNGKey(seed), counter))
        execute_block(block, env, ctx)
        outs = [env[n] for n in state_names]
        outs.append(counter + jnp.uint32(1))
        outs.extend(env[n] for n in fetch_names)
        return tuple(outs)

    arg_specs = [jax.ShapeDtypeStruct(state0[n].shape, state0[n].dtype)
                 for n in state_names]
    arg_specs.append(jax.ShapeDtypeStruct((), jnp.uint32))
    feed_dtypes = {}
    for name in feed_names:
        v = block._find_var_recursive(name)
        dt = framework.dtype_to_np(v.dtype if v is not None else "float32")
        feed_dtypes[name] = np.dtype(dt)
        arg_specs.append(jax.ShapeDtypeStruct(
            tuple(feed_shapes[name]), dt))

    os.makedirs(dirname, exist_ok=True)
    lines = []
    for i, p in enumerate(platforms):
        exported = jexport.export(jax.jit(train_step),
                                  platforms=[p])(*arg_specs)
        mod = "__train__.%s.mlirbc" % p
        with open(os.path.join(dirname, mod), "wb") as f:
            f.write(exported.mlir_module_serialized)
        if i == 0:
            # full jax.export blob: lets a Python host (or a test)
            # validate the module's loop-carried semantics without PJRT
            with open(os.path.join(dirname, "__train__.jaxexport"),
                      "wb") as f:
                f.write(bytes(exported.serialize()))
        lines.append("module %s %s" % (p, mod))
    for name in state_names:
        lines.append("state %s %s" % (name,
                                      np.dtype(state0[name].dtype).str))
    for name in feed_names:
        lines.append("input %s %s" % (name, feed_dtypes[name].str))
    for name in fetch_names:
        lines.append("output %s" % name)
    with open(os.path.join(dirname, "__train_native__.txt"), "w") as f:
        f.write("\n".join(lines) + "\n")
    # initial state as a stored npz the C++ loop can read
    np.savez(os.path.join(dirname, "state0.npz"),
             **{n: np.asarray(v) for n, v in state0.items()})
    meta = {"state": state_names, "feeds": feed_names,
            "fetches": fetch_names}
    with open(os.path.join(dirname, "__train_meta__.json"), "w") as f:
        _json.dump(meta, f)
    return state_names


# ---------------------------------------------------------------------------
# Generation-serving artifact (docs/SERVING.md): the training-side
# transformer program's decoder weights, lifted into the layout the
# continuous-batching engine's fixed-shape decode step consumes. The
# artifact directory is shared with the one-shot exports above —
# export_serving_model's __serving_native__.txt for native_serve, this
# module's __generation__.npz for paddle_tpu.serving.ServingEngine — so
# one directory deploys both the Python-free single-call path and the
# concurrent-traffic path.
# ---------------------------------------------------------------------------


def export_generation_model(dirname, program, scope=None,
                            max_seq_len=None):
    """Export a program built by ``models.transformer_fluid.build``
    (remat=False, dropout_rate=0) as a generation-serving artifact:
    ``__generation__.npz`` (fp32 decoder weights in the serving layout)
    plus ``__generation_meta__.json`` (the GenerationConfig) and
    ``__generation_manifest__.json`` (per-weight sha256 digests). The
    publish is ATOMIC (tmp + rename, manifest written last): a reader
    sees either the complete artifact or the previous one, and a torn
    write is detected by ``verify_generation_artifact`` — the
    OnlineUpdater's publish leg (docs/SERVING.md "Online updates")
    leans on exactly this. Serve it with
    ``paddle_tpu.serving.ServingEngine(dirname)`` (or
    ``load_generation_model``). Returns the GenerationConfig."""
    from .core.scope import global_scope
    from .serving import model as _serving_model

    scope = scope if scope is not None else global_scope()
    config, weights = _serving_model.extract_decoder_weights(
        program, scope, max_seq_len=max_seq_len)
    _serving_model.save_generation_artifact(dirname, config, weights)
    return config


def load_generation_model(dirname, name=None, quantize=None):
    """Load an exported generation artifact as a
    ``paddle_tpu.serving.GenerationModel`` (ready for ServingEngine).
    ``quantize='weight_only'`` serves the same artifact with the int8
    weight store (docs/QUANTIZATION.md)."""
    from .serving import load_generation_artifact

    return load_generation_artifact(dirname, name=name, quantize=quantize)


class ServingPredictor:
    """Runs an exported serving artifact (see export_serving_model)."""

    def __init__(self, dirname):
        import json
        import os

        from jax import export as jexport

        with open(os.path.join(dirname, _SERVING_BIN), "rb") as f:
            self._exported = jexport.deserialize(bytearray(f.read()))
        with open(os.path.join(dirname, _SERVING_META)) as f:
            self._meta = json.load(f)

    def get_input_names(self):
        return list(self._meta["feed_names"])

    def get_output_names(self):
        return list(self._meta["fetch_names"])

    def run_dict(self, feed):
        args = {}
        for name in self._meta["feed_names"]:
            want = np.dtype(self._meta["feed_dtypes"][name])
            arr = np.asarray(feed[name])
            if arr.dtype != want:
                arr = arr.astype(want)
            args[name] = arr
        return self._exported.call(args)

    def run(self, inputs):
        outs = self.run_dict(_resolve_feed(inputs, self._meta["feed_names"]))
        return [PaddleTensor(np.asarray(o), name=n)
                for o, n in zip(outs, self._meta["fetch_names"])]


def load_serving_model(dirname):
    return ServingPredictor(dirname)
