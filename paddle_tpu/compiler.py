"""CompiledProgram (parity: python/paddle/fluid/compiler.py:49 /
ParallelExecutor C++ runtime C10-C14).

TPU-native: `with_data_parallel` does NOT build per-device op-handle graphs
with inserted NCCL collectives. It lowers the SAME single program onto a
`jax.sharding.Mesh` whose leading axis is the data axis: feeds get
batch-sharded NamedShardings, params are replicated, and XLA's sharding
propagation inserts the gradient all-reduce over ICI (SURVEY §2.3
TPU-native-equivalent note). Loss scaling (ScaleLossGradOpHandle parity)
falls out of mean-reduction semantics — each replica computes the mean over
its shard and gradients are averaged by psum/num_replicas via propagation.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import framework
from . import observability as _observability
from .observability import metrics as _metrics
from .observability import tracing as _tracing
from .core.lowering import (LoweringContext, execute_block,
                            pack_nan_reports, pack_warn_reports,
                            raise_if_nonfinite)
from .framework import dtype_to_np

__all__ = ["CompiledProgram", "ExecutionStrategy", "BuildStrategy"]


class ExecutionStrategy:
    """Knob parity (pybind ExecutionStrategy). Most knobs are no-ops under
    XLA (thread pools, iteration scopes); kept for source compatibility."""

    def __init__(self):
        self.num_threads = 0
        self.num_iteration_per_drop_scope = 1
        self.num_iteration_per_run = 1
        self.use_thread_barrier = False


class BuildStrategy:
    class ReduceStrategy:
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy:
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = \
            BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        self.debug_graphviz_path = ""
        self.enable_sequential_execution = False
        self.fuse_elewise_add_act_ops = False
        self.fuse_all_reduce_ops = True
        self.fuse_broadcast_ops = False
        self.memory_optimize = True
        self.enable_inplace = True
        self.sync_batch_norm = False
        self.num_trainers = 1
        self.trainer_id = 0
        # TPU-native extensions (the reference's multi-device builder only
        # does dp; here ANY program shards over a dp×tp mesh):
        #   tensor_parallel_degree — tp axis size; fc/embedding params get
        #     Megatron column/row specs from parallel/planner.py
        #   sharding_specs — {param name: partition-spec tuple} explicit
        #     overrides, e.g. {"fc_w": (None, "tp")}
        self.tensor_parallel_degree = 1
        self.sharding_specs = {}
        #   pipeline_stages — pp axis size; the forward section is split
        #     into stages (auto FLOP-balanced, or `fluid.pipeline_stage(i)`
        #     annotations) and trained with a 1F1B microbatch schedule
        #     (parallel/pipeline_program.py)
        #   pipeline_microbatches — microbatches per step (default: pp)
        #   pipeline_virtual_stages — Megatron-style interleaving: each
        #     rank hosts this many non-contiguous layer chunks (virtual
        #     stage s lives on rank s % pp), shrinking the fill/drain
        #     bubble (schedule + accounting: parallel/pipeline_schedule.py,
        #     measured table in docs/PARALLEL.md)
        #   pipeline_activation_stash — backward units consume residuals
        #     stashed at forward time instead of rematerializing the
        #     chunk forward: ~one forward less compute per microbatch,
        #     O(in-flight) x chunk-activations more HBM (docs/PARALLEL.md)
        self.pipeline_stages = 1
        self.pipeline_microbatches = None
        self.pipeline_virtual_stages = 1
        self.pipeline_activation_stash = False
        #   sequence_parallel_degree — sp axis size; self-attention runs as
        #     ring attention over sp ranks (K/V ppermute rotation, O(T/sp)
        #     per-chip memory) and the residual stream seq-shards by GSPMD
        #     propagation from the attention seams (ops/compat_ops.py
        #     flash_attention; SURVEY §5.7 long-context axis)
        self.sequence_parallel_degree = 1
        #   amp — run the automatic mixed-precision dtype rewrite
        #     (paddle_tpu/amp.py amp_rewrite pass) for this compiled
        #     program even without amp.decorate()/PTPU_AMP: white-list
        #     ops compute in amp_dtype with fp32 master params
        #     (docs/MIXED_PRECISION.md)
        self.amp = False
        self.amp_level = "O1"
        self.amp_dtype = "bfloat16"


def classify_persistable_state(block, fetch_names, inplace=None):
    """(mut_names, const_names, state_out): the persistable vars a lowered
    step reads — split into donated read/write vs read-only — and writes.
    Shared by _CompiledStep, _DataParallelStep and
    parallel.pipeline_program so the scope/caching contract cannot drift.

    `inplace` (an ir_passes.InplaceInfo) is the donation policy —
    BuildStrategy.enable_inplace made real: disabled, every read+written
    persistable moves to the undonated read-only set (buffers never
    aliased in place); enabled, the last-use analysis additionally
    promotes large write-before-read persistables into the donated
    inputs so their stale scope buffers free into XLA's arena for the
    step. None keeps the legacy classification exactly."""
    produced = set()
    state_in = []
    state_out = set()
    for op in block.ops:
        for name in op.input_names():
            v = block._find_var_recursive(name)
            if v is not None and v.persistable and name not in produced \
                    and name not in state_in:
                state_in.append(name)
        for name in op.output_names():
            produced.add(name)
            v = block._find_var_recursive(name)
            if v is not None and v.persistable:
                state_out.add(name)
    for name in fetch_names:
        v = block._find_var_recursive(name)
        if v is not None and v.persistable and name not in produced \
                and name not in state_in:
            state_in.append(name)
    mut = [n for n in state_in if n in state_out]
    const = [n for n in state_in if n not in state_out]
    if inplace is not None:
        mut, const = inplace.adjust(block, state_in, sorted(state_out),
                                    mut, const)
    return mut, const, sorted(state_out)


def read_persistable_state(scope, mut_names, const_names, fallback=None):
    """(mut, const) value dicts for a step's persistable inputs, with the
    standard not-initialized error. Shared by _DataParallelStep and
    parallel.pipeline_program. `fallback(name)` supplies values for
    compile-time artifacts missing from this scope (baked folded
    constants, donation-promoted dead inputs), which are then seeded
    into the scope."""
    mut, const = {}, {}
    for names, store in ((mut_names, mut), (const_names, const)):
        for name in names:
            val = scope.get(name)
            if val is None and fallback is not None:
                val = fallback(name)
                if val is not None:
                    scope.set(name, val)
            if val is None:
                raise RuntimeError(
                    "persistable var %r is not initialized — run the "
                    "startup program first" % name)
            store[name] = val
    return mut, const


def normalize_feed_value(block, name, arr):
    """Feed normalization shared by the data-parallel and pipeline steps:
    device-resident jax.Arrays pass through without a host round-trip
    (PyReader double-buffer / user device_put); host values become numpy
    cast to the var's declared dtype. int64 ids above int32 range fail
    loudly BEFORE the branch (executor.check_feed_int64) — silently
    truncated feature hashes are the alternative."""
    from .executor import check_feed_int64

    check_feed_int64(name, arr)
    v = block._find_var_recursive(name)
    if not isinstance(arr, jax.Array):
        arr = np.asarray(arr)
    if v is not None and v.shape is not None:
        want = dtype_to_np(v.dtype)
        if arr.dtype != want:
            arr = arr.astype(want)
    return arr


def mesh_spans_processes(mesh):
    """True when the mesh has devices owned by other processes (DCN case:
    jax.distributed multi-host). Steps then must lift host values to global
    jax.Arrays via `lift_to_global` before calling into jit."""
    return any(d.process_index != jax.process_index()
               for d in mesh.devices.flat)


def lift_to_global(value, sharding):
    """Host value -> global jax.Array on a multi-process mesh. Every
    process holds the identical full value (the SPMD single-controller
    contract: same global batch, same state) and materializes only its
    addressable shards."""
    v = np.asarray(value)
    return jax.make_array_from_callback(v.shape, sharding,
                                        lambda idx, a=v: a[idx])


def grad_seed_scale_of(build_strategy, n_replicas):
    """GradientScaleStrategy -> backward seed factor (shared contract:
    CoeffNumDevice = exact global-mean gradients, One = gradients summed
    over per-replica means, Customized = rejected loudly)."""
    gss = getattr(build_strategy, "gradient_scale_strategy",
                  BuildStrategy.GradientScaleStrategy.CoeffNumDevice)
    if gss == BuildStrategy.GradientScaleStrategy.Customized:
        raise NotImplementedError(
            "GradientScaleStrategy.Customized is not supported: the "
            "TPU lowering computes exact global-batch gradients in one "
            "program, so there is no per-device seed var to customize. "
            "Scale the loss in the program instead (CoeffNumDevice = "
            "exact mean semantics, One = gradients scaled by "
            "num-devices).")
    return (float(n_replicas)
            if gss == BuildStrategy.GradientScaleStrategy.One else 1.0)


class CompiledProgram:
    def __init__(self, program_or_graph, build_strategy=None):
        self._program = program_or_graph
        self._build_strategy = build_strategy or BuildStrategy()
        self._is_data_parallel = False
        self._loss_name = None
        self._places = None
        self._exec_strategy = None
        self._share_vars_from = None
        self._compiled_steps = {}
        self._mesh = None
        self._infer_opt = False
        # inference-optimized clones for the NON-data-parallel run path,
        # keyed by (program version, fetch names)
        self._infer_programs = {}
        from .async_engine import setup_persistent_cache

        setup_persistent_cache()

    def with_data_parallel(self, loss_name=None, build_strategy=None,
                           exec_strategy=None, share_vars_from=None,
                           places=None):
        self._is_data_parallel = True
        self._loss_name = loss_name
        if build_strategy is not None:
            self._build_strategy = build_strategy
        self._exec_strategy = exec_strategy or ExecutionStrategy()
        self._share_vars_from = share_vars_from
        self._places = places
        return self

    def with_inference_optimize(self, config):
        """Opt into the inference-mode pass pipeline (dropout_remove +
        the baked conv_bn fold + conv_elementwise_add_fuse on top of the
        default compile-time passes — docs/COMPILER_PASSES.md). Honors
        `config.switch_ir_optim(False)` (AnalysisConfig parity)."""
        self._infer_opt = bool(getattr(config, "_ir_optim", True))
        return self

    # ------------------------------------------------------------------
    def _get_mesh(self):
        """Mesh = leading dp axis + one axis per model-parallel degree > 1
        (pp, sp, tp in that fixed order). Any combination composes — e.g.
        pp×sp switches attention to the all-gather sequence-parallel
        formulation inside stage branches (ops/compat_ops.py); a size-1
        degree simply contributes no axis (planner annotations naming an
        absent axis are sanitized to inert)."""
        if self._mesh is None:
            devs = np.array(jax.devices())
            bs = self._build_strategy
            degrees = [
                ("pp", "pipeline_stages",
                 int(getattr(bs, "pipeline_stages", 1) or 1)),
                ("sp", "sequence_parallel_degree",
                 int(getattr(bs, "sequence_parallel_degree", 1) or 1)),
                ("tp", "tensor_parallel_degree",
                 int(getattr(bs, "tensor_parallel_degree", 1) or 1)),
            ]
            extra = [(axis, knob, d) for axis, knob, d in degrees if d > 1]
            prod = 1
            for _, _, d in extra:
                prod *= d
            if len(devs) % prod:
                raise ValueError(
                    "%s = %s does not divide the %d-device mesh" % (
                        " * ".join(k for _, k, _ in extra),
                        " * ".join(str(d) for _, _, d in extra),
                        len(devs)))
            extra = [(axis, d) for axis, _, d in extra]
            self._mesh = Mesh(
                devs.reshape((len(devs) // prod,)
                             + tuple(d for _, d in extra)),
                axis_names=("dp",) + tuple(n for n, _ in extra))
        return self._mesh

    def _run(self, executor, feed, fetch_list, scope, return_numpy,
             fetch_every_n=None):
        from .async_engine import LazyFetchList
        from .core.scope import global_scope
        from .executor import _CompiledStep, _feed_signature

        if not self._is_data_parallel:
            from . import ir_passes

            run_program = self._program
            if self._infer_opt and ir_passes.pipeline_enabled():
                # apply the inference passes HERE — the executor's own
                # pipeline has no way to know this CompiledProgram asked
                # for them (Executor.run only sees a plain Program)
                fetch_names = tuple(
                    v.name if isinstance(v, framework.Variable) else str(v)
                    for v in (fetch_list or []))
                ikey = (self._program.version, fetch_names)
                run_program = self._infer_programs.get(ikey)
                if run_program is None:
                    from .core.scope import global_scope

                    run_program = ir_passes.optimize_for_execution(
                        self._program, fetch_names,
                        scope if scope is not None else global_scope(),
                        infer_opt=True)
                    self._infer_programs[ikey] = run_program
            return executor.run(run_program, feed=feed,
                                fetch_list=fetch_list, scope=scope,
                                return_numpy=return_numpy,
                                fetch_every_n=fetch_every_n)
        feed = dict(feed or {})
        scope = scope if scope is not None else global_scope()
        fetch_names = [
            v.name if isinstance(v, framework.Variable) else str(v)
            for v in (fetch_list or [])
        ]
        from . import ir_passes
        from .flags import flag

        pp = int(getattr(self._build_strategy,
                         "pipeline_stages", 1) or 1)
        # the pass pipeline (and its BuildStrategy knobs) is part of the
        # compiled-step identity; pipeline-parallel programs are split by
        # stage attrs the generic passes don't understand, so they keep
        # the unoptimized path
        pkey = (ir_passes.pipeline_key(self._build_strategy,
                                       self._program, self._infer_opt)
                if pp == 1 else ())
        # the scope is NOT in the key: scope-bound compile artifacts
        # (baked constants, promoted dead inputs) self-heal through
        # ir_passes.state_fallback at state-read time
        key = (self._program.version, _feed_signature(feed),
               tuple(fetch_names), bool(flag("check_nan_inf")), pkey)
        # staged substitution only after the key: device_put canonicalizes
        # some dtypes, and a signature drift would recompile spuriously
        if executor._prefetcher is not None:
            staged = executor._prefetcher.take_if_match(feed)
            if staged is not None:
                feed = staged
        rec = _metrics.enabled()
        with _observability.step_scope():
            step = self._compiled_steps.get(key)
            if step is None:
                if rec:
                    _metrics.counter("compile_cache/miss").inc()
                run_program = self._program
                if pp == 1 and ir_passes.pipeline_enabled():
                    with _tracing.span("optimize"):
                        run_program = ir_passes.optimize_for_execution(
                            self._program, fetch_names, scope,
                            build_strategy=self._build_strategy,
                            infer_opt=self._infer_opt)
                elif pp == 1:
                    # opted-out pipeline still verifies once per compile
                    # under PTPU_VERIFY_PASSES=1 (pipeline-parallel
                    # stage-split programs stay out of scope, like the
                    # generic passes themselves)
                    from .analysis import maybe_verify

                    maybe_verify(self._program, tuple(fetch_names))
                with _tracing.span("lower"):
                    if pp > 1:
                        from .parallel.pipeline_program import \
                            PipelineProgramStep

                        step = PipelineProgramStep(
                            self._program, feed.keys(), fetch_names,
                            self._get_mesh(), self._build_strategy,
                            self._loss_name)
                    else:
                        step = _DataParallelStep(
                            run_program, feed.keys(), fetch_names,
                            self._get_mesh(), self._build_strategy,
                            scope=scope)
                self._compiled_steps[key] = step
            elif rec:
                _metrics.counter("compile_cache/hit").inc()
            if not any(step is s for s in executor._warn_sources):
                # registered per EXECUTOR: a CompiledProgram's cached step
                # driven by a second executor must be drainable by that
                # executor's sync()/close() too
                executor._warn_sources.append(step)
            sharding_fn = getattr(step, "feed_sharding", None)
            if sharding_fn is not None:
                # the prefetcher stages straight into the step's target
                # sharding from now on (no device-side reshard)
                executor._feed_sharding_fn = sharding_fn
            with _tracing.span("execute"):
                fetches = step.run(scope, feed)
        if rec:
            from .executor import _nbytes

            _metrics.counter("executor/feed_bytes").inc(
                _nbytes(feed.values()))
            _metrics.counter("executor/fetch_bytes").inc(_nbytes(fetches))
        out = executor._finish_run(fetches, return_numpy, fetch_every_n)
        warns = getattr(step, "_deferred_warns", None)
        if warns is not None and not isinstance(out, LazyFetchList):
            # a materializing run is already a sync point: flush pending
            # runtime warnings so the per-step-sync loop warns promptly
            warns.drain(step._warned)
        return out


class _DataParallelStep:
    """One jitted SPMD step over the dp(×tp) mesh.

    The reference builds a per-device op graph and inserts collectives by
    hand (multi_devices_graph_pass.cc:165); here the SAME program is jitted
    once with per-var NamedShardings from `parallel.planner.plan_program`
    and GSPMD inserts them. ReduceStrategy.Reduce shards optimizer state
    over dp (ZeRO-1, reduce_op_handle.cc parity); tensor_parallel_degree>1
    adds a tp mesh axis with Megatron param specs for ANY program."""

    def __init__(self, program, feed_names, fetch_names, mesh,
                 build_strategy, scope=None):
        from . import ir_passes

        self.program = program
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        self.mesh = mesh
        block = program.global_block()
        self.block = block
        inplace = None
        if ir_passes.pipeline_enabled():
            inplace = ir_passes.InplaceInfo(
                enabled=bool(getattr(build_strategy, "enable_inplace",
                                     True)),
                scope=scope)
        self._inplace = inplace
        self.mut_names, self.const_names, self.state_out = \
            classify_persistable_state(block, self.fetch_names,
                                       inplace=inplace)
        self._seed = program.random_seed or 0

        repl = NamedSharding(mesh, P())
        batch = NamedSharding(mesh, P("dp"))
        self._repl = repl
        self._batch = batch
        self._dp = int(dict(mesh.shape).get("dp", 1))
        # long-context feeds [B, T, ...] shard their seq dim over sp too
        self._sp = int(dict(mesh.shape).get("sp", 1))
        self._batch_seq = (NamedSharding(mesh, P("dp", "sp"))
                           if self._sp > 1 else batch)

        bs = build_strategy or BuildStrategy()
        zero_mode = (getattr(bs, "reduce_strategy",
                             BuildStrategy.ReduceStrategy.AllReduce)
                     == BuildStrategy.ReduceStrategy.Reduce)
        # `One` sums per-REPLICA mean gradients: replicas = dp size only
        # (tp shards computation, it does not add replicas)
        self._grad_seed_scale = grad_seed_scale_of(
            bs, int(dict(mesh.shape).get("dp", 1)))

        from .parallel.planner import plan_program

        self._plan = plan_program(program, mesh, build_strategy=bs,
                                  zero_sharding=zero_mode)
        self._state_shardings = {
            n: NamedSharding(mesh, self._plan.spec_of(n))
            for n in set(self.mut_names) | set(self.const_names)
            | set(self.state_out)}
        self._act_constraints = {
            n: NamedSharding(mesh, spec)
            for n, spec in self._plan.constraints.items()}
        # mesh spanning several processes (DCN): numpy feeds must become
        # global jax.Arrays — every worker feeds the identical global batch
        # and each process materializes only its addressable shards
        self._multiprocess = mesh_spans_processes(mesh)

        from .flags import flag

        self._check_nan_inf = bool(flag("check_nan_inf"))
        self._nan_labels = []
        self._warn_labels = []
        self._warned = set()
        from .async_engine import DeferredWarns

        self._deferred_warns = DeferredWarns()

        def step(mut_state, const_state, feeds, step_counter):
            base_key = jax.random.fold_in(
                jax.random.PRNGKey(self._seed), step_counter)
            ctx = LoweringContext(base_key=base_key, mesh=mesh,
                                  check_nan_inf=self._check_nan_inf)
            ctx.grad_seed_scale = self._grad_seed_scale
            ctx.act_constraints = self._act_constraints
            env = {}
            env.update(const_state)
            env.update(mut_state)
            env.update(feeds)
            execute_block(block, env, ctx)
            # fetches + debug flags leave the step fully replicated so
            # multi-process (DCN) meshes can np.asarray them host-side;
            # state outputs pin to their planned sharding (per-leaf —
            # out_shardings can't express the data-dependent key set)
            fetches = [jax.lax.with_sharding_constraint(env[n], repl)
                       for n in self.fetch_names]
            new_state = {
                n: jax.lax.with_sharding_constraint(
                    env[n], self._state_shardings[n])
                for n in self.state_out if n in env}
            self._nan_labels, finite = pack_nan_reports(ctx)
            self._warn_labels, warns = pack_warn_reports(ctx)
            return (fetches, new_state,
                    jax.lax.with_sharding_constraint(finite, repl),
                    jax.lax.with_sharding_constraint(warns, repl))

        # state enters with its planned sharding (replicated by default; tp
        # column/row for planner-assigned params; dp-sharded optimizer state
        # in Reduce mode); feeds shard on the batch dim. XLA sharding
        # propagation inserts the grad all-reduces / reduce-scatters.
        # under the debug flag, keep state undonated so a nan raise can
        # leave the scope at its pre-step values (catch-and-continue safe)
        donate = () if self._check_nan_inf else (0,)
        mut_sh = {n: self._state_shardings[n] for n in self.mut_names}
        const_sh = {n: self._state_shardings[n] for n in self.const_names}
        # feeds get their sharding at run time (device_put): a batch not
        # divisible by dp falls back to replicated instead of erroring
        self._jitted = jax.jit(
            step,
            donate_argnums=donate,
            in_shardings=(mut_sh, const_sh, None, None),
        )

    def feed_sharding(self, name, arr):
        """Target sharding for one feed value: batch-sharded over dp when
        the leading dim divides (replicated fallback otherwise), seq dim
        over sp for long-context feeds. One decision point for run() AND
        the background FeedPrefetcher, so prefetched batches land on
        device already in the layout the step consumes."""
        if not np.ndim(arr) or np.shape(arr)[0] % self._dp:
            return self._repl
        if (self._sp > 1 and np.ndim(arr) >= 2
                and np.shape(arr)[1] % self._sp == 0):
            return self._batch_seq
        return self._batch

    def _state_fallback(self, name):
        from . import ir_passes

        return ir_passes.state_fallback(self.program, self._inplace, name)

    def run(self, scope, feed):
        mut, const = read_persistable_state(scope, self.mut_names,
                                            self.const_names,
                                            fallback=self._state_fallback)
        feeds = {}
        for name in self.feed_names:
            arr = normalize_feed_value(self.block, name, feed[name])
            if not self._multiprocess:
                arr = jax.device_put(arr, self.feed_sharding(name, arr))
            feeds[name] = arr
        if self._multiprocess:
            feeds = {name: lift_to_global(arr, self.feed_sharding(name, arr))
                     for name, arr in feeds.items()}
            for store in (mut, const):
                for name, val in store.items():
                    # only host values need lifting to global arrays; after
                    # step 1 the scope already holds planned-sharded
                    # jax.Arrays — re-lifting would round-trip all params
                    # device->host->device every step
                    want = self._state_shardings.get(name, self._repl)
                    if isinstance(val, jax.Array) and \
                            val.sharding.is_equivalent_to(want,
                                                          np.ndim(val)):
                        continue
                    store[name] = lift_to_global(val, want)
        ctr = np.uint32(scope.get("__step_counter__", 0) or 0)
        fetches, new_state, finite, warns = self._jitted(mut, const,
                                                         feeds, ctr)
        # deferred: flags accumulate host-side and materialize every few
        # steps — the all-false common case costs no per-step sync
        self._deferred_warns.add(self._warn_labels, warns, self._warned)
        if self._check_nan_inf and finite.size:
            # state was NOT donated under the debug flag: raising here leaves
            # the scope at its pre-step values, so the poisoned update is
            # discarded and training can resume after catching
            raise_if_nonfinite(self._nan_labels, finite)
        for name, val in new_state.items():
            scope.set(name, val)
        scope.set("__step_counter__", int(ctr) + 1)
        return fetches
