"""Any-program pipeline parallelism through the descriptor path.

The reference's defining multi-device contract is "rewrite ANY user program
for N devices" (framework/ir/multi_devices_graph_pass/
multi_devices_graph_pass.cc:165) — but its builder only does data
parallelism. Pipeline parallelism is a new-design axis (SURVEY §5.7);
round 3 delivered it only inside the hand-written SPMD trainer
(parallel/transformer.py). This module brings the SAME 1F1B schedule to an
arbitrary Fluid program built from `fluid.layers`:

    strategy = BuildStrategy()
    strategy.pipeline_stages = 4            # pp axis size
    strategy.pipeline_microbatches = 8      # defaults to pp
    CompiledProgram(prog).with_data_parallel(loss_name=..., build_strategy=strategy)

Design (TPU-native, no graph rewrite):
 - The program's op list is [forward | backward | optimizer]; the forward
   section is split into `pp` contiguous stages, either by explicit
   `with fluid.pipeline_stage(i):` annotation or by a balanced-FLOP
   auto-split. Backward ops are NOT executed — each stage's gradients come
   from `jax.vjp` of its lowered forward (the same kernels the grad ops
   would re-run, so results are identical); optimizer/clip/regularizer ops
   then run unchanged on the accumulated grads.
 - One `shard_map` over the ("dp", "pp", "tp") step mesh, MANUAL over dp/pp
   and GSPMD-auto over tp: the 1F1B ring schedule (ppermute neighbor
   exchange, O(pp) input stash, fwd fill while bwd drains) is hand-written
   over the manual axes, while the planner's Megatron tp shardings keep
   working untouched inside every stage body.
 - Stage bodies become branches of one `lax.switch` on the pp rank index —
   SPMD requires every rank to run the same traced program; the switch
   executes only the resident stage's ops at run time.
 - Activations cross stage cuts as packed wire buffers (one fp32 buffer +
   one int32 buffer, padded to the widest cut) so heterogeneous cut
   signatures ride a single fixed-shape ppermute ring. Packing is
   reshape/cast/concat — exact for bf16/fp16/fp32 payloads and transparent
   to reverse-mode AD.

Semantics: microbatching requires the loss to be a MEAN over batch
elements (the usual Fluid `mean(cross_entropy)` shape); gradients then
equal the full-batch gradient exactly, which the parity test asserts
against the single-device executor. Ops with cross-batch state (batch_norm
running stats) are rejected with a clear error — use layer_norm or run BN
under dp-only parallelism.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_index as _axis_index
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.lowering import LoweringContext, execute_op
from ..framework import dtype_to_np

__all__ = ["PipelineProgramStep", "split_sections", "assign_stages"]


# ---------------------------------------------------------------------------
# program analysis
# ---------------------------------------------------------------------------


def _is_backward_op(op):
    return "__fwd_op__" in op.attrs or op.attrs.get("__op_role__") == "backward"


def split_sections(block):
    """(fwd_ops, post_ops): forward ops before the first backward op, and
    the non-backward tail (optimizer / clip / regularizer / lr ops)."""
    ops = block.ops
    bwd = next((i for i, op in enumerate(ops) if _is_backward_op(op)), None)
    if bwd is None:
        return list(ops), []
    return list(ops[:bwd]), [op for op in ops[bwd:] if not _is_backward_op(op)]


def _numel(shape):
    n = 1
    for d in shape or ():
        if d is not None and d > 0:
            n *= d
    return n


def _op_cost(op):
    """Relative FLOP estimate for stage balancing. Static shapes with the
    batch dim as -1 are fine — only the ratio between ops matters."""
    sub_cost = 0.0
    for key in ("sub_block", "true_block", "false_block"):
        sub = op.attrs.get(key) if op.attrs else None
        if sub is not None and getattr(sub, "ops", None) is not None:
            sub_cost += sum(_op_cost(o) for o in sub.ops)
    out_n = sum(_numel(v.shape) for vs in op.outputs.values() for v in vs
                if v.shape is not None)
    t = op.type
    if t in ("mul", "matmul"):
        ys = op.inputs.get("Y", [])
        k = 1
        if ys and ys[0].shape and len(ys[0].shape) >= 2:
            k = max(1, ys[0].shape[-2] or 1)
        return sub_cost + 2.0 * out_n * k
    if t in ("conv2d", "depthwise_conv2d", "conv3d"):
        fs = op.inputs.get("Filter", [])
        k = _numel(fs[0].shape[1:]) if fs and fs[0].shape else 1
        return sub_cost + 2.0 * out_n * k
    if t == "flash_attention":
        qs = op.inputs.get("Q", [])
        seq = 1
        if qs and qs[0].shape and len(qs[0].shape) >= 2:
            seq = max(1, qs[0].shape[1] or 1)
        return sub_cost + 4.0 * out_n * seq
    return sub_cost + float(out_n)


def assign_stages(fwd_ops, pp):
    """Stage id per forward op: honor `__pipeline_stage__` stamps from
    `fluid.pipeline_stage(i)` when present (unstamped ops inherit the
    previous stamp), else balanced cumulative-cost auto-split into pp
    contiguous chunks."""
    stamped = [op.attrs.get("__pipeline_stage__") for op in fwd_ops]
    if any(s is not None for s in stamped):
        stages, cur = [], 0
        for i, s in enumerate(stamped):
            if s is not None:
                s = int(s)
                if s < cur:
                    raise ValueError(
                        "pipeline_stage annotations must be non-decreasing "
                        "in program order: op #%d (%s) is stage %d after "
                        "stage %d" % (i, fwd_ops[i].type, s, cur))
                cur = s
            if cur >= pp:
                raise ValueError(
                    "pipeline_stage %d out of range for pipeline_stages=%d"
                    % (cur, pp))
            stages.append(cur)
        return stages
    costs = [_op_cost(op) for op in fwd_ops]
    n = len(costs)
    if n < pp:
        raise ValueError(
            "cannot split %d forward ops into %d pipeline stages — "
            "reduce pipeline_stages/pipeline_virtual_stages" % (n, pp))
    # minimax contiguous partition into EXACTLY pp non-empty segments
    # (DP): unlike a greedy midpoint walk, one dominant op can never
    # leave an interior stage empty, and the bottleneck stage cost —
    # which sets the pipeline's tick time — is provably minimal
    prefix = [0.0]
    for c in costs:
        prefix.append(prefix[-1] + c)
    inf = float("inf")
    best = [[inf] * (n + 1) for _ in range(pp + 1)]
    cut = [[0] * (n + 1) for _ in range(pp + 1)]
    best[0][0] = 0.0
    for k in range(1, pp + 1):
        for j in range(k, n - (pp - k) + 1):
            for i in range(k - 1, j):
                v = max(best[k - 1][i], prefix[j] - prefix[i])
                if v < best[k][j]:
                    best[k][j] = v
                    cut[k][j] = i
    bounds = [n]
    j = n
    for k in range(pp, 0, -1):
        j = cut[k][j]
        bounds.append(j)
    bounds.reverse()
    stages = []
    for s in range(pp):
        stages.extend([s] * (bounds[s + 1] - bounds[s]))
    return stages


# ---------------------------------------------------------------------------
# wire packing: heterogeneous cut signatures over one fixed-shape ring
# ---------------------------------------------------------------------------


class _CutLayout:
    """Ordered (name, shape, np dtype) entries for one stage cut, split
    into float (fp32 wire, differentiable) and int (int32 wire) segments."""

    def __init__(self, entries):
        for n, _, d in entries:
            # the wire is fp32/int32: exact for every dtype JAX produces
            # with x64 disabled (the default); 64-bit payloads would be
            # silently narrowed, so reject them instead
            if np.dtype(d).itemsize > 4:
                raise NotImplementedError(
                    "activation %r crossing a pipeline stage cut has dtype "
                    "%s; the stage wire is fp32/int32 and would narrow it "
                    "(jax_enable_x64 programs are unsupported under "
                    "pipeline_stages > 1)" % (n, d))
        self.fent = [(n, s, d) for n, s, d in entries
                     if np.issubdtype(d, np.inexact)]
        self.ient = [(n, s, d) for n, s, d in entries
                     if not np.issubdtype(d, np.inexact)]
        self.nf = sum(_numel(s) for _, s, _ in self.fent)
        self.ni = sum(_numel(s) for _, s, _ in self.ient)

    def pack(self, env, nf_max, ni_max):
        fparts = [env[n].astype(jnp.float32).reshape(-1)
                  for n, _, _ in self.fent]
        iparts = [env[n].astype(jnp.int32).reshape(-1)
                  for n, _, _ in self.ient]
        f = (jnp.concatenate(fparts) if fparts
             else jnp.zeros((0,), jnp.float32))
        i = (jnp.concatenate(iparts) if iparts
             else jnp.zeros((0,), jnp.int32))
        return (jnp.pad(f, (0, nf_max - f.shape[0])),
                jnp.pad(i, (0, ni_max - i.shape[0])))

    def unpack(self, env, f, i):
        off = 0
        for n, s, d in self.fent:
            k = _numel(s)
            env[n] = jax.lax.slice_in_dim(f, off, off + k).reshape(s) \
                .astype(d)
            off += k
        off = 0
        for n, s, d in self.ient:
            k = _numel(s)
            env[n] = jax.lax.slice_in_dim(i, off, off + k).reshape(s) \
                .astype(d)
            off += k


class _ResidLayout:
    """Packed layout for one stage's vjp residual leaves (activation-
    stash mode): inexact leaves ride the fp32 buffer (bf16/f16/f32 cast
    is exact), 4-byte integer kinds bitcast onto the int32 buffer
    (uint32 RNG keys round-trip bit-exactly), narrower ints/bool ride
    int32 by value. The treedef is captured from an eval_shape probe of
    the SAME vjp the real trace runs, so unflattening stashed leaves at
    backward time reconstructs an identical vjp function."""

    def __init__(self, treedef, avals, rebind):
        self.treedef = treedef
        self.records = []  # (kind, shape, dtype, rebind_ref)
        for (shape, dtype), ref in zip(avals, rebind):
            d = np.dtype(dtype)
            if ref is not None:
                # this residual IS a live param/constant (identity-
                # matched at probe time): rebind at backward instead of
                # stashing N in-flight fp32 copies of the weights
                self.records.append(("rebind", tuple(shape), d, ref))
                continue
            if d == jax.dtypes.float0:
                # float0 cotangent placeholders (integer/bool primals in
                # the vjp) carry no bytes — strip them from the stash and
                # re-materialize zeros at unpack, the same treatment
                # core/lowering.py and dygraph/base.py give float0 grads
                kind = "float0"
            elif d == np.float64:
                # under jax_enable_x64 a float64 residual would silently
                # lose mantissa bits through the shared fp32 buffer —
                # refuse instead of downcasting (ADVICE round 5)
                raise NotImplementedError(
                    "pipeline_activation_stash cannot pack a float64 "
                    "residual losslessly through the fp32 stash buffer "
                    "(jax_enable_x64 run) — use the default recompute "
                    "mode for float64 models")
            elif np.issubdtype(d, np.inexact) or d == jnp.bfloat16:
                kind = "f"
            elif d.kind in "iub" and d.itemsize == 4:
                kind = "bitcast"
            elif d.kind in "iub" and d.itemsize < 4:
                kind = "i"
            else:
                raise NotImplementedError(
                    "pipeline_activation_stash cannot pack a residual of "
                    "dtype %s — use the default recompute mode" % d)
            self.records.append((kind, tuple(shape), d, None))
        self.nf = sum(_numel(s) for k, s, _, _ in self.records
                      if k == "f")
        self.ni = sum(_numel(s) for k, s, _, _ in self.records
                      if k in ("bitcast", "i"))

    def pack(self, leaves, nf_max, ni_max):
        fparts, iparts = [], []
        for leaf, (kind, s, d, _) in zip(leaves, self.records):
            if kind in ("rebind", "float0"):
                continue
            if kind == "f":
                fparts.append(leaf.astype(jnp.float32).reshape(-1))
            elif kind == "bitcast":
                iparts.append(jax.lax.bitcast_convert_type(
                    leaf, jnp.int32).reshape(-1))
            else:
                iparts.append(leaf.astype(jnp.int32).reshape(-1))
        f = (jnp.concatenate(fparts) if fparts
             else jnp.zeros((0,), jnp.float32))
        i = (jnp.concatenate(iparts) if iparts
             else jnp.zeros((0,), jnp.int32))
        return (jnp.pad(f, (0, nf_max - f.shape[0])),
                jnp.pad(i, (0, ni_max - i.shape[0])))

    def unpack(self, f, i, sources):
        """sources: {"d": dparam leaves, "c": cparam leaves} — the LIVE
        values rebound into their residual positions (constant within a
        step, so value-identical to what a stash would return)."""
        leaves = []
        foff = ioff = 0
        for kind, s, d, ref in self.records:
            if kind == "rebind":
                leaves.append(sources[ref[0]][ref[1]])
                continue
            if kind == "float0":
                leaves.append(np.zeros(s, dtype=jax.dtypes.float0))
                continue
            k = _numel(s)
            if kind == "f":
                leaves.append(jax.lax.slice_in_dim(f, foff, foff + k)
                              .reshape(s).astype(d))
                foff += k
            elif kind == "bitcast":
                leaves.append(jax.lax.bitcast_convert_type(
                    jax.lax.slice_in_dim(i, ioff, ioff + k).reshape(s),
                    d))
                ioff += k
            else:
                leaves.append(jax.lax.slice_in_dim(i, ioff, ioff + k)
                              .reshape(s).astype(d))
                ioff += k
        return leaves


# ---------------------------------------------------------------------------
# the pipelined step
# ---------------------------------------------------------------------------


class PipelineProgramStep:
    """One jitted dp×pp×tp step for an arbitrary Fluid training program.

    Built lazily per feed signature by CompiledProgram (same caching
    contract as _DataParallelStep)."""

    def __init__(self, program, feed_names, fetch_names, mesh,
                 build_strategy, loss_name):
        from ..compiler import BuildStrategy

        if loss_name is None:
            raise ValueError(
                "pipeline_stages > 1 needs with_data_parallel(loss_name=...) "
                "so the 1F1B schedule knows which scalar to differentiate")
        # Multi-process (DCN) meshes are allowed when the pp axis stays
        # within a process: the 1F1B ring's ppermute then rides local
        # devices (ICI on TPU pods) and only the dp gradient psum crosses
        # processes — the reference's multi-NODE shape (nccl_helper.h:130
        # multi-node ncclCommInitRank; dp between nodes, model parallel
        # within). A pp axis that itself spans processes needs
        # cross-process collective-permute, which XLA:CPU's Gloo backend
        # does not provide — on TPU (DCN ppermute exists) it is untested
        # here for lack of multi-host hardware, so refuse off-TPU.
        ax = mesh.axis_names.index("pp") if "pp" in mesh.axis_names else None
        if ax is not None:
            cols = np.moveaxis(mesh.devices, ax, 0)
            cols = cols.reshape(cols.shape[0], -1)
            pp_crosses = any(
                len({d.process_index for d in cols[:, j]}) > 1
                for j in range(cols.shape[1]))
            if pp_crosses and mesh.devices.flat[0].platform == "cpu":
                raise NotImplementedError(
                    "the pipeline axis spans processes, which needs "
                    "cross-process collective-permute (unavailable on "
                    "XLA:CPU). Lay out the mesh so pp is within a "
                    "process — dp over processes, pp/tp/sp within — or "
                    "run on a TPU pod slice.")
        from ..flags import flag as _flag

        if bool(_flag("check_nan_inf")):
            # per-op nan flags live inside the 1F1B scan's switch branches
            # and cannot be packed out per-tick; refuse loudly rather than
            # let a debugging user believe the checks are on
            raise NotImplementedError(
                "FLAGS_check_nan_inf is not supported under "
                "pipeline_stages > 1 — reproduce on a dp/tp mesh (or "
                "single device) to localize the NaN, then re-enable "
                "pipelining")
        self.program = program
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        self.mesh = mesh
        from ..compiler import mesh_spans_processes

        self._multiprocess = mesh_spans_processes(mesh)
        self._mesh_devs = set(mesh.devices.flat)
        self.loss_name = loss_name
        block = program.global_block()
        self.block = block
        shape = dict(mesh.shape)
        self.dp = int(shape.get("dp", 1))
        self.pp = int(shape.get("pp", 1))
        self.M = int(getattr(build_strategy, "pipeline_microbatches", None)
                     or self.pp)
        if self.M < self.pp:
            raise ValueError(
                "pipeline_microbatches (%d) must be >= pipeline_stages (%d)"
                % (self.M, self.pp))
        self.v = int(getattr(build_strategy, "pipeline_virtual_stages", 1)
                     or 1)
        self.S = self.v * self.pp  # virtual stages; stage s on rank s%pp
        self.stash_activations = bool(getattr(
            build_strategy, "pipeline_activation_stash", False))
        self._seed = program.random_seed or 0
        from .pipeline_schedule import build_schedule

        self.schedule = build_schedule(self.pp, self.M, self.v)

        self.fwd_ops, self.post_ops = split_sections(block)
        if not any(_is_backward_op(op) for op in block.ops):
            raise ValueError(
                "pipeline_stages > 1 needs a training program (append "
                "backward via optimizer.minimize); for inference use "
                "dp/tp sharding instead")
        if self.v > 1 and any(
                op.attrs.get("__pipeline_stage__") is not None
                for op in self.fwd_ops):
            # explicit stamps mean PHYSICAL stages 0..pp-1; silently
            # reinterpreting them as virtual-stage ids would leave v-1
            # chunks empty (all of K's extra ticks, none of the win)
            raise NotImplementedError(
                "fluid.pipeline_stage(i) annotations name physical "
                "stages and do not compose with "
                "pipeline_virtual_stages > 1 — drop the annotations "
                "(the balanced auto-split spreads ops over all %d "
                "virtual chunks) or set pipeline_virtual_stages=1"
                % self.S)
        self.stage_of = assign_stages(self.fwd_ops, self.S)

        # ---- dataflow over the forward section -------------------------
        feed_set = set(self.feed_names)
        produced_at = {}
        last_use = {}
        for op, s in zip(self.fwd_ops, self.stage_of):
            for name in op.input_names():
                v = block._find_var_recursive(name)
                if name in feed_set or v is None or v.persistable:
                    continue
                if name in produced_at:
                    last_use[name] = max(last_use.get(name, s), s)
            for name in op.output_names():
                v = block._find_var_recursive(name)
                if v is not None and v.persistable:
                    raise ValueError(
                        "forward op %r writes persistable var %r — ops with "
                        "cross-batch state (batch_norm running stats) don't "
                        "commute with pipeline microbatching; use "
                        "layer_norm, or dp/tp parallelism for this model"
                        % (op.type, name))
                if name in feed_set:
                    # stage branches re-read feeds fresh each microbatch, so
                    # a later stage would silently see the pre-write value
                    raise ValueError(
                        "forward op %r writes feed var %r in place — "
                        "pipeline stages read feeds immutably; copy the "
                        "feed into a new var (e.g. layers.assign) first"
                        % (op.type, name))
                prev = produced_at.get(name)
                if prev is not None and prev != s:
                    # the cut-crossing sets track one producing stage per
                    # var; a rewrite in a later stage would make every
                    # earlier consumer read the wrong (not-yet-computed)
                    # value, so reject it up front
                    raise ValueError(
                        "var %r is rewritten in place at stage %d after "
                        "being produced at stage %d — in-place rewrites "
                        "across pipeline stages are unsupported; adjust "
                        "pipeline_stage annotations so all writes to a var "
                        "land in one stage" % (name, s, prev))
                produced_at[name] = s
        self.produced_at = produced_at
        # crossing[c]: produced at stage <= c, still consumed after cut c
        self.crossing = []
        for c in range(self.S - 1):
            names = sorted(
                n for n in produced_at
                if produced_at[n] <= c and last_use.get(n, -1) > c)
            self.crossing.append(names)

        # ---- parameters ------------------------------------------------
        fwd_reads = set()
        for op in self.fwd_ops:
            fwd_reads.update(op.input_names())
        pg = dict(getattr(program, "param_grad_map", {}) or {})
        self.dparam_names = sorted(
            p for p, g in pg.items()
            if p in fwd_reads and block._find_var_recursive(g) is not None)
        self.grad_of = {p: pg[p] for p in self.dparam_names}
        self.cparam_names = sorted(
            n for n in fwd_reads
            if n not in self.grad_of and n not in feed_set
            and (lambda v: v is not None and v.persistable)(
                block._find_var_recursive(n)))

        # ---- persistable state classification (jit signature) ----------
        from ..compiler import classify_persistable_state

        self.mut_names, self.const_names, self.state_out = \
            classify_persistable_state(block, self.fetch_names)

        # ---- scalar forward fetches (loss, metrics) --------------------
        post_produced = set()
        for op in self.post_ops:
            post_produced.update(op.output_names())
        self.post_produced = post_produced
        scalar = []
        for name in dict.fromkeys([self.loss_name] + self.fetch_names):
            if name in produced_at:
                v = block._find_var_recursive(name)
                if v is not None and v.shape is not None \
                        and _numel(v.shape) == 1 and -1 not in v.shape:
                    scalar.append(name)
                elif name in self.fetch_names:
                    raise ValueError(
                        "fetch %r is a non-scalar forward activation; under "
                        "pipeline parallelism activations live per-"
                        "microbatch per-stage. Fetch scalars (loss/metrics) "
                        "or persistables instead" % name)
        if self.loss_name not in scalar:
            raise ValueError(
                "loss %r must be a scalar produced by the forward section"
                % self.loss_name)
        self.scalar_names = scalar
        self.loss_idx = scalar.index(self.loss_name)
        self.loss_stage = produced_at[self.loss_name]
        for name in self.fetch_names:
            if name in scalar or name in post_produced:
                continue
            v = block._find_var_recursive(name)
            if v is None or not v.persistable:
                raise ValueError(
                    "fetch %r is neither a scalar forward var, an optimizer "
                    "output, nor a persistable — not fetchable under "
                    "pipeline parallelism" % name)

        # validate post-section reads are resolvable
        grad_names = set(self.grad_of.values())
        resolvable = (set(self.mut_names) | set(self.const_names)
                      | set(self.state_out) | grad_names
                      | set(scalar) | feed_set | post_produced)
        for op in self.post_ops:
            for name in op.input_names():
                if name not in resolvable:
                    raise ValueError(
                        "optimizer-section op %r reads %r, which the "
                        "pipelined step cannot provide (it is a non-scalar "
                        "forward activation)" % (op.type, name))

        # ---- sharding plan (tp over the auto axis, ZeRO over dp) -------
        from ..parallel.planner import plan_program

        from ..compiler import grad_seed_scale_of

        zero_mode = (getattr(build_strategy, "reduce_strategy", 0)
                     == BuildStrategy.ReduceStrategy.Reduce)
        self._grad_seed_scale = grad_seed_scale_of(build_strategy, self.dp)
        self._plan = plan_program(program, mesh,
                                  build_strategy=build_strategy,
                                  zero_sharding=zero_mode)
        self._state_shardings = {
            n: NamedSharding(mesh, self._plan.spec_of(n))
            for n in set(self.mut_names) | set(self.const_names)
            | set(self.state_out)}
        # activation seams, stored as bare PartitionSpecs: inside the
        # manual dp/pp region they must bind to the CONTEXT abstract mesh
        # (Manual axis types) — a concrete-mesh NamedSharding there poisons
        # downstream avals with a mismatched all-Auto mesh
        self._tp_constraint_specs = dict(self._plan.constraints)
        # Inside a lax.switch branch only the resident stage's ranks run, so
        # GSPMD may NOT emit collective-permute / all-to-all there (pair
        # style collectives rendezvous across every device and deadlock;
        # group-style all-reduce / all-gather are per-group and safe).
        # Slicing a tp-sharded dim (split/slice/concat boundaries) is what
        # GSPMD lowers with collective-permute, so pin those ops' INPUTS
        # tp-replicated on the last dim: the column-parallel producer then
        # all-gathers (legal) and the split becomes shard-local; the next
        # row-parallel matmul re-shards by a local slice (no comm).
        tp = int(dict(mesh.shape).get("tp", 1))
        if tp > 1:
            def _pin(v):
                if v is None or v.shape is None or not len(v.shape) \
                        or v.persistable or getattr(v, "is_data", False) \
                        or v.name in self._tp_constraint_specs:
                    return
                spec = P(*([P.UNCONSTRAINED] * (len(v.shape) - 1) + [None]))
                self._tp_constraint_specs[v.name] = spec

            def _row_sharded(name):
                spec = tuple(self._plan.specs.get(name, P()))
                if not spec:
                    return False
                d0 = spec[0]
                axes = d0 if isinstance(d0, (tuple, list)) else (d0,)
                return "tp" in axes

            def _walk(ops):
                for op in ops:
                    for key in ("sub_block", "true_block", "false_block"):
                        sub = op.attrs.get(key) if op.attrs else None
                        if sub is not None and getattr(sub, "ops", None) \
                                is not None:
                            _walk(sub.ops)
                    if op.type in ("split", "concat", "slice", "stack"):
                        # slicing a tp-sharded dim lowers to permutes; pin
                        # the input so the producer all-gathers instead
                        for vs in op.inputs.values():
                            for v in vs:
                                _pin(v)
                    elif op.type in ("mul", "matmul"):
                        # a row-parallel matmul pulls tp-last sharding
                        # backward through its X chain (reshapes, attention
                        # heads), which Shardy lowers with permutes: pin the
                        # X input replicated so the transition is a local
                        # slice, and the partial-sum output to a psum
                        ys = op.inputs.get("Y", [])
                        if ys and getattr(ys[0], "persistable", False) \
                                and _row_sharded(ys[0].name):
                            for v in op.inputs.get("X", []):
                                _pin(v)
                            for vs in op.outputs.values():
                                for v in vs:
                                    _pin(v)

            _walk(self.fwd_ops)
        self._repl = NamedSharding(mesh, P())

        mut_sh = {n: self._state_shardings[n] for n in self.mut_names}
        const_sh = {n: self._state_shardings[n] for n in self.const_names}
        self._jitted = jax.jit(
            self._step,
            donate_argnums=(0,),
            in_shardings=(mut_sh, const_sh, None, None),
        )

    # ------------------------------------------------------------------
    # trace-time construction
    # ------------------------------------------------------------------
    def _probe_layouts(self, dstructs, cstructs, feed_structs):
        """Chain jax.eval_shape through the forward section on microbatch
        shapes to size every cut's wire layout."""
        want = sorted({n for names in self.crossing for n in names})
        constraints = self._context_constraints()

        def run(dp_, cp_, fd_):
            env = {}
            env.update(cp_)
            env.update(dp_)
            env.update(fd_)
            ctx = LoweringContext(base_key=jax.random.PRNGKey(0),
                                  mesh=self.mesh)
            ctx.act_constraints = constraints
            ctx.no_pair_collectives = True
            for op in self.fwd_ops:
                execute_op(op, env, ctx)
            return {n: env[n] for n in want}

        shapes = jax.eval_shape(run, dstructs, cstructs, feed_structs)
        layouts = []
        for names in self.crossing:
            layouts.append(_CutLayout([
                (n, tuple(shapes[n].shape), np.dtype(shapes[n].dtype))
                for n in names]))
        return layouts

    def _probe_residuals(self, branches, cparams, dstructs, micro,
                         repl_feeds, base_key, nf, ni):
        """Per-virtual-stage vjp residual layouts for activation-stash
        mode: eval_shape the SAME vjp the real trace runs and capture
        (treedef, leaf avals) by side effect — deterministic tracing
        makes the probe's treedef identical to the real one, so
        unflattening stashed leaves reconstructs the vjp exactly.
        Residual leaves that ARE the live params/constants (tracer
        identity) are marked for rebinding instead of stashing — the
        stash then holds only genuine per-microbatch activations."""
        feed_structs = {n: jax.ShapeDtypeStruct(a.shape[1:], a.dtype)
                        for n, a in micro.items()}
        feed_structs.update({
            n: jax.ShapeDtypeStruct(np.shape(a), a.dtype)
            for n, a in repl_feeds.items()})
        key_struct = jax.ShapeDtypeStruct(np.shape(base_key),
                                          base_key.dtype)
        f_struct = jax.ShapeDtypeStruct((nf,), np.float32)
        i_struct = jax.ShapeDtypeStruct((ni,), np.int32)
        c_leaves = jax.tree.leaves(cparams)
        layouts = []
        for br in branches:
            cap = {}

            def probe(dp_, f_in, i_in, feeds_mb, key, _br=br, _cap=cap):
                def g(dpp, fi):
                    f_o, i_o, scal = _br((dpp, fi, i_in, feeds_mb, key))
                    return (f_o, scal), i_o

                out, vjp_fn, _aux = jax.vjp(g, dp_, f_in, has_aux=True)
                leaves, treedef = jax.tree.flatten(vjp_fn)
                dp_leaves = jax.tree.leaves(dp_)
                rebind = []
                for leaf in leaves:
                    ref = None
                    for j, p in enumerate(dp_leaves):
                        if leaf is p:
                            ref = ("d", j)
                            break
                    if ref is None:
                        for j, p in enumerate(c_leaves):
                            if leaf is p:
                                ref = ("c", j)
                                break
                    rebind.append(ref)
                _cap["treedef"] = treedef
                _cap["avals"] = [(l.shape, l.dtype) for l in leaves]
                _cap["rebind"] = rebind
                return out

            jax.eval_shape(probe, dstructs, f_struct, i_struct,
                           feed_structs, key_struct)
            layouts.append(_ResidLayout(cap["treedef"], cap["avals"],
                                        cap["rebind"]))
        return layouts

    def _context_constraints(self):
        """NamedShardings for the activation seams, bound to the CURRENT
        abstract mesh (Manual over dp/pp inside the 1F1B region)."""
        from .mesh import current_abstract_mesh

        cmesh = current_abstract_mesh(self.mesh)
        return {n: NamedSharding(cmesh, spec)
                for n, spec in self._tp_constraint_specs.items()}

    def _make_branches(self, cparams, layouts, nf, ni, n_scal):
        """One lax.switch branch per stage: unpack wire -> run the stage's
        ops -> pack outgoing wire + scalar-fetch vector."""
        constraints = self._context_constraints()
        branches = []
        for s in range(self.S):
            in_lay = layouts[s - 1] if s > 0 else None
            out_lay = layouts[s] if s < self.S - 1 else None
            stage_ops = [op for op, st in zip(self.fwd_ops, self.stage_of)
                         if st == s]
            scal_here = [(k, n) for k, n in enumerate(self.scalar_names)
                         if self.produced_at.get(n) == s]

            def branch(operand, _in=in_lay, _out=out_lay, _ops=stage_ops,
                       _scal=scal_here):
                dp_, f_in, i_in, feeds_mb, mb_key = operand
                env = dict(cparams)
                env.update(dp_)
                env.update(feeds_mb)
                if _in is not None:
                    _in.unpack(env, f_in, i_in)
                ctx = LoweringContext(base_key=mb_key, mesh=self.mesh)
                ctx.act_constraints = constraints
                ctx.no_pair_collectives = True
                for op in _ops:
                    execute_op(op, env, ctx)
                if _out is not None:
                    f_out, i_out = _out.pack(env, nf, ni)
                else:
                    f_out = jnp.zeros((nf,), jnp.float32)
                    i_out = jnp.zeros((ni,), jnp.int32)
                scal = jnp.zeros((n_scal,), jnp.float32)
                for k, name in _scal:
                    scal = scal.at[k].set(
                        env[name].astype(jnp.float32).reshape(()))
                return f_out, i_out, scal

            branches.append(branch)
        return branches

    # ------------------------------------------------------------------
    # the traced step
    # ------------------------------------------------------------------
    def _step(self, mut_state, const_state, feeds, step_counter):
        state = {}
        state.update(const_state)
        state.update(mut_state)
        dparams = {n: state[n] for n in self.dparam_names}
        cparams = {n: state[n] for n in self.cparam_names}
        base_key = jax.random.fold_in(
            jax.random.PRNGKey(self._seed), step_counter)

        dp, pp, M = self.dp, self.pp, self.M
        # feed classification: data feeds shard over dp and microbatch;
        # everything else is replicated into every stage body
        # only declared data vars (layers.data) microbatch-split: slicing a
        # replicated auxiliary feed (a table, a mask) would silently change
        # semantics, unlike _DataParallelStep where feed sharding is just a
        # GSPMD layout choice
        batched, repl_feeds = {}, {}
        for name, arr in feeds.items():
            v = self.block._find_var_recursive(name)
            if v is not None and bool(getattr(v, "is_data", False)):
                if np.ndim(arr) < 1 or arr.shape[0] % (dp * M) != 0:
                    raise ValueError(
                        "feed %r batch %s must divide dp*microbatches = %d "
                        "for pipeline parallelism"
                        % (name, np.shape(arr), dp * M))
                sp_tp = dict(self.mesh.shape)
                if (arr.shape[0] // (dp * M) < 2
                        and int(sp_tp.get("sp", 1)) > 1
                        and int(sp_tp.get("tp", 1)) > 1):
                    # XLA:CPU's SPMD partitioner CHECK-aborts (not
                    # raises) subgrouping a size-1 batch dim under
                    # sp x tp — turn the process-killing abort into an
                    # actionable error (docs/PARALLEL.md caveat)
                    raise ValueError(
                        "feed %r microbatch size %d is 1 under combined "
                        "sequence AND tensor parallelism — the SPMD "
                        "partitioner cannot subgroup a size-1 batch dim;"
                        " use batch >= %d" % (
                            name, arr.shape[0] // (dp * M), 2 * dp * M))
                batched[name] = arr
            else:
                repl_feeds[name] = arr

        grads, scal = shard_map(
            self._pipeline_1f1b, mesh=self.mesh,
            in_specs=(P(), P(), P("dp"), P(), P()),
            out_specs=(P(), P()),
            axis_names={"dp", "pp"}, check_vma=False)(
                dparams, cparams, batched, repl_feeds, base_key)

        # ---- optimizer section on accumulated grads (GSPMD region) -----
        env = dict(state)
        env.update(feeds)
        for k, name in enumerate(self.scalar_names):
            v = self.block._find_var_recursive(name)
            val = scal[k]
            if v is not None and v.shape is not None:
                val = val.reshape(tuple(v.shape)).astype(dtype_to_np(v.dtype))
            env[name] = val
        for p, gname in self.grad_of.items():
            gv = self.block._find_var_recursive(gname)
            g = grads[p]
            if gv is not None and gv.dtype is not None:
                g = g.astype(dtype_to_np(gv.dtype))
            env[gname] = g
        ctx = LoweringContext(base_key=base_key, mesh=self.mesh)
        for op in self.post_ops:
            execute_op(op, env, ctx)

        fetches = [jax.lax.with_sharding_constraint(env[n], self._repl)
                   for n in self.fetch_names]
        new_state = {
            n: jax.lax.with_sharding_constraint(
                env[n], self._state_shardings[n])
            for n in self.state_out if n in env}
        return fetches, new_state

    def _pipeline_1f1b(self, dparams, cparams, batched, repl_feeds,
                       base_key):
        """The manual-region (interleaved) 1F1B schedule: runs per
        (dp, pp) rank with tp left to GSPMD, driven by the host-built
        schedule tables (pipeline_schedule.py) — each tick looks up its
        units/stash slots instead of computing index arithmetic, which
        makes virtual-stage interleaving (v>1) the same code path as
        classic 1F1B (v=1). Returns (psummed grads pytree, mean scalar
        vector)."""
        dp, pp, M, v = self.dp, self.pp, self.M, self.v
        sched = self.schedule
        my_pp = _axis_index("pp")
        my_dp = _axis_index("dp")

        micro = {}
        for name, arr in batched.items():
            mb = arr.shape[0] // M
            micro[name] = arr.reshape((M, mb) + arr.shape[1:])

        # wire layouts from microbatch-shaped abstract values
        feed_structs = {
            n: jax.ShapeDtypeStruct(a.shape[1:], a.dtype)
            for n, a in micro.items()}
        feed_structs.update({
            n: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype
                                    if not hasattr(a, "dtype") else a.dtype)
            for n, a in repl_feeds.items()})
        dstructs = {n: jax.ShapeDtypeStruct(v_.shape, v_.dtype)
                    for n, v_ in dparams.items()}
        cstructs = {n: jax.ShapeDtypeStruct(np.shape(v_), v_.dtype)
                    for n, v_ in cparams.items()}
        layouts = self._probe_layouts(dstructs, cstructs, feed_structs)
        nf = max([l.nf for l in layouts] + [1])
        ni = max([l.ni for l in layouts] + [1])
        n_scal = max(len(self.scalar_names), 1)

        branches = self._make_branches(cparams, layouts, nf, ni, n_scal)

        def feeds_at(i):
            d = {n: jax.lax.dynamic_index_in_dim(a, i, axis=0,
                                                 keepdims=False)
                 for n, a in micro.items()}
            d.update(repl_feeds)
            return d

        def key_at(i):
            return jax.random.fold_in(base_key, my_dp * M + i)

        def stage_apply(vs, dp_, f_in, i_in, i):
            # vs = chunk*pp + my_pp: the virtual stage resident here
            return jax.lax.switch(
                vs, branches, (dp_, f_in, i_in, feeds_at(i), key_at(i)))

        # ---- activation stash mode: vjp at FORWARD time, packed
        # residual leaves ride the input-stash slots (identical
        # lifetime); the backward unit unflattens and applies — no
        # chunk-forward rematerialization ----
        if self.stash_activations:
            resid_layouts = self._probe_residuals(
                branches, cparams, dstructs, micro, repl_feeds, base_key,
                nf, ni)
            nfr = max([l.nf for l in resid_layouts] + [1])
            nir = max([l.ni for l in resid_layouts] + [1])

            def _fwd_branch(s):
                br, lay = branches[s], resid_layouts[s]

                def b(operand):
                    dp_, f_in, i_in, feeds_mb, key = operand

                    def g(dpp, fi):
                        f_o, i_o, scal = br((dpp, fi, i_in, feeds_mb,
                                             key))
                        return (f_o, scal), i_o

                    (f_o, scal), vjp_fn, i_o = jax.vjp(
                        g, dp_, f_in, has_aux=True)
                    fr, ir = lay.pack(jax.tree.leaves(vjp_fn), nfr, nir)
                    return f_o, i_o, scal, fr, ir

                return b

            def _bwd_branch(s):
                lay = resid_layouts[s]

                def b(operand):
                    fr, ir, wire_cot, scal_cot = operand
                    sources = {"d": jax.tree.leaves(dparams),
                               "c": jax.tree.leaves(cparams)}
                    vjp_fn = jax.tree.unflatten(
                        lay.treedef, lay.unpack(fr, ir, sources))
                    return vjp_fn((wire_cot, scal_cot))

                return b

            fwd_branches = [_fwd_branch(s) for s in range(self.S)]
            bwd_branches = [_bwd_branch(s) for s in range(self.S)]
        else:
            nfr, nir = nf, ni  # input-wire stash doubles as "residual"

        seed = self._grad_seed_scale / float(M * dp)
        loss_onehot = jnp.zeros((n_scal,), jnp.float32).at[
            self.loss_idx].set(1.0)
        loss_vs = self.loss_stage  # virtual-stage index of the loss
        A, B, C = (sched.arrive_slots, sched.input_slots,
                   sched.cot_slots)
        zf = jnp.zeros((nf,), jnp.float32)
        zi = jnp.zeros((ni,), jnp.int32)

        xs = {k: jnp.asarray(getattr(sched, k)) for k in (
            "fwd_mb", "fwd_chunk", "fwd_read", "fwd_save", "fwd_recv",
            "bwd_mb", "bwd_chunk", "bwd_read", "cot_read", "cot_recv")}

        def tick(carry, row):
            (fwd_f, fwd_i, bwd_f, arr_f, arr_i, in_f, in_i, cot_f,
             gacc, sacc) = carry
            at = {k: jnp.take(r_, my_pp) for k, r_ in row.items()}

            # ---- land last tick's ring wires into the stashes ----
            arr_f = jnp.where(
                at["fwd_recv"] >= 0,
                jax.lax.dynamic_update_index_in_dim(
                    arr_f, fwd_f, jnp.clip(at["fwd_recv"], 0, A - 1), 0),
                arr_f)
            arr_i = jnp.where(
                at["fwd_recv"] >= 0,
                jax.lax.dynamic_update_index_in_dim(
                    arr_i, fwd_i, jnp.clip(at["fwd_recv"], 0, A - 1), 0),
                arr_i)
            cot_f = jnp.where(
                at["cot_recv"] >= 0,
                jax.lax.dynamic_update_index_in_dim(
                    cot_f, bwd_f, jnp.clip(at["cot_recv"], 0, C - 1), 0),
                cot_f)

            # ---- forward unit ----
            valid_f = at["fwd_mb"] >= 0
            i_fc = jnp.clip(at["fwd_mb"], 0, M - 1)
            vs_f = jnp.clip(at["fwd_chunk"], 0, v - 1) * pp + my_pp
            rd = jnp.clip(at["fwd_read"], 0, A - 1)
            f_in = jnp.where(
                at["fwd_read"] >= 0,
                jax.lax.dynamic_index_in_dim(arr_f, rd, 0, keepdims=False),
                zf)
            i_in = jnp.where(
                at["fwd_read"] >= 0,
                jax.lax.dynamic_index_in_dim(arr_i, rd, 0, keepdims=False),
                zi)
            if self.stash_activations:
                f_out, i_out, scal_f, save_f, save_i = jax.lax.switch(
                    vs_f, fwd_branches,
                    (dparams, f_in, i_in, feeds_at(i_fc), key_at(i_fc)))
            else:
                f_out, i_out, scal_f = stage_apply(vs_f, dparams, f_in,
                                                   i_in, i_fc)
                save_f, save_i = f_in, i_in
            sv = jnp.clip(at["fwd_save"], 0, B - 1)
            in_f = jnp.where(
                valid_f,
                jax.lax.dynamic_update_index_in_dim(in_f, save_f, sv, 0),
                in_f)
            in_i = jnp.where(
                valid_f,
                jax.lax.dynamic_update_index_in_dim(in_i, save_i, sv, 0),
                in_i)
            sacc = sacc + jnp.where(valid_f, scal_f, 0.0)

            # ---- backward unit (vjp re-runs the chunk forward) ----
            valid_b = at["bwd_mb"] >= 0
            i_bc = jnp.clip(at["bwd_mb"], 0, M - 1)
            vs_b = jnp.clip(at["bwd_chunk"], 0, v - 1) * pp + my_pp
            br = jnp.clip(at["bwd_read"], 0, B - 1)
            f_in_b = jax.lax.dynamic_index_in_dim(in_f, br, 0,
                                                  keepdims=False)
            i_in_b = jax.lax.dynamic_index_in_dim(in_i, br, 0,
                                                  keepdims=False)
            cr = jnp.clip(at["cot_read"], 0, C - 1)
            cot_in = jnp.where(
                at["cot_read"] >= 0,
                jax.lax.dynamic_index_in_dim(cot_f, cr, 0, keepdims=False),
                zf)
            # cotangent routing: the loss stage seeds; earlier stages
            # relay the ring cotangent; later (post-loss metric) stages
            # send 0
            wire_cot = jnp.where(vs_b < loss_vs, 1.0, 0.0) * cot_in
            scal_cot = loss_onehot * jnp.where(
                vs_b == loss_vs, jnp.float32(seed), 0.0)
            if self.stash_activations:
                gP, g_in = jax.lax.switch(
                    vs_b, bwd_branches, (f_in_b, i_in_b, wire_cot,
                                         scal_cot))
            else:
                def g(dp_, f_in_):
                    f_o, _, scal = stage_apply(vs_b, dp_, f_in_, i_in_b,
                                               i_bc)
                    return f_o, scal

                _, svjp = jax.vjp(g, dparams, f_in_b)
                gP, g_in = svjp((wire_cot, scal_cot))
            gacc = jax.tree.map(
                lambda a, d: a + jnp.where(valid_b, d, 0.0).astype(
                    jnp.float32), gacc, gP)

            # ---- ring exchange (unconditional, all ranks) ----
            fwd_perm = [(i, (i + 1) % pp) for i in range(pp)]
            bwd_perm = [(i, (i - 1) % pp) for i in range(pp)]
            fwd_f2 = jax.lax.ppermute(f_out, "pp", fwd_perm)
            fwd_i2 = jax.lax.ppermute(i_out, "pp", fwd_perm)
            bwd_f2 = jax.lax.ppermute(g_in, "pp", bwd_perm)
            return (fwd_f2, fwd_i2, bwd_f2, arr_f, arr_i, in_f, in_i,
                    cot_f, gacc, sacc), None

        init = (zf, zi, zf,
                jnp.zeros((A, nf), jnp.float32),
                jnp.zeros((A, ni), jnp.int32),
                jnp.zeros((B, nfr), jnp.float32),
                jnp.zeros((B, nir), jnp.int32),
                jnp.zeros((C, nf), jnp.float32),
                jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             dparams),
                jnp.zeros((n_scal,), jnp.float32))
        carry, _ = jax.lax.scan(tick, init, xs)
        gacc, sacc = carry[-2], carry[-1]

        grads = jax.tree.map(lambda g: jax.lax.psum(g, ("dp", "pp")), gacc)
        # each scalar is owned by exactly one stage: pp-psum recovers its
        # M-microbatch sum, the dp-psum sums replicas -> mean over both
        scal = jax.lax.psum(sacc, ("dp", "pp")) / float(M * dp)
        return grads, scal

    # ------------------------------------------------------------------
    # host-side driver (same contract as _DataParallelStep.run)
    # ------------------------------------------------------------------
    def run(self, scope, feed):
        from ..compiler import (lift_to_global, normalize_feed_value,
                                read_persistable_state)

        mut, const = read_persistable_state(scope, self.mut_names,
                                            self.const_names)
        feeds = {name: normalize_feed_value(self.block, name, feed[name])
                 for name in self.feed_names}
        if self._multiprocess:
            # DCN case: jit on a multi-process mesh takes only global
            # jax.Arrays. Feeds lift replicated (every worker feeds the
            # identical global batch; the shard_map in_specs reshard the
            # data feeds over dp), state lifts to its planned sharding
            # unless the scope already holds a correctly-sharded array
            # from the previous step.
            def _is_global(a):
                return (isinstance(a, jax.Array)
                        and set(a.sharding.device_set) == self._mesh_devs)

            feeds = {n: (a if _is_global(a)
                         else lift_to_global(a, self._repl))
                     for n, a in feeds.items()}
            for store in (mut, const):
                for name, val in store.items():
                    want = self._state_shardings.get(name, self._repl)
                    if isinstance(val, jax.Array) and \
                            val.sharding.is_equivalent_to(want,
                                                          np.ndim(val)):
                        continue
                    store[name] = lift_to_global(val, want)
        ctr = np.uint32(scope.get("__step_counter__", 0) or 0)
        fetches, new_state = self._jitted(mut, const, feeds, ctr)
        for name, val in new_state.items():
            scope.set(name, val)
        scope.set("__step_counter__", int(ctr) + 1)
        return fetches
