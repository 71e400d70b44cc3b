"""Post-training int8 quantized inference (parity: the contrib/slim +
contrib/quantize deployment toolkit, SURVEY §2 — `QuantizeTranspiler`
gave Fluid its int8-deploy shape; here the same capability is a
COMPILE-TIME rewrite riding the PR-3 pass pipeline, exactly the way the
PR-5 `amp_rewrite` pass carries bf16 training).

Workflow (docs/QUANTIZATION.md):

  1. **Calibrate** — ``calibrate(program, sample_feeds,
     strategy='abs_max'|'percentile')`` runs the fp32 program over a
     small representative feed set and collects per-tensor activation
     ranges (per-CHANNEL ranges for the persistable weights, read
     straight from the scope) into a serializable
     :class:`CalibrationTable`.
  2. **Rewrite** — the ``quant_rewrite`` pass (registered in
     `fluid.ir`'s registry, scheduled by the default pipeline right
     after `amp_rewrite`'s slot) rewrites each white-list op
     (mul/matmul/conv2d family) on the compile clone:

       full_int8    quantize(activation, scale from the table) -> int8
                    dot/conv accumulating in int32
                    (``preferred_element_type=int32`` — the op carries
                    ``__quant_int8__``) -> ``dequantize_linear`` back to
                    fp32 with the combined per-channel scale
       weight_only  the weight is STORED int8 (baked as a fresh
                    content-addressed persistable scope entry via the
                    PR-3 baking machinery) and a ``dequantize_linear``
                    reconstructs the fp32 weight on use — the compute
                    stays fp32; the win is the halved-or-better weight
                    store, which is what memory-bandwidth-bound decode
                    monetizes.

     Grad-referenced ops, optimizer ops, structural ops, non-fp32
     operands and black-listed names are never rewritten; the original
     fp32 weight vars simply stop being read, so the compiled step's
     device weight store shrinks while the user's program and scope stay
     untouched (the non-destructive compile-clone contract).
  3. **Deploy** — ``AnalysisConfig.enable_quantize(...)`` quantizes at
     predictor load (weight_only rides
     ``QuantizeTranspiler.convert_to_int8``'s genuinely halved scope
     store; full_int8 decorates the loaded program for this pass), and
     ``serving.GenerationModel.quantized()`` is the weight-only-int8
     decode-step variant for the continuous-batching engine.

Activation: ``decorate(program, ...)`` pins a :class:`QuantConfig` on
the program; ``PTPU_QUANT=1`` activates a process-wide default
(``PTPU_QUANT_MODE``, ``PTPU_QUANT_TABLE``, ``PTPU_QUANT_BLACKLIST``).
With both unset the pass pipeline, the compile-cache keys and every
lowered program are BITWISE identical to the pre-quant framework
(pinned by tests/test_quant.py, the AMP-off invariance pattern).

Telemetry: ``quant/{ops_rewritten,weights_quantized,calib_tensors,
weight_bytes_saved,weight_fp32_bytes}`` (docs/OBSERVABILITY.md).
"""

import hashlib
import json
import os

import numpy as np

from .flags import env as _env
from .ir import Pass, register_pass
from .observability import metrics as _metrics

__all__ = [
    "CalibrationTable", "QuantConfig", "calibrate", "decorate",
    "active_config", "quant_env_enabled", "weight_channel_scales",
    "quantize_to_int8", "quantize_symmetric", "weight_store_bytes",
    "quantize_predictor_program", "DEFAULT_QUANT_OPS",
]

# white list: MXU-dot ops whose persistable weight operand can store int8
DEFAULT_QUANT_OPS = frozenset({
    "mul", "matmul", "conv2d", "depthwise_conv2d",
})

# per-op-type slot layout: (activation slot, weight slot)
_SLOTS = {
    "mul": ("X", "Y"),
    "matmul": ("X", "Y"),
    "conv2d": ("Input", "Filter"),
    "depthwise_conv2d": ("Input", "Filter"),
}

_QMAX = 127.0        # symmetric int8 grid (reference weight_bits=8)
_EPS = 1e-8

MODES = ("weight_only", "full_int8")


def _kernel_enabled(name):
    """Emission-time dispatch policy for the fused Pallas kernels
    (ops/kernel_registry.enabled_for): mode + platform only — shape
    qualification happens at trace time inside the emitted op. The
    kernel mode rides the pipeline cache key (ir_passes.pipeline_key),
    so a program rewritten under one policy never serves another."""
    from .ops.kernel_registry import enabled_for

    return enabled_for(name)


def _check_ops(ops):
    """Validate a user-supplied quantizable-op set against the known
    slot layouts — a typo'd op type fails here with the supported list,
    not as a KeyError deep inside the pass."""
    ops = frozenset(ops)
    unknown = ops - frozenset(_SLOTS)
    if unknown:
        raise ValueError(
            "unsupported quantizable op type(s) %s — supported: %s"
            % (sorted(unknown), sorted(_SLOTS)))
    return ops


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------


class CalibrationTable:
    """Serializable per-tensor ranges: ``acts`` maps an activation var
    name to its scalar range (abs-max or percentile of |x| over the
    calibration feeds — the value `s` such that the int8 grid spans
    [-s, s]); ``weights`` maps a weight var name to its per-output-
    channel ranges plus the channel axis. JSON round-trips via
    save/load."""

    def __init__(self, acts=None, weights=None, strategy="abs_max",
                 percentile=None):
        self.acts = {str(k): float(v) for k, v in (acts or {}).items()}
        self.weights = {str(k): {"scales": [float(s) for s in v["scales"]],
                                 "axis": int(v["axis"])}
                        for k, v in (weights or {}).items()}
        self.strategy = strategy
        self.percentile = percentile
        self._digest = None

    def act_scale(self, name):
        return self.acts.get(name)

    def weight_scales(self, name):
        w = self.weights.get(name)
        return None if w is None else (np.asarray(w["scales"], np.float32),
                                       w["axis"])

    def to_dict(self):
        return {"strategy": self.strategy, "percentile": self.percentile,
                "acts": self.acts, "weights": self.weights}

    @classmethod
    def from_dict(cls, d):
        return cls(acts=d.get("acts"), weights=d.get("weights"),
                   strategy=d.get("strategy", "abs_max"),
                   percentile=d.get("percentile"))

    def save(self, path):
        with open(path, "w") as f:
            json.dump(self.to_dict(), f, indent=2, sort_keys=True)
        return path

    @classmethod
    def load(cls, path):
        with open(path) as f:
            return cls.from_dict(json.load(f))

    def digest(self):
        # memoized: digest() sits on the per-compile cache-key path
        # (pipeline_key), and a table is immutable once handed to a
        # QuantConfig
        if self._digest is None:
            h = hashlib.sha1()
            h.update(repr((
                self.strategy, self.percentile,
                sorted(self.acts.items()),
                sorted((k, tuple(v["scales"]), v["axis"])
                       for k, v in self.weights.items()))).encode())
            self._digest = h.hexdigest()[:10]
        return self._digest


def record_weight_store(n_weights, saved_bytes, fp32_bytes):
    """The one emitter for the weight-store telemetry triple — the
    rewrite pass, convert_to_int8 and GenerationModel.quantized() all
    report through here (docs/OBSERVABILITY.md)."""
    _metrics.counter("quant/weights_quantized").inc(n_weights)
    _metrics.counter("quant/weight_bytes_saved").inc(saved_bytes)
    _metrics.counter("quant/weight_fp32_bytes").inc(fp32_bytes)


def weight_store_bytes(weights):
    """Byte accounting for a (possibly int8) weight dict: ``n_int8``
    int8-stored entries, ``int8_bytes`` they occupy (int8 payload plus
    their fp32 ``@qscale`` companions) and ``fp32_bytes`` the same
    entries would occupy dequantized — the serving-stats receipt that a
    model really is running off the int8 store. ``bytes`` is what the
    whole dict holds as stored and ``by_dtype`` the same by storage
    dtype (a bfloat16 dot-operand store reads half its float32 size
    there). Shapes/dtypes only; no device transfer."""
    n_int8 = 0
    int8_bytes = 0
    fp32_bytes = 0
    by_dtype = {}
    for key, v in weights.items():
        v = v if hasattr(v, "dtype") else np.asarray(v)
        size = int(v.size)
        name = str(v.dtype)
        by_dtype[name] = by_dtype.get(name, 0) \
            + size * np.dtype(v.dtype).itemsize
        if name == "int8":
            n_int8 += 1
            int8_bytes += size
            fp32_bytes += size * 4
        elif key.endswith("@qscale"):
            int8_bytes += size * 4
    return {"n_int8": n_int8, "int8_bytes": int8_bytes,
            "fp32_bytes": fp32_bytes, "bytes": sum(by_dtype.values()),
            "by_dtype": by_dtype}


def quantize_to_int8(w, scale_broadcast, qmax=_QMAX):
    """THE symmetric int8 grid (one formula for the pass, the serving
    store and the transpiler): round(w / s * qmax) clipped to
    [-qmax, qmax], with `scale_broadcast` already shaped to broadcast
    onto `w` (`qmax` generalizes to the transpiler's weight_bits
    knob)."""
    return np.clip(np.round(np.asarray(w, np.float32) / scale_broadcast
                            * qmax), -qmax, qmax).astype(np.int8)


def quantize_symmetric(w, channel_axis=-1):
    """Per-channel symmetric int8 quantization along one axis: returns
    ``(q, scales)`` with ``w ≈ q * (scales / 127)`` broadcast along
    `channel_axis` (abs-max ranges reduced over every other axis)."""
    w = np.asarray(w, np.float32)
    ax = channel_axis % w.ndim
    reduce_axes = tuple(i for i in range(w.ndim) if i != ax)
    s = np.maximum(np.abs(w).max(axis=reduce_axes) if reduce_axes
                   else np.abs(w), _EPS).astype(np.float32)
    shape = [1] * w.ndim
    shape[ax] = s.size
    return quantize_to_int8(w, s.reshape(shape)), s


def weight_channel_scales(w, op_type, attrs=None):
    """Per-output-channel abs-max ranges of one weight array plus the
    channel axis: conv filters are ranged over C_out (axis 0); mul/matmul
    weights over the output-feature axis (the trailing dims past
    y_num_col_dims for `mul`, rows under transpose_Y for `matmul`)."""
    attrs = attrs or {}
    w = np.asarray(w)
    if op_type in ("conv2d", "depthwise_conv2d"):
        axis = 0
        s = np.abs(w.reshape(w.shape[0], -1)).max(axis=1)
    elif op_type == "matmul" and attrs.get("transpose_Y"):
        axis = 0
        s = np.abs(w.reshape(w.shape[0], -1)).max(axis=1)
    else:
        yn = int(attrs.get("y_num_col_dims", 1)) if op_type == "mul" \
            else w.ndim - 1
        axis = yn
        s = np.abs(w.reshape(int(np.prod(w.shape[:yn])), -1)).max(axis=0)
    return np.maximum(s, _EPS).astype(np.float32), axis


def _quantizable_sites(program, white):
    """[(op, act var, weight var)] for every global-block white op with a
    persistable, never-in-block-written fp32 weight operand (the shape
    quantization can bake) — skipping grad/optimizer/structural ops."""
    from .core.lowering import _SPECIAL, _STRUCTURAL
    from .framework import (_AMP_STATE_OP_TYPES, _OPTIMIZER_OP_TYPES,
                            Block, Operator, convert_dtype)
    from .ir_passes import _grad_referenced_ids, _write_indices

    block = program.global_block()
    writes = _write_indices(block)
    grad_refed = _grad_referenced_ids(program)
    sites = []
    for op in block.ops:
        if op.type not in white or id(op) in grad_refed:
            continue
        if ("__fwd_op__" in op.attrs or op.type in _OPTIMIZER_OP_TYPES
                or op.type in _AMP_STATE_OP_TYPES
                or op.type in _STRUCTURAL or op.type in _SPECIAL
                or any(isinstance(a, (Block, Operator))
                       for a in op.attrs.values())):
            continue
        aslot, wslot = _SLOTS[op.type]
        avs = op.inputs.get(aslot, [])
        wvs = op.inputs.get(wslot, [])
        if len(avs) != 1 or len(wvs) != 1:
            continue
        a, w = avs[0], wvs[0]
        if not getattr(w, "persistable", False) or writes.get(w.name):
            continue
        if convert_dtype(w.dtype) != "float32" \
                or convert_dtype(a.dtype) != "float32":
            continue
        sites.append((op, a, w))
    return sites


def calibrate(program, sample_feeds, strategy="abs_max", percentile=99.9,
              scope=None, place=None, ops=None,
              max_samples_per_tensor=1 << 19):
    """Run the fp32 `program` over `sample_feeds` (an iterable of feed
    dicts) and collect a :class:`CalibrationTable`: per-tensor activation
    ranges for every quantizable op's activation input (``abs_max`` keeps
    the running max of |x|; ``percentile`` keeps a bounded subsample of
    |x| and takes its `percentile`), plus per-channel weight ranges read
    directly from `scope`. The calibration run is pinned un-quantized
    (a process-wide ``PTPU_QUANT=1`` cannot recurse into it)."""
    from .core.place import CPUPlace
    from .core.scope import global_scope
    from .executor import Executor

    if strategy not in ("abs_max", "percentile"):
        raise ValueError("calibrate: unknown strategy %r "
                         "(use 'abs_max' or 'percentile')" % (strategy,))
    scope = scope if scope is not None else global_scope()
    white = _check_ops(ops) if ops else DEFAULT_QUANT_OPS

    sites = _quantizable_sites(program, white)
    weights = {}
    for op, _a, w in sites:
        if w.name in weights:
            continue
        val = scope.get(w.name)
        if val is None:
            continue
        s, axis = weight_channel_scales(val, op.type, op.attrs)
        weights[w.name] = {"scales": [float(x) for x in s], "axis": axis}
    act_names = sorted({a.name for _op, a, _w in sites
                        if not getattr(a, "persistable", False)})

    acts = {}
    if act_names:
        calib = program.clone(for_test=True)
        # the calibration run must see the plain fp32 graph even when
        # PTPU_QUANT=1 is exported process-wide (chicken-and-egg)
        calib._quant_disable = True
        exe = Executor(place if place is not None else CPUPlace())
        maxima = {n: 0.0 for n in act_names}
        samples = {n: [] for n in act_names}
        # EVERY batch contributes to the percentile distribution: each
        # one is strided down to a bounded slice, and the concatenation
        # is re-strided to the cap at the end — a large first batch can
        # neither blow the memory bound nor shadow later feeds whose
        # ranges differ
        per_batch = max(1, max_samples_per_tensor // 16)
        batches = 0
        for feed in sample_feeds:
            outs = exe.run(calib, feed=feed, fetch_list=list(act_names),
                           scope=scope)
            batches += 1
            for name, val in zip(act_names, outs):
                a = np.abs(np.asarray(val, np.float32)).ravel()
                if strategy == "abs_max":
                    maxima[name] = max(maxima[name], float(a.max()))
                else:
                    stride = max(1, -(-a.size // per_batch))
                    samples[name].append(a[::stride])
        exe.close()
        if batches == 0:
            raise ValueError("calibrate: sample_feeds yielded no batches")
        for name in act_names:
            if strategy == "abs_max":
                acts[name] = max(maxima[name], _EPS)
            else:
                allv = np.concatenate(samples[name])
                if allv.size > max_samples_per_tensor:
                    allv = allv[::max(
                        1, -(-allv.size // max_samples_per_tensor))]
                acts[name] = max(
                    float(np.percentile(allv, percentile)), _EPS)

    _metrics.counter("quant/calib_tensors").inc(len(acts) + len(weights))
    return CalibrationTable(acts=acts, weights=weights, strategy=strategy,
                            percentile=percentile
                            if strategy == "percentile" else None)


# ---------------------------------------------------------------------------
# config + activation
# ---------------------------------------------------------------------------


class QuantConfig:
    """Resolved quantization policy consumed by the `quant_rewrite`
    pass. mode ``weight_only``: int8 weight store, dequantize-on-use,
    fp32 compute (no table needed). mode ``full_int8``: activations
    quantize per-tensor against the calibration table and the dot/conv
    executes int8×int8→int32; an op whose activation has no table entry
    degrades to weight_only for that op. `blacklist` names (any input or
    output var) pin their ops fp32."""

    def __init__(self, mode="weight_only", table=None, ops=None,
                 blacklist=None):
        mode = str(mode)
        if mode not in MODES:
            raise ValueError("quant mode must be one of %s, got %r"
                             % (MODES, mode))
        if table is not None and not isinstance(table, CalibrationTable):
            table = coerce_table(table)
        self.mode = mode
        self.table = table
        self.ops = _check_ops(ops or DEFAULT_QUANT_OPS)
        self.blacklist = frozenset(blacklist or ())

    def cache_key(self):
        """Short stable digest for the compile-cache pipeline key."""
        h = hashlib.sha1()
        h.update(repr((self.mode, sorted(self.ops),
                       sorted(self.blacklist),
                       self.table.digest() if self.table is not None
                       else None)).encode())
        return "%s:%s" % (self.mode, h.hexdigest()[:8])


# saved-table files resolved from PTPU_QUANT_TABLE sit on the per-run
# cache-key path (pipeline_key -> active_config): cache the parsed table
# per (mtime, size) so steady-state runs never re-read or re-parse it
_TABLE_CACHE = {}


def _load_table_cached(path):
    path = str(path)
    try:
        st = os.stat(path)
        sig = (st.st_mtime_ns, st.st_size)
    except OSError:
        # table file moved/deleted mid-run: keep serving the already-
        # loaded table so compiled-and-cached steps stay usable
        hit = _TABLE_CACHE.get(path)
        if hit is not None:
            return hit[1]
        raise
    hit = _TABLE_CACHE.get(path)
    if hit is not None and hit[0] == sig:
        return hit[1]
    table = CalibrationTable.load(path)
    _TABLE_CACHE[path] = (sig, table)
    return table


def coerce_table(table):
    """CalibrationTable from a table object, a dict, or a JSON path
    (paths are cached by mtime+size — env-activated compiles resolve
    the table on every cache-key computation)."""
    if table is None or isinstance(table, CalibrationTable):
        return table
    if isinstance(table, dict):
        return CalibrationTable.from_dict(table)
    return _load_table_cached(table)


def quant_env_enabled():
    return bool(_env("PTPU_QUANT"))


def _env_config():
    blk = _env("PTPU_QUANT_BLACKLIST")
    return QuantConfig(
        mode=_env("PTPU_QUANT_MODE"),
        table=coerce_table(_env("PTPU_QUANT_TABLE")),
        blacklist=[s.strip() for s in blk.split(",") if s.strip()]
        if blk else None)


def active_config(program=None, build_strategy=None):
    """The quantization config in effect for one compile, or None.
    Precedence: program decoration (`decorate`) > PTPU_QUANT=1. A
    program carrying ``_quant_disable`` (the calibration clone) is
    always un-quantized."""
    if program is not None and getattr(program, "_quant_disable", False):
        return None
    cfg = getattr(program, "_quant_config", None) if program is not None \
        else None
    if cfg is not None:
        return cfg
    if quant_env_enabled():
        return _env_config()
    return None


def decorate(program, mode="weight_only", table=None, ops=None,
             blacklist=None):
    """Pin a quantization policy on `program`: every subsequent compile
    of it (executor, CompiledProgram, AnalysisPredictor) schedules the
    `quant_rewrite` pass with this config. Returns the program."""
    program._quant_config = QuantConfig(mode=mode, table=table, ops=ops,
                                        blacklist=blacklist)
    return program


# ---------------------------------------------------------------------------
# the rewrite pass
# ---------------------------------------------------------------------------


@register_pass("quant_rewrite")
class QuantRewritePass(Pass):
    """Rewrite white-list ops to int8 execution on the compile clone.
    Soundness:

      - only forward, non-grad-referenced ops with a persistable,
        never-rewritten fp32 weight operand are touched — training
        programs keep their exact graph (grad ops re-run forward
        kernels; an int8 dot has no useful vjp);
      - the op's ORIGINAL output var keeps its name, declared dtype and
        write position — consumers, fetches and reaching-def reasoning
        are untouched; only fresh vars (int8 activation, int8 weight,
        int32 accumulator, scale constants) are introduced;
      - int8 weights and their fp32 scales bake as fresh
        content-addressed persistable scope entries via the PR-3
        machinery (`bake_value` + `state_fallback`), so cached compiled
        steps stay scope-portable and the original fp32 parameters are
        never overwritten;
      - activation quantize ops are deduped per (source, reaching
        definition), weight dequantize ops per weight name.
    """

    def apply(self, program, scope=None):
        cfg = active_config(program)
        if cfg is None or scope is None:
            return program
        from . import unique_name
        from .framework import Operator, convert_dtype
        from .ir_passes import (_fetch_targets, _write_indices, bake_value)

        targets = _fetch_targets(program)
        if targets is None:
            # fetch set unknown (standalone apply): pin
            # program._opt_fetch_targets to run this pass standalone
            return program
        block = program.global_block()
        writes = _write_indices(block)

        def rdef(name, i):
            last = -1
            for w in writes.get(name, ()):
                if w < i:
                    last = w
                else:
                    break
            return last

        sites = {id(op): (a, w)
                 for op, a, w in _quantizable_sites(program, cfg.ops)}
        table = cfg.table
        quant_cache = {}   # (act name, reaching def) -> int8 Variable
        deq_cache = {}     # weight layout key -> dequantized fp32 Var
        baked_w = {}       # weight layout key -> (int8 var, scales, sb,
        #                    fp32 value) — keyed per LAYOUT, not per
        #                    name: a weight shared by consumers with
        #                    different channel axes (matmul vs its
        #                    transpose_Y twin, conv vs mul) must not
        #                    reuse the other layout's scales
        new_ops = []
        rewritten = 0
        stats = {"saved": 0, "fp32": 0}
        counted = set()  # weight NAMES in the byte stats — a shared
        # weight baked under two layouts still has ONE fp32 original
        # (the saved-ratio denominator must not double-count it)

        def wkey(op, w):
            if op.type == "mul":
                return (w.name, "mul",
                        int(op.attrs.get("y_num_col_dims", 1)))
            if op.type == "matmul":
                return (w.name, "matmul",
                        bool(op.attrs.get("transpose_Y")))
            return (w.name, "conv")

        def bake_const(name, arr, dtype):
            """Fresh content-addressed persistable scope entry (PR-3
            bake machinery — existing names are never overwritten)."""
            digest = hashlib.sha1(
                arr.tobytes() + repr((name, arr.shape,
                                      str(arr.dtype))).encode()
            ).hexdigest()[:12]
            fname = "__quant__.%s.%s" % (digest, name)
            if not block.has_var(fname):
                block.create_var(name=fname, shape=arr.shape, dtype=dtype,
                                 persistable=True)
            scope.set(fname, arr)
            bake_value(program, fname, arr)
            return block.var(fname)

        def quantized_weight(op, w):
            key = wkey(op, w)
            hit = baked_w.get(key)
            if hit is not None:
                return hit
            val = np.asarray(scope.get(w.name), np.float32)
            scales, axis = weight_channel_scales(val, op.type, op.attrs)
            if table is not None and table.weight_scales(w.name) \
                    is not None:
                ts, taxis = table.weight_scales(w.name)
                if taxis == axis and ts.size == scales.size:
                    scales = ts
            # scale broadcast shape along the channel axis; the trailing
            # output-feature axes of `mul` may span several dims — the
            # flattened per-column vector reshapes onto them
            if op.type == "mul":
                yn = int(op.attrs.get("y_num_col_dims", 1))
                sb = scales.reshape((1,) * yn + val.shape[yn:])
            else:
                bshape = [1] * val.ndim
                bshape[axis] = scales.size
                sb = scales.reshape(bshape)
            q = quantize_to_int8(val, sb)
            qv = bake_const(w.name + ".int8", q, "int8")
            if w.name not in counted:
                # int8 twin + fp32 per-channel scales vs the fp32
                # original: the step's device weight store shrinks by
                # this (once per weight, however many layouts bake)
                counted.add(w.name)
                stats["saved"] += max(val.nbytes - (q.nbytes
                                                    + scales.size * 4),
                                      0)
                stats["fp32"] += val.nbytes
            out = (qv, scales, sb, val)
            baked_w[key] = out
            return out

        for i, op in enumerate(block.ops):
            site = sites.get(id(op))
            if site is None:
                new_ops.append(op)
                continue
            a, w = site
            names = (set(op.input_names()) | set(op.output_names()))
            if names & cfg.blacklist:
                new_ops.append(op)
                continue
            aslot, wslot = _SLOTS[op.type]
            out_slot = "Output" if op.type.startswith(
                ("conv", "depthwise")) else "Out"
            outs = op.outputs.get(out_slot, [])
            if len(outs) != 1 \
                    or convert_dtype(outs[0].dtype) != "float32":
                new_ops.append(op)
                continue
            if scope.get(w.name) is None:
                new_ops.append(op)
                continue

            full = (cfg.mode == "full_int8" and table is not None
                    and table.act_scale(a.name) is not None
                    and not getattr(a, "persistable", False)
                    # int8 matmul constraints: plain 2-D dot, no alpha
                    # (declared rank — no host materialization here)
                    and (op.type != "matmul"
                         or (op.attrs.get("alpha", 1.0) == 1.0
                             and w.shape is not None
                             and len(w.shape) == 2))
                    # FoldedBias lands on the fp32 conv output — an
                    # int32 accumulator cannot absorb it
                    and not op.inputs.get("FoldedBias"))

            qv, scales, sb, val = quantized_weight(op, w)

            # full-int8 dense layers (mul / plain matmul) fuse the whole
            # quantize -> int8 dot -> dequantize chain into ONE op when
            # the Pallas int8 kernel's dispatch policy has it on
            # (ops/kernel_registry.enabled_for — an emission-time mode+
            # platform decision, so kernels-off programs are op-for-op
            # the historical 3-op emission): the standalone
            # quantize/dequantize_linear HLOs around the dot vanish from
            # the lowered module
            fuse = full and op.type in ("matmul", "mul") \
                and not op.attrs.get("transpose_X", False) \
                and not op.attrs.get("transpose_Y", False) \
                and _kernel_enabled("int8_matmul")

            if fuse:
                s_a = float(table.act_scale(a.name))
                out = outs[0]
                # flat per-output-channel combined scale: the op impl
                # flattens mul's operands to 2-D the same way the mul
                # op does, so the kernel always sees an [N] vector
                dq = (np.asarray(scales).reshape(-1) / _QMAX) \
                    * (s_a / _QMAX)
                dqv = bake_const(out.name + ".qdq",
                                 np.asarray(dq, np.float32), "float32")
                fattrs = {"act_scale": _QMAX / max(s_a, _EPS),
                          "__quant__": True}
                if op.type == "mul":
                    fattrs["x_num_col_dims"] = int(
                        op.attrs.get("x_num_col_dims", 1))
                    fattrs["y_num_col_dims"] = int(
                        op.attrs.get("y_num_col_dims", 1))
                new_ops.append(Operator(
                    block, "fused_int8_matmul",
                    inputs={"X": [a], "Y": [qv], "Scale": [dqv]},
                    outputs={"Out": [out]},
                    attrs=fattrs))
            elif full:
                s_a = float(table.act_scale(a.name))
                qa_key = (a.name, rdef(a.name, i))
                qa = quant_cache.get(qa_key)
                if qa is None:
                    qa = block.create_var(
                        name=unique_name.generate(a.name + "@quant.int8"),
                        shape=a.shape, dtype="int8", persistable=False)
                    new_ops.append(Operator(
                        block, "quantize", inputs={"Input": [a]},
                        outputs={"Output": [qa]},
                        attrs={"Scale": _QMAX / max(s_a, _EPS),
                               "__quant__": True}))
                    quant_cache[qa_key] = qa
                out = outs[0]
                acc = block.create_var(
                    name=unique_name.generate(out.name + "@quant.acc"),
                    shape=out.shape, dtype="int32", persistable=False)
                # combined dequant scale, shaped to broadcast onto the
                # op's OUTPUT: trailing feature dims for mul/matmul, the
                # (C_out, 1, 1) channel axis for NCHW conv
                if op.type in ("conv2d", "depthwise_conv2d"):
                    dq = (scales.reshape((-1, 1, 1)) / _QMAX) \
                        * (s_a / _QMAX)
                elif op.type == "mul":
                    yn = int(op.attrs.get("y_num_col_dims", 1))
                    dq = (scales.reshape(val.shape[yn:]) / _QMAX) \
                        * (s_a / _QMAX)
                else:  # matmul
                    dq = (scales / _QMAX) * (s_a / _QMAX)
                dqv = bake_const(out.name + ".qdq",
                                 np.asarray(dq, np.float32), "float32")
                op.inputs[aslot] = [qa]
                op.inputs[wslot] = [qv]
                op.outputs[out_slot] = [acc]
                op.attrs["__quant_int8__"] = True
                new_ops.append(op)
                new_ops.append(Operator(
                    block, "dequantize_linear",
                    inputs={"Input": [acc], "Scale": [dqv]},
                    outputs={"Output": [out]},
                    attrs={"out_dtype": "float32", "__quant__": True}))
            else:
                dqw = deq_cache.get(wkey(op, w))
                if dqw is None:
                    sv = bake_const(w.name + ".qscale",
                                    np.asarray(sb / _QMAX, np.float32),
                                    "float32")
                    dqw = block.create_var(
                        name=unique_name.generate(w.name + "@quant.deq"),
                        shape=w.shape, dtype="float32",
                        persistable=False)
                    new_ops.append(Operator(
                        block, "dequantize_linear",
                        inputs={"Input": [qv], "Scale": [sv]},
                        outputs={"Output": [dqw]},
                        attrs={"out_dtype": "float32",
                               "__quant__": True}))
                    deq_cache[wkey(op, w)] = dqw
                op.inputs[wslot] = [dqw]
                new_ops.append(op)
            rewritten += 1

        if not rewritten:
            return program
        block.ops = new_ops
        _metrics.counter("quant/ops_rewritten").inc(rewritten)
        record_weight_store(len(counted), stats["saved"], stats["fp32"])
        program._bump_version()
        return program


# ---------------------------------------------------------------------------
# predictor integration (inference.AnalysisPredictor load-time hook)
# ---------------------------------------------------------------------------


def quantize_predictor_program(program, scope, mode="weight_only",
                               table=None, blacklist=None):
    """Load-time quantization for a freshly loaded predictor program
    with its own private scope (docs/QUANTIZATION.md):

      weight_only  rides ``QuantizeTranspiler.convert_to_int8`` — the
                   fp32 weights are REPLACED by int8 twins in the scope
                   (the store genuinely halves-plus) and prepended
                   ``dequantize`` ops reconstruct them on use;
      full_int8    decorates the program so the compile pipeline's
                   `quant_rewrite` pass emits the int8 execution path
                   (requires a calibration `table` for the activation
                   ranges; ops it cannot calibrate fall back to
                   weight-only).

    Destructive scope edits are safe here exactly because the predictor
    owns both the program and the scope (the same argument that lets
    the load-time conv_bn fold edit weights)."""
    if mode == "weight_only":
        from .contrib.quantize import QuantizeTranspiler

        QuantizeTranspiler().convert_to_int8(program, scope=scope,
                                             skip=blacklist or ())
    elif mode == "full_int8":
        decorate(program, mode=mode, table=coerce_table(table),
                 blacklist=blacklist)
    else:
        raise ValueError("quant mode must be one of %s, got %r"
                         % (MODES, mode))
    return program
