"""Decode-side transformer for the serving runtime.

The serving engine does not re-run the training program descriptor per
token — generation wants one *fixed-shape* decode step (one token per
active batch slot, cache reads/writes through block tables) that XLA
compiles exactly once. This module holds that step and the bridge from
the training world into it:

  * ``GenerationConfig`` — the decoder-only architecture hyperparameters
    (the shape of ``models/transformer_fluid.build``: pre-LN blocks,
    fused QKV, gelu FFN, sinusoidal position encoding, untied LM head).
  * ``extract_decoder_weights(program, scope)`` — walks a Fluid program
    built by ``transformer_fluid.build`` (remat=False, dropout=0) and
    lifts its parameters out of the scope into the serving weight
    layout. This is what ``inference.export_generation_model`` calls.
  * ``GenerationModel`` — config + weights; ``make_decode_step`` builds
    the jitted continuous-batching decode step over a ``KVBlockPool``.
  * ``reference_decode`` — an unbatched, unpaged greedy decoder over a
    contiguous cache; the correctness oracle the tests pin the paged
    batched step against token-for-token.

The decode step's calling convention (all shapes fixed per engine):

    step(weights, kv_k, kv_v, prompt_feed, use_prompt, prev_tokens,
         positions, block_tables, active)
      -> (kv_k', kv_v', next_tokens)

``prev_tokens`` is the *device* token vector the previous step returned:
decode-phase slots chain their input token on device (the host never
has to materialize a step before dispatching the next — the PR-2
async-window contract). A slot under ``use_prompt`` takes its token
from ``prompt_feed`` instead: the one-token window of a prompt (the
engine prefills through the chunk step below and feeds these all-false;
a caller stepping the model by hand may prefill through them). Inactive
slots route their cache writes to the pool's null block and their
outputs are ignored.

``make_prefill_step`` is the second step shape, the engine's prefill
(Sarathi-style mixed batches, docs/SERVING.md): every row carries a
``[chunk]`` token window — prefill rows consume up to ``chunk`` prompt
tokens per call (writing that many KV slots, masked per row by
``lengths``), decode rows ride the same step as 1-token windows
chaining ``prev_tokens`` on device. Each engine geometry compiles
exactly TWO step shapes: this one and the one-token decode step.

``make_spec_step`` is the speculative-decoding **verify window**
(docs/SERVING.md): the same ``[max_batch, window]`` chunk shape, except
the target's greedy token comes back at EVERY window slot, so feeding
``[t0, d1..dk]`` (a row's last committed token plus ``k`` drafted
continuations) verifies all ``k`` drafts in one step. The matching
draft sources live here too: :class:`NGramDrafter` (prompt-lookup
drafting over the sequence's own prompt+output history — zero extra
weights) and :class:`ModelDrafter` (the pluggable draft-model hook
reusing :class:`GenerationModel`).
"""

import math
import time
from zipfile import BadZipFile as zipfile_BadZipFile

import numpy as np

__all__ = ["GenerationArtifactError", "GenerationConfig",
           "GenerationModel", "ModelDrafter",
           "NGramDrafter", "extract_decoder_weights",
           "parse_tree_shape", "random_weights", "reference_decode",
           "save_generation_artifact", "load_generation_artifact",
           "verify_generation_artifact", "tree_topology"]


def parse_tree_shape(spec):
    """Parse a ``PTPU_SERVE_SPEC_TREE`` value: ``"WxD"`` (e.g. ``"2x3"``
    = width 2, depth 3) -> ``(width, depth)``; empty/None/off -> None
    (tree speculation disabled, the PR-12 linear window)."""
    if not spec:
        return None
    if isinstance(spec, (tuple, list)):
        w, d = spec
    else:
        s = str(spec).strip().lower()
        if s in ("", "0", "off", "false", "no"):
            return None
        if "x" not in s:
            raise ValueError(
                "spec tree shape must look like 'WxD' (width x depth, "
                "e.g. '2x3'), got %r" % (spec,))
        w, d = s.split("x", 1)
    w, d = int(w), int(d)
    if w < 1 or d < 1:
        raise ValueError(
            "spec tree width and depth must be >= 1, got %dx%d" % (w, d))
    return w, d


def tree_topology(width, depth):
    """Static topology of the speculative token tree (docs/SERVING.md):
    ``width`` root-anchored chains of ``depth`` draft slots in
    LEVEL-ORDER layout, slot 0 the root (the row's last committed
    token). Level ``l`` (1-based) of chain ``c`` is slot
    ``1 + (l - 1) * width + c``; its parent is the same chain one level
    up (the root at ``l == 1``). Level order means any slot-prefix of
    the window is itself a valid (shallower) tree, so the per-row
    budget clamp reuses the window-length masking.

    Returns ``(parents, depths, anc)`` — int32 ``[C]``, int32 ``[C]``
    and bool ``[C, C]`` for ``C = 1 + width * depth``, with
    ``anc[j, t]`` true iff slot ``t`` is ``j`` or an ancestor of ``j``
    (slot ``j``'s in-window attention visibility: exactly its own root
    path, sibling branches mutually invisible)."""
    width, depth = int(width), int(depth)
    C = 1 + width * depth
    parents = np.zeros(C, np.int32)
    depths = np.zeros(C, np.int32)
    for level in range(1, depth + 1):
        for c in range(width):
            s = 1 + (level - 1) * width + c
            parents[s] = 0 if level == 1 else s - width
            depths[s] = level
    anc = np.zeros((C, C), bool)
    for s in range(C):
        anc[s, s] = True
        j = s
        while j:
            j = int(parents[j])
            anc[s, j] = True
    return parents, depths, anc

# serving-artifact file names (written by
# inference.export_generation_model next to the one-shot
# __serving__/__serving_native__ artifacts so native_serve and the
# continuous-batching engine deploy from ONE directory). The manifest
# (per-leaf sha256 digests + file-size inventory, written LAST) is the
# publish marker the atomic tmp+rename export leaves behind — a torn
# export is detected by the loader, never served.
GENERATION_WEIGHTS = "__generation__.npz"
GENERATION_META = "__generation_meta__.json"
GENERATION_MANIFEST = "__generation_manifest__.json"


def _kernel_key_suffix():
    """Step-cache key component for the Pallas kernel dispatch policy
    (ops/kernel_registry): a step traced under one PTPU_KERNELS mode
    must not serve another. Empty in the default (auto) state so
    pre-kernel cache keys stay bitwise identical."""
    from ..ops.kernel_registry import cache_key

    key = cache_key()
    return () if key == "auto" else ("kernels:" + key,)


# Query slots in one attention tile of the chunk step (docs/SERVING.md,
# "Chunked prefill"): a prefilling row's chunk is cut into tiles of this
# many consecutive tokens, a decode row is a tile of one. Settled by step
# timings on the chip (PERF.md §6, PR 28): at the benchmark's chunk of
# 256 a tile is a row's whole chunk, whose pages are then fetched once
# (a mixed step of 33.5 / 33.8 / 35.6 ms against 33.3 / 36.0 / 41.0 at
# tiles of 64, for windows of the batch, document and full-budget kind).
CHUNK_TILE = 256


def chunk_tile_count(max_batch, window, max_tokens=None, tile=CHUNK_TILE):
    """How many query tiles the chunk step's attention is compiled for:
    the most that ``max_batch`` rows holding ``max_tokens`` tokens
    between them can need, sum of ``ceil(n / tile)`` over the rows,
    which is ``max_batch + budget / tile`` under the engine's promise
    of ``max_batch + budget`` tokens; every slot's tile without one."""
    tile = min(int(tile), int(window))
    dense = max_batch * -(-window // tile)
    if max_tokens is None:
        return dense
    return min(dense, (max_tokens + max_batch * (tile - 1)) // tile)


def _chunk_layout(jnp, positions, lengths, active, block_tables, window,
                  rows, tile, n_tiles, block_size):
    """The index arithmetic of a chunk window whose per-token work runs
    over ``rows`` token rows: where each token row comes from, where its
    K/V go, and the window's query tiles. A dict of int32 arrays.

    With ``rows`` smaller than the window's ``B * window`` slots the
    real tokens are COMPACTED, in slot order (a row's tokens stay
    together and in order; slots past a row's length and inactive rows
    get no token row; token rows past the window's tokens are padding:
    ``live`` false, written to the null block). Otherwise token row
    ``b * window + c`` is slot ``(b, c)``.

    Tile ``n`` is up to ``tile`` consecutive tokens of one window row:
    ``tile_rows[n]`` their token rows, ``tile_tables[n]`` the row's
    block-table line, ``tile_pos[n]`` the first one's position,
    ``tile_len[n]`` how many (0: an unused tile); ``back`` finds a
    token row's slot among the ``n_tiles * tile`` tile slots."""
    i32 = jnp.int32
    B, Mb = block_tables.shape
    C, T, Cq = window, B * window, tile
    lens = jnp.where(active, jnp.clip(lengths, 0, C), 0).astype(i32)
    if rows < T:
        first = jnp.cumsum(lens) - lens           # a row's first token row
        held = (jnp.arange(C, dtype=i32)[None, :] < lens[:, None]).reshape(T)
        at = jnp.nonzero(held, size=rows, fill_value=T)[0].astype(i32)
        live = at < T
        at = jnp.minimum(at, T - 1)
    else:
        first = jnp.arange(B, dtype=i32) * C
        at = jnp.arange(T, dtype=i32)
        live = at % C < lens[at // C]
    row, col = at // C, at % C
    pos = positions[row] + col
    tiles_of = (lens + Cq - 1) // Cq              # tiles a window row fills
    tile_end = jnp.cumsum(tiles_of)
    tile_0 = tile_end - tiles_of                  # a row's first tile
    n = jnp.arange(n_tiles, dtype=i32)
    t_row = jnp.minimum(
        jnp.searchsorted(tile_end, n, side="right"), B - 1).astype(i32)
    t_off = (n - tile_0[t_row]) * Cq              # first slot in its row
    return {
        "at": at, "live": live, "pos": pos,
        "write_blk": jnp.where(live, block_tables[
            row, jnp.clip(pos // block_size, 0, Mb - 1)], 0),
        "slot_idx": pos % block_size,
        "last": jnp.clip(first + lens - 1, 0, rows - 1),
        "tile_rows": jnp.clip(
            (first[t_row] + t_off)[:, None]
            + jnp.arange(Cq, dtype=i32)[None, :], 0, rows - 1),
        "tile_tables": block_tables[t_row],
        "tile_pos": positions[t_row] + t_off,
        "tile_len": jnp.where(n < tile_end[-1],
                              jnp.clip(lens[t_row] - t_off, 0, Cq), 0),
        "back": jnp.clip((tile_0[row] + col // Cq) * Cq + col % Cq,
                         0, n_tiles * Cq - 1)}


# The decoder blocks beside XGLM's own, by ``block.kind``: the module
# (imported when a configuration first names the kind, so a server of
# another block never loads it) and the class that describes one. A
# block's description answers for everything that differs by block:
# ``leaf_shapes(config)``, ``random_weights(config, seed, scale)``,
# ``cache_entry()``, ``page_kinds(config)``, ``step_counters``,
# ``make_decode_step(model, return_logits)`` and
# ``make_window_step(model, window, return_logits, max_tokens)``.
BLOCK_KINDS = {"latent_moe": ("latent_moe", "LatentMoEBlock"),
               "afmoe": ("afmoe", "AfmoeBlock"),
               "zaya": ("zaya", "ZayaBlock"),
               "ling": ("ling", "LingBlock")}


def block_from_dict(d):
    """A block description from its ``to_dict()`` (``kind`` names the
    class; a dict without one is the latent block's, as first written)."""
    import importlib

    kind = d.get("kind", "latent_moe")
    if kind not in BLOCK_KINDS:
        raise ValueError("unknown decoder block kind %r (have %s)"
                         % (kind, sorted(BLOCK_KINDS)))
    module, name = BLOCK_KINDS[kind]
    return getattr(importlib.import_module("." + module, __package__),
                   name).from_dict(d)


class GenerationConfig:
    """Decoder-only LM hyperparameters (transformer_fluid.build shape).

    ``block`` is the ONE description of the decoder block that selects
    the forwards, the weight layout and the cache entry: ``None`` is the
    pre-LN multi-head block of ``transformer_fluid.build`` (XGLM's); a
    :class:`~paddle_tpu.serving.latent_moe.LatentMoEBlock` (or its
    ``to_dict()``) is the latent-attention / routed-expert block, whose
    dense width is ``d_ff`` and which ignores ``pe_alpha``/``pe_beta``
    (rotary positions)."""

    def __init__(self, vocab_size, d_model, n_heads, n_layers, d_ff,
                 max_seq_len=512, pe_alpha=1.0, pe_beta=1.0, block=None):
        if isinstance(block, dict):
            block = block_from_dict(block)
        self.block = block
        if d_model % n_heads and getattr(block, "head_dim", None) is None:
            raise ValueError("n_heads must divide d_model")
        self.vocab_size = int(vocab_size)
        self.d_model = int(d_model)
        self.n_heads = int(n_heads)
        self.n_layers = int(n_layers)
        self.d_ff = int(d_ff)
        self.max_seq_len = int(max_seq_len)
        self.pe_alpha = float(pe_alpha)
        self.pe_beta = float(pe_beta)

    @property
    def head_dim(self):
        """A block that states its own head (grouped-query attention:
        ``n_heads * head_dim`` need not be ``d_model``) is asked."""
        own = getattr(self.block, "head_dim", None)
        return own if own is not None else self.d_model // self.n_heads

    def to_dict(self):
        d = {k: getattr(self, k) for k in
             ("vocab_size", "d_model", "n_heads", "n_layers", "d_ff",
              "max_seq_len", "pe_alpha", "pe_beta")}
        if self.block is not None:   # absent: artifacts stay as written
            d["block"] = self.block.to_dict()
        return d

    @classmethod
    def from_dict(cls, d):
        return cls(**d)


# weight-name layout (one flat dict; per-layer names carry an l<i>/
# prefix). `leaf_shapes` states each leaf's shape and storage dtype.
_LAYER_KEYS = ("ln1_scale", "ln1_bias", "wqkv", "bqkv", "wproj", "bproj",
               "ln2_scale", "ln2_bias", "wff1", "bff1", "wff2", "bff2")
# a layer's leaves that are only ever the weight operand of a
# default-precision dot (`_layers`: `a @ w`), as `lm_head` is: stored as
# that dot rounds them. With the gathered embedding they are also what
# `quantized()` puts on the int8 grid.
_DOT_OPERAND_KEYS = ("wqkv", "wproj", "wff1", "wff2")


def default_dot_rounds_to_bf16():
    """Whether a default-precision float32 dot, on the device this is
    traced (or built) for, is one bf16 MXU pass with float32
    accumulation: the TPU, unless the user raised
    ``jax_default_matmul_precision``. Elsewhere the backend multiplies
    float32 as it is. What the platform fixes, read in one place."""
    import jax

    from ..core import device

    asked = jax.config.jax_default_matmul_precision
    try:
        one_pass = asked is None or \
            jax.lax.Precision(asked) == jax.lax.Precision.DEFAULT
    except ValueError:       # a dot-algorithm preset: the user chose
        one_pass = False
    return one_pass and device.on_tpu()


def dot_operand_dtype():
    """The dtype a default-precision float32 dot hands the multiplier:
    ``bfloat16`` where :func:`default_dot_rounds_to_bf16`, ``float32``
    elsewhere. THE one place that decides the XGLM block's
    weight-operand storage (docs/SERVING.md, "What the store holds and
    why"): a weight kept in this dtype is the value the dot computed
    with anyway, at half the bytes to stream."""
    return "bfloat16" if default_dot_rounds_to_bf16() else "float32"


def leaf_shapes(config):
    """{weight name: (shape, dtype name)}: the serving layout, and what
    ``GenerationModel`` stores each leaf as. XGLM's block: the dot
    operands in :func:`dot_operand_dtype`, everything else float32 (the
    embedding is gathered and scaled, never multiplied on the MXU;
    LayerNorm gains and every bias are added in float32)."""
    if config.block is not None:
        return config.block.leaf_shapes(config)
    D, F, V = config.d_model, config.d_ff, config.vocab_size
    shape = {"wqkv": (D, 3 * D), "bqkv": (3 * D,), "wproj": (D, D),
             "wff1": (D, F), "bff1": (F,), "wff2": (F, D)}
    w, f32 = dot_operand_dtype(), "float32"
    out = {"embedding": ((V, D), f32), "lm_head": ((D, V), w),
           "final_ln_scale": ((D,), f32), "final_ln_bias": ((D,), f32)}
    for i in range(config.n_layers):
        for k in _LAYER_KEYS:
            out["l%d/%s" % (i, k)] = (
                shape.get(k, (D,)), w if k in _DOT_OPERAND_KEYS else f32)
    return out


def dot_operand_names(config):
    """The leaves a step multiplies on the MXU (its weight stream), for
    any block: every matrix but the gathered embedding, which a block
    whose head is TIED to it multiplies too."""
    tied = getattr(config.block, "tied_head", False)
    return [n for n, (shape, _d) in leaf_shapes(config).items()
            if len(shape) > 1 and (tied or n != "embedding")]


def weight_names(config):
    return list(leaf_shapes(config))


def store_leaf(value, dtype):
    """``value`` on the device as the store keeps it: cast to ``dtype``
    THERE (XLA's convert rounds to nearest even), so neither a second
    host copy nor, leaf by leaf, a second copy of the model is held. An
    int8 payload (the weight-only store) stays what it is."""
    import jax.numpy as jnp

    value = jnp.asarray(value)
    if "int8" in (str(value.dtype), str(dtype)):
        return value
    return value.astype(dtype)


def _position_encoding_table(config):
    """The exact ``add_position_encoding`` kernel table
    (ops/nn_ops.py): pe[t] = [sin(t/10000^(2i/d)) | cos(...)]."""
    d = config.d_model
    pos = np.arange(config.max_seq_len)[:, None]
    i = np.arange(d // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * i / d)
    return np.concatenate([np.sin(angle), np.cos(angle)],
                          axis=1).astype(np.float32)


def random_weights(config, seed=0, scale=0.1):
    """Deterministic random weights (tests/bench: a servable model with
    no training program behind it)."""
    if config.block is not None:
        return config.block.random_weights(config, seed, scale)
    rng = np.random.RandomState(seed)
    D, F, V = config.d_model, config.d_ff, config.vocab_size

    def w(*shape):
        return (rng.randn(*shape) * scale).astype(np.float32)

    weights = {
        "embedding": w(V, D),
        "lm_head": w(D, V),
        "final_ln_scale": np.ones(D, np.float32),
        "final_ln_bias": np.zeros(D, np.float32),
    }
    for i in range(config.n_layers):
        p = "l%d/" % i
        weights[p + "ln1_scale"] = np.ones(D, np.float32)
        weights[p + "ln1_bias"] = np.zeros(D, np.float32)
        weights[p + "wqkv"] = w(D, 3 * D)
        weights[p + "bqkv"] = np.zeros(3 * D, np.float32)
        weights[p + "wproj"] = w(D, D)
        weights[p + "bproj"] = np.zeros(D, np.float32)
        weights[p + "ln2_scale"] = np.ones(D, np.float32)
        weights[p + "ln2_bias"] = np.zeros(D, np.float32)
        weights[p + "wff1"] = w(D, F)
        weights[p + "bff1"] = np.zeros(F, np.float32)
        weights[p + "wff2"] = w(F, D)
        weights[p + "bff2"] = np.zeros(D, np.float32)
    return weights


# ---------------------------------------------------------------------------
# extraction from a transformer_fluid.build program
# ---------------------------------------------------------------------------


def extract_decoder_weights(program, scope, max_seq_len=None):
    """Lift the decoder weights out of a program built by
    ``models.transformer_fluid.build(remat=False, dropout_rate=0)`` (the
    bench/CI flagship configuration) into the serving layout.

    The walker is positional over op *types*, so it is insensitive to the
    interleaved elementwise/reshape plumbing: embeddings come from the
    ``lookup_table`` op, per-layer weights from the in-order sequence of
    ``layer_norm`` / ``fused_multihead_attention`` / parameter ``mul``
    ops, and the LM head from the (chunk-shared) ``lm_head_w`` matmuls.
    Returns ``(GenerationConfig, weights_dict)`` with everything cast to
    fp32.
    """
    block = program.global_block()

    def _is_param(name):
        v = block._find_var_recursive(name)
        return v is not None and getattr(v, "persistable", False)

    def _val(name):
        val = scope.get(name)
        if val is None:
            raise RuntimeError(
                "parameter %r has no value — run the startup program "
                "before exporting" % name)
        return np.asarray(val, np.float32)

    emb = None
    pe_alpha = pe_beta = 1.0
    lns, atts, muls = [], [], []
    pending_mul = None
    for op in block.ops:
        if op.type == "lookup_table" and emb is None:
            emb = op.inputs["W"][0].name
        elif op.type == "add_position_encoding":
            pe_alpha = op.attrs.get("alpha", 1.0)
            pe_beta = op.attrs.get("beta", 1.0)
        elif op.type == "layer_norm":
            lns.append((op.inputs["Scale"][0].name,
                        op.inputs["Bias"][0].name))
        elif op.type == "fused_multihead_attention":
            atts.append({k: v[0].name for k, v in op.inputs.items()
                         if k != "X"})
        elif op.type == "mul" and _is_param(op.inputs["Y"][0].name):
            pending_mul = [op.inputs["Y"][0].name, None]
            muls.append(pending_mul)
        elif (op.type == "elementwise_add" and pending_mul is not None
              and _is_param(op.inputs["Y"][0].name)):
            pending_mul[1] = op.inputs["Y"][0].name
            pending_mul = None
        elif op.type == "recompute":
            raise NotImplementedError(
                "export_generation_model walks the flat op list — build "
                "the program with transformer_fluid.build(remat=False)")

    if emb is None or not atts:
        raise ValueError(
            "program does not look like transformer_fluid.build output "
            "(no embedding / fused_multihead_attention ops found)")
    L = len(atts)
    if len(lns) != 2 * L + 1:
        raise ValueError(
            "expected %d layer_norm ops for %d layers, found %d — only "
            "the remat=False, dropout_rate=0 build is exportable"
            % (2 * L + 1, L, len(lns)))
    ffn_muls = muls[:2 * L]
    head_muls = muls[2 * L:]
    head_params = {m[0] for m in head_muls}
    if len(ffn_muls) != 2 * L or len(head_params) != 1:
        raise ValueError(
            "expected 2 FFN matmuls per layer plus one shared LM-head "
            "parameter; found %d muls over params %r"
            % (len(muls), sorted({m[0] for m in muls})))

    emb_w = _val(emb)
    V, D = emb_w.shape
    wq0 = _val(atts[0]["WQ"])
    H = wq0.shape[1]
    F = _val(ffn_muls[0][0]).shape[1]
    config = GenerationConfig(
        vocab_size=V, d_model=D, n_heads=H, n_layers=L, d_ff=F,
        max_seq_len=max_seq_len or 512, pe_alpha=pe_alpha,
        pe_beta=pe_beta)

    weights = {"embedding": emb_w,
               "lm_head": _val(next(iter(head_params))),
               "final_ln_scale": _val(lns[2 * L][0]),
               "final_ln_bias": _val(lns[2 * L][1])}
    if weights["lm_head"].shape != (D, V):
        raise ValueError("LM head shape %r != (d_model, vocab)"
                         % (weights["lm_head"].shape,))
    for i in range(L):
        p = "l%d/" % i
        att = atts[i]
        # [D, H, Dh] per-head projections -> fused [D, 3D] qkv matmul
        wq, wk, wv = (_val(att[k]).reshape(D, D)
                      for k in ("WQ", "WK", "WV"))
        weights[p + "wqkv"] = np.concatenate([wq, wk, wv], axis=1)
        bq, bk, bv = (_val(att[k]).reshape(D) if k in att
                      else np.zeros(D, np.float32)
                      for k in ("BQ", "BK", "BV"))
        weights[p + "bqkv"] = np.concatenate([bq, bk, bv])
        weights[p + "wproj"] = _val(att["WO"]).reshape(D, D)
        weights[p + "bproj"] = (_val(att["BO"]) if "BO" in att
                                else np.zeros(D, np.float32))
        weights[p + "ln1_scale"] = _val(lns[2 * i][0])
        weights[p + "ln1_bias"] = _val(lns[2 * i][1])
        weights[p + "ln2_scale"] = _val(lns[2 * i + 1][0])
        weights[p + "ln2_bias"] = _val(lns[2 * i + 1][1])
        for j, nm in ((0, "ff1"), (1, "ff2")):
            wname, bname = ffn_muls[2 * i + j]
            weights[p + "w" + nm] = _val(wname)
            weights[p + "b" + nm] = (
                _val(bname) if bname is not None
                else np.zeros(weights[p + "w" + nm].shape[1], np.float32))
    return config, weights


# ---------------------------------------------------------------------------
# serving artifact (weights npz + meta json)
# ---------------------------------------------------------------------------


class GenerationArtifactError(RuntimeError):
    """A generation artifact failed digest/inventory verification — a
    torn export (crash mid-write, injected `ckpt_torn_export`). The
    message names the artifact directory and the first mismatch, so
    the rollout ledger and the operator see the same structured
    story."""

    def __init__(self, dirname, reason):
        self.dirname = dirname
        self.reason = reason
        super().__init__(
            "generation artifact %s is torn or corrupt: %s — "
            "re-export it (inference.export_generation_model); it must "
            "never be served" % (dirname, reason))


def _weight_digest(arr):
    """sha256 over dtype + shape + host bytes (the checkpoint.py leaf
    digest, specialized to the flat fp32 serving layout)."""
    import hashlib

    arr = np.ascontiguousarray(arr)
    h = hashlib.sha256()
    h.update(str(arr.dtype).encode())
    h.update(repr(arr.shape).encode())
    h.update(arr.tobytes())
    return h.hexdigest()


def _fsync_file(path):
    import os

    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _fsync_dir(path):
    import os

    try:
        _fsync_file(path)
    except OSError:
        pass  # fsync on a dir is best-effort (not all filesystems)


def _maybe_tear_export(dirname):
    """`ckpt_torn_export` fault injection: after a publish lands,
    truncation-corrupt the weights payload in place — the torn export
    the digest manifest exists to catch (the checkpoint.py
    `ckpt_torn_write` pattern, at the serving-artifact layer)."""
    import os

    from ..resilience import global_injector

    if not global_injector().fire_occurrence("ckpt_torn_export"):
        return
    path = os.path.join(dirname, GENERATION_WEIGHTS)
    with open(path, "r+b") as f:
        data = f.read()
        if not data:
            return
        f.seek(0)
        f.write(bytes(b ^ 0xFF for b in data[: max(1, len(data) // 2)]))
        f.truncate(max(1, len(data) // 2))


def save_generation_artifact(dirname, config, weights):
    """Atomically publish the generation-serving artifact: one STORED
    npz of fp32 weights, a json config, and a digest manifest
    (per-weight sha256 + file-size inventory). Everything lands in a
    temp dir first; a fresh ``dirname`` is published by ONE rename,
    an existing one by per-file replaces with the manifest LAST (the
    completeness marker a crash mid-export never writes). Returns the
    npz path."""
    import json
    import os
    import shutil

    if config.block is not None:
        # this writer normalises every leaf to float32; a block that
        # states its storage dtype must not be written as something else
        raise NotImplementedError(
            "save_generation_artifact writes float32 leaves only: the %s "
            "block stores %s weights, and its artifact (the block "
            "description and per-leaf dtypes in the meta file) is not "
            "built yet (ROADMAP Queue 2a)"
            % (config.block.kind, config.block.weight_dtype))
    dirname = os.path.abspath(dirname)
    parent = os.path.dirname(dirname) or "."
    os.makedirs(parent, exist_ok=True)
    tmp = os.path.join(parent,
                       ".ptpu_tmp_" + os.path.basename(dirname))
    shutil.rmtree(tmp, ignore_errors=True)
    os.makedirs(tmp)
    # float32 on disk whatever the store keeps (a bfloat16 leaf widens
    # exactly, and the loader's store rounds it back to the same value)
    weights = {k: np.asarray(v, np.float32) for k, v in weights.items()}
    np.savez(os.path.join(tmp, GENERATION_WEIGHTS), **weights)
    with open(os.path.join(tmp, GENERATION_META), "w") as f:
        json.dump(config.to_dict(), f, indent=2, sort_keys=True)
    manifest = {
        "format": 1,
        "digests": {k: _weight_digest(v) for k, v in weights.items()},
        "files": {n: os.path.getsize(os.path.join(tmp, n))
                  for n in (GENERATION_WEIGHTS, GENERATION_META)},
    }
    with open(os.path.join(tmp, GENERATION_MANIFEST), "w") as f:
        json.dump(manifest, f, indent=1, sort_keys=True)
        f.flush()
        os.fsync(f.fileno())
    for n in (GENERATION_WEIGHTS, GENERATION_META):
        _fsync_file(os.path.join(tmp, n))
    if not os.path.exists(dirname):
        os.rename(tmp, dirname)
    else:
        # the directory already holds other artifacts (__serving__,
        # a prior generation export): replace per file, payloads
        # before the manifest — a crash in between leaves a digest
        # mismatch the loader reports, never a silently-torn read
        stale = os.path.join(dirname, GENERATION_MANIFEST)
        if os.path.exists(stale):
            os.remove(stale)
        for n in (GENERATION_WEIGHTS, GENERATION_META,
                  GENERATION_MANIFEST):
            os.replace(os.path.join(tmp, n), os.path.join(dirname, n))
        shutil.rmtree(tmp, ignore_errors=True)
    _fsync_dir(dirname)
    _fsync_dir(parent)
    _maybe_tear_export(dirname)
    return os.path.join(dirname, GENERATION_WEIGHTS)


def verify_generation_artifact(dirname):
    """Verify an exported artifact against its digest manifest: file
    inventory sizes plus per-weight sha256 over the loaded arrays.
    Raises :class:`GenerationArtifactError` naming the artifact on any
    mismatch. Returns True when verified, False for a legacy artifact
    with no manifest (nothing to verify against)."""
    import json
    import os

    mpath = os.path.join(dirname, GENERATION_MANIFEST)
    if not os.path.exists(mpath):
        return False
    try:
        with open(mpath) as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        raise GenerationArtifactError(dirname,
                                      "unreadable manifest (%s)" % e)
    for n, size in manifest.get("files", {}).items():
        p = os.path.join(dirname, n)
        if not os.path.exists(p):
            raise GenerationArtifactError(dirname, "missing file %s" % n)
        actual = os.path.getsize(p)
        if actual != int(size):
            raise GenerationArtifactError(
                dirname, "file %s is %d bytes, manifest says %d"
                % (n, actual, size))
    digests = manifest.get("digests", {})
    try:
        with np.load(os.path.join(dirname, GENERATION_WEIGHTS)) as z:
            names = set(z.files)
            if names != set(digests):
                raise GenerationArtifactError(
                    dirname, "weight set mismatch (%d stored vs %d in "
                    "manifest)" % (len(names), len(digests)))
            for k in sorted(names):
                if _weight_digest(z[k]) != digests[k]:
                    raise GenerationArtifactError(
                        dirname, "digest mismatch on weight %r" % k)
    except (OSError, ValueError, zipfile_BadZipFile) as e:
        raise GenerationArtifactError(dirname,
                                      "unreadable weights (%s)" % e)
    return True


def load_generation_artifact(dirname, name=None, quantize=None,
                             verify=True):
    """Load an exported generation artifact as a ready-to-serve
    :class:`GenerationModel`. ``quantize='weight_only'`` serves the SAME
    artifact with the int8 weight store (``GenerationModel.quantized``)
    — no re-export needed. Artifacts carrying a digest manifest are
    verified on load (``verify=False`` skips it); a torn export raises
    :class:`GenerationArtifactError` naming the artifact."""
    import json
    import os

    meta_path = os.path.join(dirname, GENERATION_META)
    if not os.path.exists(meta_path):
        raise FileNotFoundError(
            "%s has no %s — export with "
            "paddle_tpu.inference.export_generation_model"
            % (dirname, GENERATION_META))
    if verify:
        verify_generation_artifact(dirname)
    with open(meta_path) as f:
        config = GenerationConfig.from_dict(json.load(f))
    if config.block is not None:
        raise NotImplementedError(
            "%s describes a %s block: this loader reads float32 leaves "
            "only (ROADMAP Queue 2a)" % (dirname, config.block.kind))
    try:
        with np.load(os.path.join(dirname, GENERATION_WEIGHTS)) as z:
            weights = {k: z[k] for k in z.files}
    except (OSError, ValueError, zipfile_BadZipFile) as e:
        raise GenerationArtifactError(dirname,
                                      "unreadable weights (%s)" % e)
    model = GenerationModel(config, weights,
                            name=name or os.path.basename(dirname))
    if quantize:
        if quantize not in (True, "weight_only", "int8"):
            raise ValueError(
                "quantize=%r — the serving runtime supports the "
                "weight_only int8 store (docs/QUANTIZATION.md)"
                % (quantize,))
        model = model.quantized()
    return model


# ---------------------------------------------------------------------------
# the fixed-shape decode step
# ---------------------------------------------------------------------------


class GenerationModel:
    """Config + weights + the jitted continuous-batching decode step.

    ``weights`` holds every leaf on the device in the dtype
    ``leaf_shapes(config)`` states: for XGLM's block the dot operands
    as a default-precision dot rounds them (:func:`dot_operand_dtype`:
    bfloat16 on the TPU, float32 elsewhere), everything else float32.

    ``quantized()`` derives the weight-only-int8 variant
    (docs/QUANTIZATION.md): every matmul weight and the embedding
    (chosen by role: qkv, proj, ffn, lm head, embedding) is STORED int8
    with a per-output-channel fp32 scale riding in the same weights
    dict under ``<name>@qscale``, and
    the decode step dequantizes on use — the compute stays fp32, the
    HBM-resident weight store (what a memory-bandwidth-bound decode
    step actually streams) shrinks ~4x. Decoding a quantized model is
    token-identical to ``reference_decode`` over
    ``dequantized_weights()`` (its fp32 reference)."""

    def __init__(self, config, weights, name="model"):
        self.config = config
        self.name = name
        leaves = leaf_shapes(config)
        missing = [n for n in leaves if n not in weights]
        if missing:
            raise ValueError("missing weights: %s" % missing[:4])
        # every leaf in the dtype its block states (`leaf_shapes`), cast
        # on the device a leaf at a time: one that is already a device
        # array of it is taken as it is (no pass through the host: the
        # weights may be most of the chip). An int8 payload (the
        # weight-only store) stays int8 beside its float32 `@qscale`.
        self.weights = {}
        for k, (_shape, dtype) in leaves.items():
            self.weights[k] = store_leaf(weights[k], dtype)
            scale = weights.get(k + "@qscale")
            if scale is not None:
                self.weights[k + "@qscale"] = store_leaf(scale, "float32")
        self.weight_only_int8 = any(
            str(v.dtype) == "int8" for v in self.weights.values())
        # what a step streams through the MXU, as stored (the step log's
        # `weight_bytes` / `weight_params`)
        operands = [self.weights[n] for n in dot_operand_names(config)]
        self.dot_operand_params = sum(int(v.size) for v in operands)
        self.dot_operand_bytes = sum(
            int(v.size) * v.dtype.itemsize for v in operands)
        # python-trace counter: the body below only executes while jax
        # traces, so tests can pin "no retrace across join/retire"
        self.trace_count = 0
        self._steps = {}
        # every step builder below compiles through jax's on-disk cache
        from ..async_engine import setup_persistent_cache

        setup_persistent_cache()

    @classmethod
    def random(cls, config, seed=0, name="model"):
        return cls(config, random_weights(config, seed), name=name)

    def cache_entry(self):
        """What this model caches for one token in one layer
        (``kv_cache.CacheEntry``): the pool allocates from it."""
        from .kv_cache import CacheEntry

        if self.config.block is not None:
            return self.config.block.cache_entry()
        return CacheEntry.per_head(self.config.n_heads,
                                   self.config.head_dim)

    def page_kinds(self):
        """The kinds of page this model's layers keep their cache in
        (``kv_cache.PageKind``), or None: every layer keeps every
        position, one kind."""
        kinds = getattr(self.config.block, "page_kinds", None)
        return kinds(self.config) if kinds is not None else None

    def row_state(self):
        """What a batch row of this model carries from step to step
        beside its pages, ``(shape a row, dtype)``
        (``kv_cache.RowState``), or None: every block but one keeps all
        it needs of the past in its pages."""
        state = getattr(self.config.block, "row_state", None)
        return state(self.config) if state is not None else None

    @property
    def step_counters(self):
        """Names of the integers a compiled step of this model returns
        after its tokens, reduced on the device (the step log's fields
        of that name); the XGLM block has none."""
        block = self.config.block
        return () if block is None else block.step_counters

    @property
    def returns_top_logit(self):
        """Whether this model's decode and chunk steps return, after
        their tokens and counters, each row's chosen token's own logit
        (float32 ``[max_batch]``): the engine then keeps it beside the
        token (``GenerationRequest.top_logits``), which lets a caller
        hold the served arithmetic against a reference token by token."""
        return bool(getattr(self.config.block, "returns_top_logit", False))

    def _no_such_step(self, what):
        if self.config.block is not None:
            raise NotImplementedError(
                "%s is not built for the %s block: speculative, tree and "
                "draft steps over its cache are ROADMAP Queue 2a"
                % (what, self.config.block.kind))

    # -- weight-only int8 ---------------------------------------------------
    def quantized(self, name=None):
        """The weight-only-int8 variant of this model: 2-D matmul
        weights become int8 + ``@qscale`` per-output-channel scales;
        biases, layer norms and the model structure are untouched.
        Records quant/{weights_quantized,weight_bytes_saved,
        weight_fp32_bytes} telemetry.

        The latent/expert block has no int8 store yet (ROADMAP Queue
        2a) and says so."""
        from ..quant import quantize_symmetric, record_weight_store

        if self.config.block is not None:
            raise NotImplementedError(
                "quantized(): the %s block has no int8 weight store "
                "(ROADMAP Queue 2a)" % self.config.block.kind)
        if self.weight_only_int8:
            return self
        # chosen by role, not by what the store keeps them as: the dot
        # operands (float32 or bfloat16, `dot_operand_dtype`) and the
        # gathered embedding
        on_grid = set(dot_operand_names(self.config)) | {"embedding"}
        qw = {}
        n_q = saved = fp32 = 0
        for k, v in self.weights.items():
            if k in on_grid:
                # the shared symmetric int8 grid (paddle_tpu.quant) over
                # the STORED value, per output column (axis 1 of the
                # [in, out] layout; per d_model column for the [V, D]
                # embedding)
                w = np.asarray(v, np.float32)
                q, s = quantize_symmetric(w, channel_axis=1)
                qw[k] = q
                qw[k + "@qscale"] = (s / 127.0).astype(np.float32)
                n_q += 1
                saved += max(int(v.nbytes) - q.nbytes - s.nbytes, 0)
                fp32 += w.nbytes
            else:
                qw[k] = v
        record_weight_store(n_q, saved, fp32)
        return GenerationModel(self.config, qw,
                               name=name or self.name + ".int8")

    def dequantized_weights(self):
        """fp32 weights dict on the host: the int8 store multiplied back
        out, a bfloat16 leaf widened (exactly) — the model's numerics
        reference (a GenerationModel built from these decodes
        token-identically to this one)."""
        out = {}
        for k, v in self.weights.items():
            if k.endswith("@qscale"):
                continue
            w = np.asarray(v, np.float32)
            s = self.weights.get(k + "@qscale")
            out[k] = w * np.asarray(s) if s is not None else w
        return out

    def _w(self, jnp, weights, key):
        """One dot's weight operand: dequantize-on-use for the int8
        store (XLA fuses the convert+scale into the consuming dot); a
        float leaf as it is stored. A bfloat16 leaf (`leaf_shapes`)
        goes into its dot as it is (`_layers`: ``dot``): the product is
        the float32 dot at default precision it always was, and on the
        TPU XLA reads the bf16 leaf straight into the matmul fusion (no
        float32 copy in HBM: `tools/lowered_serving_steps.py`)."""
        s = weights.get(key + "@qscale")
        w = weights[key]
        return w.astype(jnp.float32) * s if s is not None else w

    def _layers(self, jnp, weights, x, kv_k, kv_v, write_blk, slot_idx,
                attend, pick=None):
        """The XGLM decoder, written once: every layer, then the head.
        x: ``[..., D]``, a token a row (``[B]`` decoding, ``[B, C]`` a
        verify window, ``[rows]`` a chunk step's compacted tokens). The
        steps hand in what differs: the page and slot each row's K/V go
        to (``write_blk``/``slot_idx``, x's leading shape), layer
        ``i``'s attention over the pool as written so far
        (``attend(i, q, kv_k, kv_v)`` -> ``[..., H * Dh]``), and
        ``pick(x)``, the rows whose logits are wanted (``None``: all).
        Returns (kv_k, kv_v, logits)."""
        import jax

        cfg = self.config
        H, Dh = cfg.n_heads, cfg.head_dim
        lead = x.shape[:-1]

        def ln(h, scale, bias):
            mu = jnp.mean(h, axis=-1, keepdims=True)
            var = jnp.mean((h - mu) ** 2, axis=-1, keepdims=True)
            return (h - mu) * jax.lax.rsqrt(var + 1e-5) * scale + bias

        # where this is traced for a device whose default-precision dot
        # rounds BOTH operands to bfloat16, a bf16 leaf meets its
        # activations rounded the same way in front of the dot: the
        # same product, as the MXU's own bf16 x bf16 pass with float32
        # accumulation (a mixed f32 x bf16 dot costs a 1,040-row chunk
        # step 3 ms more: PERF.md §6, PR 35). Anywhere else the dot
        # is `a @ w` as written
        narrow = default_dot_rounds_to_bf16()

        def dot(a, key):
            w = self._w(jnp, weights, key)
            if narrow and w.dtype == jnp.bfloat16:
                return jnp.matmul(a.astype(w.dtype), w,
                                  preferred_element_type=jnp.float32)
            return a @ w

        for i in range(cfg.n_layers):
            p = "l%d/" % i
            a = ln(x, weights[p + "ln1_scale"], weights[p + "ln1_bias"])
            qkv = dot(a, p + "wqkv") + weights[p + "bqkv"]
            q, k_new, v_new = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(lead + (H, Dh))
            k_new = k_new.reshape(lead + (H, Dh))
            v_new = v_new.reshape(lead + (H, Dh))
            with jax.named_scope("kv_write"):
                kv_k = kv_k.at[i, write_blk, slot_idx].set(k_new)
                kv_v = kv_v.at[i, write_blk, slot_idx].set(v_new)
            with jax.named_scope("attention"):
                ctx = attend(i, q, kv_k, kv_v)
                x = x + dot(ctx, p + "wproj") + weights[p + "bproj"]
            with jax.named_scope("ffn"):
                b2 = ln(x, weights[p + "ln2_scale"],
                        weights[p + "ln2_bias"])
                f = jax.nn.gelu(dot(b2, p + "wff1") + weights[p + "bff1"],
                                approximate=False)
                x = x + dot(f, p + "wff2") + weights[p + "bff2"]

        with jax.named_scope("head"):
            if pick is not None:
                x = pick(x)
            x = ln(x, weights["final_ln_scale"], weights["final_ln_bias"])
            return kv_k, kv_v, dot(x, "lm_head")

    def _forward_token(self, jnp, weights, x, positions, block_tables,
                       active, kv_k, kv_v):
        """One token through all layers. x: [B, D]; returns
        (kv_k, kv_v, logits[B, V])."""
        import jax

        cfg = self.config
        B = x.shape[0]
        H, Dh = cfg.n_heads, cfg.head_dim
        bs = kv_k.shape[2]
        max_ctx = block_tables.shape[1] * bs
        sm_scale = Dh ** -0.5

        blk_idx = positions // bs
        slot_idx = positions % bs
        # inactive slots scatter into the null block (never read back)
        write_blk = jnp.where(
            active,
            jnp.take_along_axis(block_tables, blk_idx[:, None],
                                axis=1)[:, 0],
            0)

        # one dispatch decision per forward (trace time), shared by all
        # layers: the decode kernel reads the pool's pages through the
        # block table itself, so the contiguous kv[block_tables] gather
        # below never materializes. Which kernel that is follows the
        # head's width (`paged_decode_attention`): at heads of whole
        # lane tiles one grid step a row that copies the row's own
        # pages, else the BlockSpec grid over every table slot.
        from ..ops.kernel_registry import choose as _choose_kernel

        use_paged = _choose_kernel("paged_decode", head_dim=Dh,
                                   block_size=bs)
        if use_paged:
            from ..ops import pallas_kernels as _pk

        # context-position validity: t <= position (the current token's
        # k/v are written before the gather, so self-attention sees them)
        t_ids = jnp.arange(max_ctx)[None, :]
        valid = t_ids <= positions[:, None]

        def attend(i, q, kv_k, kv_v):
            if use_paged:
                # The kernel gets the pool WHOLE and finds layer and page
                # itself. `kv_k[i]` here would make XLA copy the layer's
                # pages out of the pool for the custom call and lay them
                # out again, which cost more than the rest of the step
                # (docs/SERVING.md, "No kernel step slices the pool").
                ctx = _pk.paged_decode_attention(
                    kv_k, kv_v, q[:, None], block_tables,
                    positions[:, None], layer=i, sm_scale=sm_scale,
                    active=active)
                return ctx[:, 0].reshape(B, -1)
            # lax path: the layer's pages, then the paged gather
            # [B, Mb, bs, H, Dh] -> [B, max_ctx, H, Dh]. XLA fuses this
            # slice with the convert the dot wants (one pass, bf16 out on
            # the chip); one gather from the whole pool measured slower
            # (docs/SERVING.md).
            with jax.named_scope("kv_read"):
                k_ctx = kv_k[i][block_tables].reshape(B, max_ctx, H, Dh)
                v_ctx = kv_v[i][block_tables].reshape(B, max_ctx, H, Dh)
            scores = jnp.einsum("bhd,bthd->bht", q, k_ctx) * sm_scale
            scores = jnp.where(valid[:, None, :], scores, -jnp.inf)
            w = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
            w = w / jnp.sum(w, axis=-1, keepdims=True)
            return jnp.einsum("bht,bthd->bhd", w, v_ctx).reshape(B, -1)

        return self._layers(jnp, weights, x, kv_k, kv_v, write_blk,
                            slot_idx, attend)

    def make_decode_step(self, max_batch, max_blocks_per_seq,
                         return_logits=False):
        """Build (and cache) the jitted fixed-shape decode step for this
        engine geometry. The KV arrays are donated — updates alias
        in-place in device memory."""
        key = (int(max_batch), int(max_blocks_per_seq),
               bool(return_logits)) + _kernel_key_suffix()
        if key in self._steps:
            return self._steps[key]
        import jax
        import jax.numpy as jnp

        cfg = self.config
        if cfg.block is not None:
            jitted = self._instrument_step(
                "decode", cfg.block.make_decode_step(self, return_logits))
            self._steps[key] = jitted
            return jitted
        pe = jnp.asarray(_position_encoding_table(cfg))
        emb_scale = float(cfg.d_model) ** 0.5

        def decode_step(weights, kv_k, kv_v, prompt_feed, use_prompt,
                        prev_tokens, positions, block_tables, active):
            self.trace_count += 1
            tok = jnp.where(use_prompt, prompt_feed, prev_tokens)
            tok = jnp.clip(tok, 0, cfg.vocab_size - 1)
            # int8 embedding store: gather the int8 rows FIRST, then
            # dequantize the [B, D] slice — the full fp32 table is never
            # materialized
            emb = jnp.take(weights["embedding"], tok, axis=0)
            es = weights.get("embedding@qscale")
            if es is not None:
                emb = emb.astype(jnp.float32) * es
            x = (emb * emb_scale * cfg.pe_alpha
                 + cfg.pe_beta * jnp.take(pe, positions, axis=0))
            kv_k, kv_v, logits = self._forward_token(
                jnp, weights, x, positions, block_tables, active,
                kv_k, kv_v)
            next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if return_logits:
                return kv_k, kv_v, next_tokens, logits
            return kv_k, kv_v, next_tokens

        jitted = self._instrument_step("decode", jax.jit(
            decode_step, donate_argnums=(1, 2)))
        self._steps[key] = jitted
        return jitted

    def _instrument_step(self, kind, jitted):
        """With metrics enabled, wrap a jitted step so its first call
        compiles ahead of time (the executor's `_compile_instrumented`
        pattern) and the executable's XLA cost analysis lands in the
        exec/* gauges — serving cache misses get the same FLOPs/bytes
        receipts training steps do. Identity when metrics are off: the
        raw jitted function is returned and cached, zero wrapper frames
        on the default hot path."""
        from ..observability import metrics as _metrics

        if not _metrics.enabled():
            return jitted

        from ..observability import cost as _cost
        from ..observability import tracing as _tracing

        aot = []

        def step(*args):
            if not aot:
                with _tracing.span("serving_compile", kind=kind):
                    t0 = time.perf_counter()
                    compiled = jitted.lower(*args).compile()
                    _metrics.histogram(
                        "serving/step_compile_time").observe(
                        time.perf_counter() - t0)
                _cost.publish(compiled)
                aot.append(compiled)
            return aot[0](*args)

        return step

    def _forward_chunk(self, jnp, weights, x, pos2d, lengths,
                       block_tables, active, kv_k, kv_v,
                       all_slots=False, tree_anc=None, layout=None):
        """A ``[B, C]`` token window through all layers. x: [B, C, D];
        returns (kv_k, kv_v, logits[B, V]) — each row's logits at its
        LAST valid window slot (``lengths - 1``) — or, with
        ``all_slots=True`` (the speculative verify window), the logits
        at EVERY window slot: (kv_k, kv_v, logits[B, C, V]).

        ``tree_anc`` (bool ``[C, C]``, trace-time constant from
        :func:`tree_topology`) switches the in-window causal mask to
        TREE visibility: window slot ``j`` still writes its KV at cache
        position ``pos2d[b, j]`` (= pos + j, the linear slot layout the
        block tables already cover), but attends the committed prefix
        (cache positions before the window) plus only its OWN root path
        inside the window — sibling branches are mutually invisible, so
        one step verifies every branch of the token tree.

        ``layout`` (:func:`_chunk_layout`; the chunk step) hands the
        window over as TOKEN ROWS instead: x is ``[rows, D]``, every
        row one token (``pos2d`` is not used), and everything a token
        does alone runs over those rows. The attention sees the window
        as query tiles (the ``chunk_window`` kernel, or its lax
        fallback over the same tiles): nothing of ``[B, C, H, T]`` is
        made. The verify and tree windows, where nearly every slot is a
        token, keep the ``[B, C]`` window and their own kernels."""
        import jax

        cfg = self.config
        H, Dh = cfg.n_heads, cfg.head_dim
        bs = kv_k.shape[2]
        Mb = block_tables.shape[1]
        max_ctx = Mb * bs
        sm_scale = Dh ** -0.5
        lead = x.shape[:-1]            # [B, C], or [rows] of tokens

        # one kernel decision per forward (trace time), shared by all
        # layers; every kernel gets the pool whole, never `kv_k[i]`
        # (see _forward_token)
        from ..ops.kernel_registry import choose as _choose_kernel

        if layout is not None:
            from ..ops import pallas_kernels as _pk

            # token rows: where each one's K/V go (padding rows, as
            # invalid slots, into the null block), and the query tiles
            write_blk, slot_idx = layout["write_blk"], layout["slot_idx"]
            n_tiles, Cq = layout["tile_rows"].shape
            use_chunk = _choose_kernel("chunk_window", head_dim=Dh,
                                       block_size=bs, window=Cq)
            tile_attention = (_pk.paged_chunk_attention if use_chunk
                              else _pk.paged_chunk_attention_reference)
            # A tile of ONE token (every decode row of a mixed step, a
            # chunk's tail of one, a one-token prompt) is a decode
            # query: where both kernels are chosen (heads of whole lane
            # tiles, so `paged_decode_attention` walks the row's own
            # pages) the decode kernel takes it and the chunk kernel
            # skips it (docs/SERVING.md, "Chunked prefill": 0.18 ms a
            # row against 0.47). A narrower head keeps the single call:
            # its decode kernel is the grid over every table slot.
            route = use_chunk and _choose_kernel(
                "paged_decode", head_dim=Dh, block_size=bs)
            tile_len = layout["tile_len"]
            live = layout["live"][:, None]
            if route:
                one_token = tile_len == 1
                tile_len = jnp.where(one_token, 0, tile_len)
                first_rows = layout["tile_rows"][:, :1]
                # a token row's tile, and whether the decode kernel
                # had it
                tile_of = layout["back"] // Cq
                from_decode = live & one_token[tile_of][:, None]

            def attend(i, q, kv_k, kv_v):
                ctx = tile_attention(
                    kv_k, kv_v, q[layout["tile_rows"]],
                    layout["tile_tables"], layout["tile_pos"],
                    tile_len, layer=i, sm_scale=sm_scale)
                ctx = ctx.reshape(n_tiles * Cq, -1)[layout["back"]]
                if not use_chunk:
                    return ctx
                # the kernel leaves a skipped or unused tile's slots
                # unwritten, and a padding row's `back` may point into
                # one: a padding row takes zero
                ctx = jnp.where(live, ctx, 0.0)
                if not route:
                    return ctx
                one = _pk.paged_decode_attention(
                    kv_k, kv_v, q[first_rows],
                    layout["tile_tables"], layout["tile_pos"][:, None],
                    layer=i, sm_scale=sm_scale, active=one_token)
                return jnp.where(
                    from_decode, one.reshape(n_tiles, -1)[tile_of], ctx)
        else:
            B, C = lead
            # per-slot write targets: window slot j of row b lands at
            # position pos2d[b, j]; slots past the row's valid length
            # (and whole inactive rows) scatter into the null block
            valid = ((jnp.arange(C, dtype=jnp.int32)[None, :]
                      < lengths[:, None]) & active[:, None])
            blk_idx = jnp.clip(pos2d // bs, 0, Mb - 1)
            write_blk = jnp.where(
                valid, jnp.take_along_axis(block_tables, blk_idx, axis=1),
                0)
            slot_idx = pos2d % bs

            # context validity per window slot: t <= that slot's
            # position. The whole window's k/v are written BEFORE the
            # gather, so in-window self-attention sees exactly the
            # causal prefix; t=0 is always visible, so no softmax row
            # is fully masked.
            t_ids = jnp.arange(max_ctx)[None, None, :]
            if tree_anc is None:
                attn_valid = t_ids <= pos2d[:, :, None]      # [B, C, T]
            else:
                # tree window: slot j's visibility is the committed
                # prefix (strictly before the window's first position)
                # plus the static ancestor mask over in-window cache
                # positions. The root slot sees itself via anc[0, 0];
                # pos0 >= 1 past prefill, so no softmax row is ever
                # fully masked.
                pos0 = pos2d[:, 0]
                rel = t_ids - pos0[:, None, None]            # [B, 1, T]
                in_win = (rel >= 0) & (rel < C)
                rel_c = jnp.clip(rel, 0, C - 1)
                anc_t = tree_anc[jnp.arange(C)[None, :, None], rel_c]
                attn_valid = (rel < 0) | (in_win & anc_t)    # [B, C, T]

            # the verify window dispatches the fused spec_window kernel
            # (k+1 query positions against the paged cache in one
            # launch, block table resolved in-kernel), the tree window
            # the tree-mask variant, which takes the ancestor mask as
            # an extra operand
            use_paged = all_slots and _choose_kernel(
                "spec_window" if tree_anc is None else "spec_window_tree",
                head_dim=Dh, block_size=bs, window=C)
            if use_paged:
                from ..ops.pallas_kernels import (paged_attention,
                                                  paged_attention_tree)
                anc_f = (None if tree_anc is None
                         else tree_anc.astype(jnp.float32))

            def attend(i, q, kv_k, kv_v):
                if use_paged and tree_anc is None:
                    return paged_attention(
                        kv_k, kv_v, q, block_tables, pos2d,
                        layer=i, sm_scale=sm_scale).reshape(B, C, -1)
                if use_paged:
                    return paged_attention_tree(
                        kv_k, kv_v, q, block_tables, pos2d, anc_f,
                        layer=i, sm_scale=sm_scale).reshape(B, C, -1)
                # lax path: the layer's pages, then the paged gather
                # [B, Mb, bs, H, Dh] -> [B, max_ctx, H, Dh]
                # (see _forward_token)
                with jax.named_scope("kv_read"):
                    k_ctx = kv_k[i][block_tables].reshape(
                        B, max_ctx, H, Dh)
                    v_ctx = kv_v[i][block_tables].reshape(
                        B, max_ctx, H, Dh)
                scores = jnp.einsum("bchd,bthd->bcht", q, k_ctx) \
                    * sm_scale
                scores = jnp.where(attn_valid[:, :, None, :], scores,
                                   -jnp.inf)
                w = jnp.exp(scores
                            - jnp.max(scores, axis=-1, keepdims=True))
                w = w / jnp.sum(w, axis=-1, keepdims=True)
                return jnp.einsum("bcht,bthd->bchd", w, v_ctx) \
                    .reshape(B, C, -1)

        def last_slot(x):
            if layout is not None:
                return x[layout["last"]]
            last = jnp.clip(lengths - 1, 0, lead[1] - 1).astype(jnp.int32)
            return jnp.take_along_axis(x, last[:, None, None],
                                       axis=1)[:, 0]

        # the verify windows want every slot's logits
        return self._layers(jnp, weights, x, kv_k, kv_v, write_blk,
                            slot_idx, attend,
                            None if all_slots else last_slot)

    def make_prefill_step(self, max_batch, max_blocks_per_seq, chunk,
                          return_logits=False, max_tokens=None):
        """Build (and cache) the jitted fixed-shape CHUNKED step for
        this engine geometry — the mixed prefill/decode shape
        (docs/SERVING.md). Calling convention:

            step(weights, kv_k, kv_v, chunk_tokens[B, C], use_prompt[B],
                 prev_tokens[B], positions[B], lengths[B],
                 block_tables[B, Mb], active[B])
              -> (kv_k', kv_v', next_tokens[B])

        ``positions[b]`` is row b's FIRST window position; window slot
        ``j`` processes position ``positions[b] + j``. Prefill rows
        (``use_prompt``) take all ``lengths[b]`` tokens from
        ``chunk_tokens``; decode rows are 1-token windows whose first
        slot chains ``prev_tokens`` on device. ``next_tokens[b]`` is
        the greedy token at the row's last valid slot — meaningful when
        the window consumed the final prompt token (the first generated
        token) or for decode rows. The KV arrays are donated.

        ``max_tokens`` is the caller's promise of how many tokens a
        window can hold at once (the engine: ``max_batch`` plus the
        scheduler's prefill budget, which ``plan_step`` keeps). Both
        blocks compact the window's real tokens to that many rows for
        everything a token does alone: embedding, norms, projections,
        the K/V write, the FFN (``None``, or a promise no smaller than
        the window: every slot is a row). A window holding more tokens
        than promised would lose the ones past the promise, so only
        the owner of the plan may make it. Either way the window's
        attention runs over query tiles (:data:`CHUNK_TILE`,
        docs/SERVING.md "Chunked prefill"), never over
        ``[B, C, H, T]``."""
        if self.config.block is not None:
            key = ("chunk", int(max_batch), int(max_blocks_per_seq),
                   int(chunk), bool(return_logits),
                   max_tokens) + _kernel_key_suffix()
            if key not in self._steps:
                self._steps[key] = self._instrument_step(
                    "chunk", self.config.block.make_window_step(
                        self, int(chunk), return_logits, max_tokens))
            return self._steps[key]
        return self._make_window_step("chunk", max_batch,
                                      max_blocks_per_seq, chunk,
                                      all_slots=False,
                                      return_logits=return_logits,
                                      max_tokens=max_tokens)

    def _make_window_step(self, kind, max_batch, max_blocks_per_seq,
                          window, all_slots, return_logits, tree=None,
                          max_tokens=None):
        """The shared ``[max_batch, window]`` jitted step builder behind
        :meth:`make_prefill_step` (``all_slots=False`` — logits at each
        row's last valid slot), :meth:`make_spec_step`
        (``all_slots=True`` — the verify window, argmax at every slot)
        and :meth:`make_spec_tree_step` (``tree=(width, depth)`` — the
        tree verify window: tree attention mask, position encodings at
        each slot's tree DEPTH rather than its window offset). One
        body, so the token-splice/embedding/position plumbing can never
        diverge between the shapes. The chunk step (``kind ==
        "chunk"``) runs it over token rows, ``max_tokens`` of them
        where that is a smaller number than the window's slots
        (:func:`_chunk_layout`); the verify windows over ``[B, C]``."""
        C = int(window)
        # token rows of the chunk step: the promise, or every slot
        rows = int(max_batch) * C
        if kind == "chunk" and max_tokens is not None:
            rows = min(rows, int(max_tokens))
        key = (kind, int(max_batch), int(max_blocks_per_seq),
               C, bool(return_logits)) + _kernel_key_suffix()
        if tree is not None:
            key = key + ("tree:%dx%d" % (int(tree[0]), int(tree[1])),)
        if rows < int(max_batch) * C:
            key = key + ("rows:%d" % rows,)
        if key in self._steps:
            return self._steps[key]
        import jax
        import jax.numpy as jnp

        cfg = self.config
        pe = jnp.asarray(_position_encoding_table(cfg))
        emb_scale = float(cfg.d_model) ** 0.5
        if tree is None:
            depths_j = anc_j = None
        else:
            _parents, depths_np, anc_np = tree_topology(*tree)
            depths_j = jnp.asarray(depths_np)            # [C]
            anc_j = jnp.asarray(anc_np)                  # [C, C] bool

        def step(weights, kv_k, kv_v, window_tokens, use_prompt,
                 prev_tokens, positions, lengths, block_tables, active):
            self.trace_count += 1
            tok0 = jnp.where(use_prompt, window_tokens[:, 0],
                             prev_tokens)
            tok = jnp.concatenate([tok0[:, None], window_tokens[:, 1:]],
                                  axis=1)
            tok = jnp.clip(tok, 0, cfg.vocab_size - 1)
            pos2d = (positions[:, None]
                     + jnp.arange(C, dtype=jnp.int32)[None, :])
            layout = None
            if kind == "chunk":
                # the window as token rows, before anything is looked
                # up for a slot that holds no token
                tile = min(CHUNK_TILE, C)
                layout = _chunk_layout(
                    jnp, positions, lengths, active, block_tables, C,
                    rows, tile,
                    chunk_tile_count(int(max_batch), C, rows, tile),
                    kv_k.shape[2])
                tok, pos2d = tok.reshape(-1)[layout["at"]], layout["pos"]
            emb = jnp.take(weights["embedding"], tok, axis=0)
            es = weights.get("embedding@qscale")
            if es is not None:
                emb = emb.astype(jnp.float32) * es
            if tree is None:
                pe_idx = jnp.clip(pos2d, 0, cfg.max_seq_len - 1)
            else:
                # a tree slot's LOGICAL position is root + its depth
                # (siblings share a position; the cache slot stays
                # pos + j)
                pe_idx = jnp.clip(positions[:, None] + depths_j[None, :],
                                  0, cfg.max_seq_len - 1)
            x = (emb * emb_scale * cfg.pe_alpha
                 + cfg.pe_beta * jnp.take(pe, pe_idx, axis=0))
            kv_k, kv_v, logits = self._forward_chunk(
                jnp, weights, x, pos2d, lengths, block_tables, active,
                kv_k, kv_v, all_slots=all_slots, tree_anc=anc_j,
                layout=layout)
            next_tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if return_logits:
                return kv_k, kv_v, next_tokens, logits
            return kv_k, kv_v, next_tokens

        # the program's name in a device trace (`XLA Modules`:
        # jit_chunk_step, jit_spec_step, jit_spec_tree_step)
        step.__name__ = step.__qualname__ = kind + "_step"
        jitted = self._instrument_step(kind, jax.jit(
            step, donate_argnums=(1, 2)))
        self._steps[key] = jitted
        return jitted

    def make_spec_step(self, max_batch, max_blocks_per_seq, window,
                       return_logits=False):
        """Build (and cache) the jitted speculative **verify window**
        for this engine geometry (docs/SERVING.md): the
        ``[max_batch, window]`` chunk shape of :meth:`make_prefill_step`
        except that the target's greedy token is returned at EVERY
        window slot instead of only the last one:

            step(weights, kv_k, kv_v, window_tokens[B, W],
                 use_prompt[B], prev_tokens[B], positions[B],
                 lengths[B], block_tables[B, Mb], active[B])
              -> (kv_k', kv_v', next_tokens[B, W])

        ``next_tokens[b, j]`` is the argmax AFTER window slot ``j`` —
        the token the target would emit at position
        ``positions[b] + j + 1``. A row feeding ``[t0, d1..dk]`` (its
        last committed token plus ``k`` draft tokens) therefore
        verifies every draft in one step: acceptance is the longest
        prefix with ``d[j+1] == next_tokens[b, j]``, and
        ``next_tokens[b, m]`` after the last accepted draft is the
        correction token — computed over an all-verified context, so
        every window emits at least one sequential-greedy-identical
        token. Slots at or past ``lengths[b]`` write to the null block
        and their outputs are meaningless. The KV arrays are donated."""
        self._no_such_step("the speculative verify window")
        return self._make_window_step("spec", max_batch,
                                      max_blocks_per_seq, window,
                                      all_slots=True,
                                      return_logits=return_logits)

    def make_spec_tree_step(self, max_batch, max_blocks_per_seq, width,
                            depth, return_logits=False):
        """Build (and cache) the jitted TREE verify window
        (docs/SERVING.md tree speculation): the :meth:`make_spec_step`
        shape over a ``C = 1 + width * depth`` window holding a
        level-order token tree (:func:`tree_topology` — slot 0 the
        row's last committed token, ``width`` root-anchored chains of
        ``depth`` slots), verified in ONE compiled step via the
        in-window tree attention mask:

            step(weights, kv_k, kv_v, window_tokens[B, C],
                 use_prompt[B], prev_tokens[B], positions[B],
                 lengths[B], block_tables[B, Mb], active[B])
              -> (kv_k', kv_v', next_tokens[B, C])

        ``next_tokens[b, j]`` is the target's greedy token after window
        slot ``j``'s ROOT PATH (committed prefix + j's ancestors + j) —
        the token sequential greedy decoding would emit after accepting
        exactly that path. Acceptance (the host walk,
        ``scheduler.spec_tree_acceptance``) is the deepest root path
        whose every node matches the running argmax; the argmax at the
        accepted frontier is the correction token, so every window
        emits at least one greedy-identical token. Rows may feed any
        level-order PREFIX of the full tree via ``lengths`` (shallower
        trees near budget caps); slots at or past ``lengths[b]`` write
        to the null block. At ``width == 1`` the mask, positions and
        outputs are numerically the linear verify window. The KV arrays
        are donated."""
        self._no_such_step("the tree verify window")
        width, depth = int(width), int(depth)
        return self._make_window_step("spec_tree", max_batch,
                                      max_blocks_per_seq,
                                      1 + width * depth,
                                      all_slots=True,
                                      return_logits=return_logits,
                                      tree=(width, depth))

    def make_tree_commit_step(self, max_batch, max_blocks_per_seq,
                              window):
        """Build (and cache) the jitted post-acceptance KV
        **compaction** step for tree speculation (docs/SERVING.md): the
        verify window wrote every tree slot's KV at cache position
        ``pos + slot``, but the committed layout needs the ACCEPTED
        root path contiguous at ``pos + 1 ..``. One tiny gather/scatter
        over the window span moves it:

            commit(kv_k, kv_v, positions[B], src_slots[B, C],
                   n_commit[B], block_tables[B, Mb], active[B])
              -> (kv_k', kv_v')

        Row ``b`` copies window slot ``src_slots[b, j]`` (cache
        position ``positions[b] + src_slots[b, j]``) onto cache
        position ``positions[b] + j`` for every ``j < n_commit[b]``
        (the engine passes ``[0, path...]`` so ``j = 0`` is the root's
        identity self-copy); rows needing no move pass ``n_commit = 0``
        and their writes route to the null block. All sources are
        gathered before any destination is written, and the engine
        dispatches this BEFORE ``truncate_owner`` re-points the tail
        blocks, so sources always live in still-owned blocks. Pure data
        movement — no weights are read. The KV arrays are donated."""
        self._no_such_step("the tree commit step")
        key = ("tree_commit", int(max_batch), int(max_blocks_per_seq),
               int(window)) + _kernel_key_suffix()
        if key in self._steps:
            return self._steps[key]
        import jax
        import jax.numpy as jnp

        C = int(window)

        def tree_commit_step(kv_k, kv_v, positions, src_slots, n_commit,
                             block_tables, active):
            self.trace_count += 1
            Mb = block_tables.shape[1]
            bs = kv_k.shape[2]
            src_pos = positions[:, None] + src_slots        # [B, C]
            src_blk = jnp.take_along_axis(
                block_tables, jnp.clip(src_pos // bs, 0, Mb - 1),
                axis=1)
            k_win = kv_k[:, src_blk, src_pos % bs]  # [L, B, C, H, Dh]
            v_win = kv_v[:, src_blk, src_pos % bs]
            dst_pos = (positions[:, None]
                       + jnp.arange(C, dtype=jnp.int32)[None, :])
            dst_ok = ((jnp.arange(C, dtype=jnp.int32)[None, :]
                       < n_commit[:, None]) & active[:, None])
            dst_blk = jnp.where(
                dst_ok,
                jnp.take_along_axis(block_tables,
                                    jnp.clip(dst_pos // bs, 0, Mb - 1),
                                    axis=1),
                0)
            kv_k = kv_k.at[:, dst_blk, dst_pos % bs].set(k_win)
            kv_v = kv_v.at[:, dst_blk, dst_pos % bs].set(v_win)
            return kv_k, kv_v

        jitted = self._instrument_step("tree_commit", jax.jit(
            tree_commit_step, donate_argnums=(0, 1)))
        self._steps[key] = jitted
        return jitted

    def make_draft_step(self, max_batch, max_blocks_per_seq, n_new):
        """Build (and cache) the fused jitted DRAFT step
        (docs/SERVING.md tree speculation): starting from
        ``first_tokens`` (each row's first draft token, already argmaxed
        by the catch-up chunk) at ``positions``, run ``n_new`` greedy
        one-token micro-steps in ONE compiled call (a ``lax.scan`` over
        the one-token forward), each writing its KV slot and chaining
        its argmax into the next — this is what retires the per-row
        host ``reference_decode`` loop of the PR-12 :class:`ModelDrafter`:

            draft(weights, kv_k, kv_v, first_tokens[B], positions[B],
                  block_tables[B, Mb], active[B])
              -> (kv_k', kv_v', tokens[B, n_new])

        ``tokens[b, i]`` is the greedy token after feeding the
        ``i+1``-th chain token, i.e. chain tokens ``2 .. n_new + 1`` of
        a draft whose first token is ``first_tokens[b]``. Active rows
        MUST have ``positions + n_new <= max_seq_len`` (the caller
        deactivates rows near the cap — inactive rows write to the null
        block and their outputs are ignored). The KV arrays are
        donated."""
        self._no_such_step("the draft step")
        key = ("draft", int(max_batch), int(max_blocks_per_seq),
               int(n_new)) + _kernel_key_suffix()
        if key in self._steps:
            return self._steps[key]
        import jax
        import jax.numpy as jnp

        cfg = self.config
        pe = jnp.asarray(_position_encoding_table(cfg))
        emb_scale = float(cfg.d_model) ** 0.5
        n_new = int(n_new)

        def embed(weights, tok, pos):
            tok = jnp.clip(tok, 0, cfg.vocab_size - 1)
            emb = jnp.take(weights["embedding"], tok, axis=0)
            es = weights.get("embedding@qscale")
            if es is not None:
                emb = emb.astype(jnp.float32) * es
            pe_idx = jnp.clip(pos, 0, cfg.max_seq_len - 1)
            return (emb * emb_scale * cfg.pe_alpha
                    + cfg.pe_beta * jnp.take(pe, pe_idx, axis=0))

        def draft_step(weights, kv_k, kv_v, first_tokens, positions,
                       block_tables, active):
            self.trace_count += 1

            def micro(carry, i):
                kv_k, kv_v, tok = carry
                pos = positions + i
                x = embed(weights, tok, pos)
                kv_k, kv_v, logits = self._forward_token(
                    jnp, weights, x, pos, block_tables, active,
                    kv_k, kv_v)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (kv_k, kv_v, nxt), nxt

            (kv_k, kv_v, _last), toks = jax.lax.scan(
                micro, (kv_k, kv_v, first_tokens),
                jnp.arange(n_new, dtype=jnp.int32))
            return kv_k, kv_v, jnp.transpose(toks)      # [B, n_new]

        jitted = self._instrument_step("draft", jax.jit(
            draft_step, donate_argnums=(1, 2)))
        self._steps[key] = jitted
        return jitted


# ---------------------------------------------------------------------------
# draft sources for speculative decoding (docs/SERVING.md)
# ---------------------------------------------------------------------------


class NGramDrafter:
    """Prompt-lookup / n-gram drafting (zero extra weights): match the
    sequence's most recent suffix n-gram against earlier occurrences in
    its OWN prompt+output history and propose the tokens that followed
    the most recent earlier match. Strongest exactly where the radix
    prefix cache already wins — templated, repetitive and structured
    generation (code, JSON, quoting the prompt back) — and free
    everywhere else: a miss proposes nothing and the verify window
    degrades to a plain one-token decode step.

    ``propose(history, k)`` tries match lengths from ``max_ngram`` down
    to ``min_ngram`` and returns up to ``k`` continuation tokens (empty
    when no n-gram recurs).

    With a ``seq_id`` (``propose_for`` — what the scheduler passes),
    the drafter keeps an INCREMENTAL per-sequence suffix index instead
    of rescanning the full history every window: each n-gram's start
    positions are recorded once when the history first covers them
    (committed history is append-only between windows; a shrunken or
    diverged history rebuilds the index from scratch), so draft-side
    host time per window is O(k + tokens newly committed), not O(L).
    ``index_ops`` counts gram insertions + occurrence probes — the
    unit-test pin that the rescan is really gone. ``release(seq_id)``
    drops a retired sequence's index (the scheduler's reap calls it)."""

    def __init__(self, max_ngram=3, min_ngram=1):
        self.max_ngram = int(max_ngram)
        self.min_ngram = int(min_ngram)
        if self.min_ngram < 1:
            raise ValueError("min_ngram must be >= 1")
        if self.max_ngram < self.min_ngram:
            raise ValueError("max_ngram must be >= min_ngram")
        self._index = {}        # seq_id -> {len, last, grams{n: {...}}}
        self.index_ops = 0

    def release(self, seq_id):
        """Drop a retired sequence's memoized suffix index."""
        self._index.pop(seq_id, None)

    def _indexed(self, seq_id, hist):
        """The per-sequence suffix index advanced to cover ``hist``:
        ``grams[n]`` maps each n-gram tuple to its ASCENDING start
        positions. Incremental — only grams starting in the newly
        appended span are inserted; a history that shrank or whose
        last cached token changed (external rollback/divergence)
        rebuilds from scratch."""
        L = len(hist)
        ent = self._index.get(seq_id)
        if (ent is None or ent["len"] > L
                or (ent["len"] > 0 and hist[ent["len"] - 1] != ent["last"])):
            ent = {"len": 0, "last": None,
                   "grams": {n: {} for n in
                             range(self.min_ngram, self.max_ngram + 1)}}
            self._index[seq_id] = ent
        L0 = ent["len"]
        for n in range(self.min_ngram, self.max_ngram + 1):
            grams = ent["grams"][n]
            for j in range(max(L0 - n + 1, 0), L - n + 1):
                grams.setdefault(tuple(hist[j:j + n]), []).append(j)
                self.index_ops += 1
        ent["len"] = L
        ent["last"] = hist[L - 1] if L else None
        return ent

    def propose_for(self, seq_id, history, k):
        """``propose`` through the incremental per-sequence index —
        identical tokens, O(k)-per-window host cost."""
        return self.propose(history, k, seq_id=seq_id)

    def propose(self, history, k, seq_id=None):
        k = int(k)
        if k < 1 or len(history) < self.min_ngram + 1:
            return []
        hist = [int(t) for t in history]
        L = len(hist)
        ent = self._indexed(seq_id, hist) if seq_id is not None else None
        for n in range(min(self.max_ngram, L - 1),
                       self.min_ngram - 1, -1):
            suffix = hist[L - n:]
            # the most recent earlier occurrence able to supply a FULL
            # k-token continuation wins (recency beats frequency for
            # local repetition, but a match right at the history's end
            # can only offer a truncated draft — on a period-p
            # repetition the nearest match yields only p tokens, so
            # scan on for an earlier full-window one); the match must
            # end before the suffix starts so the continuation is real
            best = None
            if ent is not None:
                # memoized path: same candidates in the same recency
                # order, read straight off the occurrence list
                occ = ent["grams"][n].get(tuple(suffix), ())
                for j in reversed(occ):
                    self.index_ops += 1
                    if j >= L - n:      # the trailing suffix itself
                        continue
                    avail = min(k, L - (j + n))
                    if best is None or avail > best[1]:
                        best = (j, avail)
                    if avail >= k:
                        break
            else:
                for j in range(L - n - 1, -1, -1):
                    if hist[j:j + n] != suffix:
                        continue
                    avail = min(k, L - (j + n))
                    if best is None or avail > best[1]:
                        best = (j, avail)
                    if avail >= k:
                        break
            if best is not None:
                start = best[0] + n
                return hist[start:start + k]
        return []

    def propose_tree(self, history, width, depth, seq_id=None):
        """Tree drafting (docs/SERVING.md): up to ``width``
        root-anchored chains of up to ``depth`` tokens. Chain 0 is the
        linear :meth:`propose` draft; alternate chains are the
        continuations of OTHER occurrence sites of the same suffix
        whose next token differs — exactly the traffic
        (period-alternating repetition) where a single linear chain
        keeps losing the verify window. Host work is bounded by a small
        per-call probe budget, so tree drafting stays O(width * depth)
        per window on the memoized path."""
        width, depth = int(width), int(depth)
        primary = self.propose(history, depth, seq_id=seq_id)
        if width <= 1 or len(history) < self.min_ngram + 1:
            return [primary] if primary else []
        hist = [int(t) for t in history]
        L = len(hist)
        chains = [primary] if primary else []
        seen = {primary[0]} if primary else set()
        for n in range(min(self.max_ngram, L - 1),
                       self.min_ngram - 1, -1):
            if seq_id is not None:
                occ = list(self._indexed(seq_id, hist)["grams"][n]
                           .get(tuple(hist[L - n:]), ()))
            else:
                suffix = hist[L - n:]
                occ = [j for j in range(L - n)
                       if hist[j:j + n] == suffix]
            budget = 8 * width + depth
            for j in reversed(occ):
                if len(chains) >= width or budget <= 0:
                    break
                budget -= 1
                self.index_ops += 1
                if j >= L - n:
                    continue
                cont = hist[j + n:j + n + depth]
                if not cont or cont[0] in seen:
                    continue
                seen.add(cont[0])
                chains.append(cont)
            if occ:
                # branches come from the longest recurring suffix only
                break
        return chains


class _DraftSeq:
    """Per-sequence drafter-side KV state: the drafter pool's owner
    object (reservation/rollback accounting hangs off its identity)."""

    __slots__ = ("slot", "n_cached")

    def __init__(self, slot):
        self.slot = int(slot)
        self.n_cached = 0


class ModelDrafter:
    """The pluggable draft-model hook: greedy-decode continuation
    tokens from a (smaller) :class:`GenerationModel` over each
    sequence's committed history.

    ``propose(history, k)`` is the PR-12 host-side oracle path
    (``reference_decode`` — exact, unbatched, the API the original
    tests pin). The production fast path is ``propose_batch`` /
    ``propose_tree_batch``: the draft model runs as its OWN tiny jitted
    steps batched across all occupied rows — catch-up prefill chunks
    (``make_prefill_step`` with ``return_logits``) bring each row's
    draft KV level with its committed history, then ONE fused
    ``make_draft_step`` scan drafts the whole chain on device. Draft KV
    lives in the drafter's own :class:`~.kv_cache.KVBlockPool` slice
    and every window ends with the same reservation-restoring
    ``truncate_owner`` rollback the target cache uses, so speculative
    draft state can never leak blocks (``pool.check_invariants`` is
    clean at every window boundary). Drafting with the TARGET model
    itself yields perfect acceptance, which is what the tests pin.

    ``draft_steps`` counts jitted draft-side dispatches (catch-up
    chunks + fused scans) — the bench's draft-cost accounting."""

    def __init__(self, model, block_size=16, chunk=None):
        if not isinstance(model, GenerationModel):
            raise TypeError("ModelDrafter needs a GenerationModel, got "
                            "%r" % (type(model).__name__,))
        model._no_such_step("a draft model")
        self.model = model
        self.draft_steps = 0
        self._block_size = int(block_size)
        self._chunk = chunk
        self._pool = None
        self._tables = None
        self._max_batch = 0
        self._n_new = 0
        self._mb = 0
        self._states = {}       # seq_id -> _DraftSeq
        self._free_slots = []

    # -- PR-12 host oracle path (API-compatible) ----------------------------
    def propose(self, history, k):
        k = int(k)
        hist = [int(t) for t in history]
        if k < 1 or not hist:
            return []
        if len(hist) >= self.model.config.max_seq_len:
            return []
        return reference_decode(self.model, hist, k)

    # -- jitted batched path ------------------------------------------------
    def bind(self, max_batch, max_chain):
        """Size the drafter-side geometry (the engine calls this once
        at worker construction): ``max_batch`` rows, chains up to
        ``max_chain`` tokens. Builds the drafter's own KV pool —
        ``max_batch * blocks_needed(draft max_seq_len)`` blocks, so a
        full reservation per row always succeeds and admission can
        never deadlock on draft KV. Growing an existing binding resets
        all per-sequence draft state (the next window re-prefills)."""
        from .kv_cache import KVBlockPool, blocks_needed

        max_batch = int(max_batch)
        max_chain = max(int(max_chain), 1)
        if (self._pool is not None and self._max_batch >= max_batch
                and self._n_new == max_chain - 1):
            return
        cfg = self.model.config
        if self._chunk is None:
            from .. import flags as _flags
            self._chunk = int(_flags.env("PTPU_SERVE_DRAFT_CHUNK"))
        self._chunk = max(int(self._chunk), 1)
        self._max_batch = max(max_batch, self._max_batch)
        self._n_new = max_chain - 1
        self._mb = blocks_needed(cfg.max_seq_len, self._block_size)
        self._pool = KVBlockPool(
            cfg.n_layers, cfg.n_heads, cfg.head_dim, self._block_size,
            num_blocks=self._max_batch * self._mb)
        self._tables = np.zeros((self._max_batch, self._mb), np.int32)
        self._states = {}
        self._free_slots = list(range(self._max_batch - 1, -1, -1))

    def release(self, seq_id):
        """Free a retired sequence's draft-side KV state (the
        scheduler's reap calls this)."""
        st = self._states.pop(seq_id, None)
        if st is None:
            return
        self._pool.free_owner(st)
        self._tables[st.slot, :] = 0
        self._free_slots.append(st.slot)

    def _state_for(self, seq_id):
        st = self._states.get(seq_id)
        if st is None:
            st = _DraftSeq(self._free_slots.pop())
            self._states[seq_id] = st
            # full per-row reservation up front: the drafter pool is
            # sized so this can never fail, and truncate_owner restores
            # it after every window's rollback
            self._pool.reserve(st, self._mb)
        return st

    def _alloc_span(self, st, start, stop):
        """Own (and table-map) the draft blocks covering positions
        [start, stop)."""
        from .kv_cache import blocks_needed

        have = blocks_needed(start, self._block_size)
        need = blocks_needed(stop, self._block_size)
        for b in range(have, need):
            self._tables[st.slot, b] = self._pool.alloc_block(st)

    def propose_batch(self, rows, k):
        """Draft up to ``k`` greedy continuation tokens for MANY
        sequences in a constant number of jitted draft-side steps.
        ``rows`` is ``[(seq_id, history), ...]``; returns
        ``{seq_id: [tokens...]}`` (missing/empty where a row cannot be
        drafted — at the draft model's sequence cap)."""
        got = self.propose_tree_batch(
            [(sid, hist, k) for sid, hist in rows], width=1)
        return {sid: (ch[0] if ch else []) for sid, ch in got.items()}

    def propose_tree_batch(self, rows, width):
        """Tree drafting for MANY sequences in a constant number of
        jitted steps. ``rows`` is ``[(seq_id, history, depth), ...]``;
        returns ``{seq_id: [chain0, chain1, ...]}`` — chain 0 the fused
        greedy scan (up to ``depth`` tokens), chains 1.. the top
        ``width - 1`` alternate FIRST tokens from the same catch-up
        logits (depth-1 branches: the cheap high-value part of the
        tree, no extra device steps)."""
        import jax.numpy as jnp
        from .kv_cache import blocks_needed

        width = int(width)
        out = {sid: [] for sid, _h, _d in rows}
        cfg = self.model.config
        work = []
        for sid, hist, depth in rows:
            hist = [int(t) for t in hist]
            depth = int(depth)
            if depth < 1 or not hist or len(hist) >= cfg.max_seq_len:
                continue
            work.append((sid, hist,
                         min(depth, cfg.max_seq_len - len(hist))))
        if not work:
            return out
        max_depth = max(d for _s, _h, d in work)
        if self._pool is None:
            self.bind(len(work), max_depth)
        # grow the binding when a call outruns it (direct/unit-test use;
        # the engine binds its full geometry up front so this is a
        # no-op there) — growing resets draft state, the next window
        # simply re-prefills
        new_ids = sum(1 for sid, _h, _d in work
                      if sid not in self._states)
        if (new_ids > len(self._free_slots)
                or max_depth - 1 > self._n_new):
            self.bind(max(self._max_batch,
                          len(self._states) + new_ids),
                      max(max_depth, self._n_new + 1))
        B, Mb, chunk = self._max_batch, self._mb, self._chunk
        weights = self.model.weights
        pool = self._pool

        # -- catch-up: feed history[n_cached:] through prefill chunks;
        # each row's FINAL chunk's logits give draft token 1 (argmax)
        # and the alternate branch roots (top width-1 runners-up)
        states = {}
        for sid, hist, depth in work:
            st = self._state_for(sid)
            if st.n_cached > len(hist):
                # diverged/rolled-back history: rebuild from scratch
                pool.truncate_owner(st, 0)
                self._tables[st.slot, :] = 0
                st.n_cached = 0
            states[sid] = st
        pstep = self.model.make_prefill_step(B, Mb, chunk,
                                             return_logits=True)
        final_logits = {}
        while True:
            feed = np.zeros((B, chunk), np.int32)
            lengths = np.zeros(B, np.int32)
            positions = np.zeros(B, np.int32)
            active = np.zeros(B, bool)
            finishing = []
            for sid, hist, depth in work:
                st = states[sid]
                rem = len(hist) - st.n_cached
                if rem <= 0:
                    continue
                n = min(chunk, rem)
                feed[st.slot, :n] = hist[st.n_cached:st.n_cached + n]
                lengths[st.slot] = n
                positions[st.slot] = st.n_cached
                active[st.slot] = True
                self._alloc_span(st, st.n_cached, st.n_cached + n)
                st.n_cached += n
                if st.n_cached == len(hist):
                    finishing.append(sid)
            if not active.any():
                break
            k_arr, v_arr, _nt, logits = pstep(
                weights, pool.k, pool.v, jnp.asarray(feed),
                jnp.asarray(active), jnp.zeros((B,), jnp.int32),
                jnp.asarray(positions), jnp.asarray(lengths),
                jnp.asarray(self._tables), jnp.asarray(active))
            pool.k, pool.v = k_arr, v_arr
            self.draft_steps += 1
            if finishing:
                lg = np.asarray(logits)
                for sid in finishing:
                    final_logits[sid] = lg[states[sid].slot]

        # -- branch roots from the final-chunk logits (stable argsort:
        # order[0] is exactly np.argmax, the chain-0 first token)
        first_tok = {}
        alt_tok = {}
        for sid, hist, depth in work:
            order = np.argsort(-final_logits[sid], kind="stable")
            first_tok[sid] = int(order[0])
            alt_tok[sid] = [int(t) for t in order[1:width]]

        # -- fused scan: draft chain-0 tokens 2..depth in ONE step.
        # Rows whose remaining draft span would cross the draft cache
        # cap ride inactive (their chain stays [d1]).
        scan_toks = None
        if self._n_new > 0 and any(d > 1 for _s, _h, d in work):
            first = np.zeros(B, np.int32)
            positions = np.zeros(B, np.int32)
            active = np.zeros(B, bool)
            for sid, hist, depth in work:
                st = states[sid]
                H = len(hist)
                if depth < 2 or H + self._n_new > cfg.max_seq_len:
                    continue
                first[st.slot] = first_tok[sid]
                positions[st.slot] = H
                active[st.slot] = True
                self._alloc_span(st, H, H + self._n_new)
            if active.any():
                dstep = self.model.make_draft_step(B, Mb, self._n_new)
                k_arr, v_arr, toks = dstep(
                    weights, pool.k, pool.v, jnp.asarray(first),
                    jnp.asarray(positions), jnp.asarray(self._tables),
                    jnp.asarray(active))
                pool.k, pool.v = k_arr, v_arr
                self.draft_steps += 1
                scan_toks = np.asarray(toks)
                scan_active = active
            else:
                scan_active = np.zeros(B, bool)
        else:
            scan_active = np.zeros(B, bool)

        # -- assemble chains + roll draft KV back to the committed
        # history (same truncate_owner contract as the target cache:
        # reservation restored, freed table entries re-point to null)
        for sid, hist, depth in work:
            st = states[sid]
            chain0 = [first_tok[sid]]
            if scan_active[st.slot] and scan_toks is not None:
                chain0 += [int(t) for t in scan_toks[st.slot]]
            chains = [chain0[:depth]]
            chains += [[t] for t in alt_tok[sid]]
            out[sid] = chains
            keep = blocks_needed(len(hist), self._block_size)
            dropped = pool.truncate_owner(st, keep)
            if dropped:
                self._tables[st.slot, keep:keep + len(dropped)] = 0
            st.n_cached = len(hist)
        return out


# ---------------------------------------------------------------------------
# unbatched, unpaged reference decoder (the correctness oracle)
# ---------------------------------------------------------------------------


def reference_decode(model, prompt, max_new_tokens, eos_id=None):
    """Greedy-decode ONE sequence with a plain contiguous KV cache and
    full attention — no blocks, no batching, no masking tricks. The
    batched paged decode must match this token-for-token. It decodes
    over ``dequantized_weights()``: the values the step computes with
    (an int8 store multiplied back out, a bfloat16 leaf widened)."""
    import jax.numpy as jnp

    cfg = model.config
    if cfg.block is not None:
        raise NotImplementedError(
            "reference_decode is the XGLM block's oracle; the %s block's "
            "plain reference is under perfbench/reference/ "
            "(tests/test_latent_moe.py, tests/test_afmoe.py compare "
            "against theirs)" % cfg.block.kind)
    w = model.dequantized_weights()
    pe = _position_encoding_table(cfg)
    emb_scale = float(cfg.d_model) ** 0.5
    H, Dh = cfg.n_heads, cfg.head_dim
    sm_scale = Dh ** -0.5

    def ln(h, scale, bias):
        mu = np.mean(h, keepdims=True)
        var = np.mean((h - mu) ** 2, keepdims=True)
        return (h - mu) / np.sqrt(var + 1e-5) * np.asarray(scale) \
            + np.asarray(bias)

    ks = [[] for _ in range(cfg.n_layers)]
    vs = [[] for _ in range(cfg.n_layers)]
    tokens = list(prompt)
    generated = []

    def one(tok, pos):
        x = (np.asarray(w["embedding"])[tok] * emb_scale * cfg.pe_alpha
             + cfg.pe_beta * pe[pos])
        for i in range(cfg.n_layers):
            p = "l%d/" % i
            a = ln(x, w[p + "ln1_scale"], w[p + "ln1_bias"])
            qkv = a @ np.asarray(w[p + "wqkv"]) + np.asarray(
                w[p + "bqkv"])
            q, k_new, v_new = np.split(qkv, 3)
            ks[i].append(k_new.reshape(H, Dh))
            vs[i].append(v_new.reshape(H, Dh))
            k_ctx = np.stack(ks[i])            # [T, H, Dh]
            v_ctx = np.stack(vs[i])
            qh = q.reshape(H, Dh)
            scores = np.einsum("hd,thd->ht", qh, k_ctx) * sm_scale
            scores = scores - scores.max(axis=-1, keepdims=True)
            wgt = np.exp(scores)
            wgt = wgt / wgt.sum(axis=-1, keepdims=True)
            ctx = np.einsum("ht,thd->hd", wgt, v_ctx).reshape(-1)
            x = x + ctx @ np.asarray(w[p + "wproj"]) + np.asarray(
                w[p + "bproj"])
            b2 = ln(x, w[p + "ln2_scale"], w[p + "ln2_bias"])
            h = b2 @ np.asarray(w[p + "wff1"]) + np.asarray(w[p + "bff1"])
            # exact (erf) gelu, matching jax.nn.gelu(approximate=False)
            h = h * 0.5 * (1.0 + np.vectorize(math.erf)(
                h / np.sqrt(2.0)))
            x = x + h @ np.asarray(w[p + "wff2"]) + np.asarray(
                w[p + "bff2"])
        x = ln(x, w["final_ln_scale"], w["final_ln_bias"])
        logits = x @ np.asarray(w["lm_head"])
        return int(np.argmax(logits))

    nxt = None
    for pos, tok in enumerate(tokens):
        nxt = one(tok, pos)
    pos = len(tokens)
    while len(generated) < max_new_tokens and pos < cfg.max_seq_len:
        generated.append(nxt)
        if eos_id is not None and nxt == eos_id:
            break
        nxt = one(generated[-1], pos)
        pos += 1
    return generated
