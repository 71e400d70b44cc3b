"""The latent-attention / routed-expert decoder block of the serving
runtime (the ``deepseek_v3`` family of layer equations; docs/SERVING.md,
"A second block").

``GenerationConfig(block=LatentMoEBlock(...))`` selects it; the engine,
scheduler, pool accounting, in-flight window and step log are the ones
every model uses. The block states what the XGLM block does not have:

  * RMSNorm, no biases, SwiGLU, an untied head, no position table;
  * multi-head LATENT attention (MLA): a token caches one row a layer,
    ``[c | k_pe]`` (the RMS-normalised ``kv_lora_rank``-wide latent and
    the rotated ``qk_rope_head_dim``-wide key all heads share), never a
    per-head K or V. The one-token step and the kernel path of the
    chunk step compute it ABSORBED (queries taken into latent space,
    ``q_nope @ W_UK``, the context brought back by ``@ W_UV``); the lax
    path of the chunk step computes it EXPANDED (keys and values rebuilt
    from the gathered rows). The two are one function
    (tests/test_latent_moe.py);
  * interleaved rotary embedding on the rope slice (pairs are the
    adjacent lanes ``2i, 2i+1``);
  * after ``first_k_dense`` dense layers, expert layers: a sigmoid
    router over ALL ``n_routed_experts`` in float32, the top
    ``experts_per_token`` of ``score + bias`` (the bias picks, it does
    not weigh), weights renormalised and scaled; the layer is told which
    experts it HOLDS and computes their part of the result: pairs are
    counted into a tile-aligned order by expert, one grouped matmul a
    projection, un-sorted and combined; the shared expert is added by
    whoever holds it. No capacity, no dropped token.

Weights are stored in ``weight_dtype`` (bfloat16; norm gains and the
router float32). Matmul operands are rounded to ``activation_dtype``
and accumulated in float32, which is what the chip's default precision
does to float32 operands anyway; the residual stream, the norms, the
router, softmax and the combine are float32.
"""

import numpy as np

from .kv_cache import CacheEntry

__all__ = ["LatentMoEBlock", "BlockDescription", "weight_names", "random_weights",
           "make_decode_step", "make_window_step", "route",
           "group_limited", "expert_layer", "mla_absorbed", "mla_expanded",
           "latent_rows", "absorbed_queries", "context_to_heads",
           "rope_interleaved", "COUNTERS"]

# what a step returns beside its tokens, reduced over the expert layers
# (``expert_slots``: held experts x expert layers, what ``experts_touched``
# is a share of)
COUNTERS = ("expert_pairs", "experts_touched", "expert_rows_max",
            "expert_slots")


def held_experts(n_routed_experts, experts_held):
    """The experts a chip holds as a tuple of global ids (``None``: all
    of them), checked: ascending, distinct, below the router's width."""
    held = tuple(int(e) for e in (range(n_routed_experts)
                                  if experts_held is None
                                  else experts_held))
    if (sorted(set(held)) != list(held) or not held
            or held[-1] >= n_routed_experts):
        raise ValueError("experts_held must be ascending, distinct "
                         "ids below n_routed_experts")
    return held


class BlockDescription:
    """What every decoder block's description shares: the round trip
    through a plain dict (``FIELDS`` names the constructor's arguments;
    tuples go out as lists) and the answers ``serving.model`` asks a
    block for (model.BLOCK_KINDS), given by the functions of the
    module the block's class lives in."""

    kind = None
    FIELDS = ()

    def to_dict(self):
        d = {k: getattr(self, k) for k in self.FIELDS}
        d = {k: list(v) if isinstance(v, tuple) else v
             for k, v in d.items()}
        return dict(d, kind=self.kind)

    @classmethod
    def from_dict(cls, d):
        d = dict(d)
        if d.pop("kind", cls.kind) != cls.kind:
            raise ValueError("not a %s block description" % cls.kind)
        return cls(**d)

    def replace(self, **changes):
        return type(self).from_dict(dict(self.to_dict(), **changes))

    def _module(self):
        import importlib

        return importlib.import_module(type(self).__module__)

    def leaf_shapes(self, config):
        return self._module().leaf_shapes(config)

    def random_weights(self, config, seed=0, scale=0.1):
        return random_weights(config, seed, scale)

    def make_decode_step(self, model, return_logits=False):
        return self._module().make_decode_step(model, return_logits)

    def make_window_step(self, model, window, return_logits=False,
                         max_tokens=None):
        return self._module().make_window_step(model, window,
                                               return_logits, max_tokens)


def decode_pages_per_run(pool, table_len):
    """The pages of one run of the page pipe of
    ``latent_paged_attention`` over ``pool``
    (``pk.latent_pages_per_run``, which the kernel asks too). A block
    whose layers attend through that kernel states it as its own
    ``decode_pages_per_run``, and the step log counts the runs by it
    (``engine._decode_pipe_walked``)."""
    from ..ops.pallas_kernels import latent_pages_per_run

    return latent_pages_per_run(pool, table_len)


class LatentMoEBlock(BlockDescription):
    """The block's description, carried by ``GenerationConfig.block``
    (``d_model``, ``n_heads``, ``n_layers``, ``vocab_size`` and the
    dense width ``d_ff`` stay on the configuration)."""

    kind = "latent_moe"
    step_counters = COUNTERS
    decode_pages_per_run = staticmethod(decode_pages_per_run)
    FIELDS = ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "kv_lora_rank", "rope_theta", "rms_norm_eps",
              "first_k_dense", "n_routed_experts", "experts_per_token",
              "n_shared_experts", "moe_d_ff", "routed_scaling_factor",
              "experts_held", "weight_dtype",
              "activation_dtype", "router_dtype", "cache_dtype")

    def __init__(self, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 kv_lora_rank, n_routed_experts, experts_per_token,
                 n_shared_experts, moe_d_ff, rope_theta=10000.0,
                 rms_norm_eps=1e-6, first_k_dense=1,
                 routed_scaling_factor=1.0, experts_held=None,
                 weight_dtype="bfloat16",
                 activation_dtype="bfloat16", router_dtype="float32",
                 cache_dtype="bfloat16"):
        self.qk_nope_head_dim = int(qk_nope_head_dim)
        self.qk_rope_head_dim = int(qk_rope_head_dim)
        self.v_head_dim = int(v_head_dim)
        self.kv_lora_rank = int(kv_lora_rank)
        self.rope_theta = float(rope_theta)
        self.rms_norm_eps = float(rms_norm_eps)
        self.first_k_dense = int(first_k_dense)
        self.n_routed_experts = int(n_routed_experts)
        self.experts_per_token = int(experts_per_token)
        self.n_shared_experts = int(n_shared_experts)
        self.moe_d_ff = int(moe_d_ff)
        self.routed_scaling_factor = float(routed_scaling_factor)
        # the experts this chip holds (global ids, ascending); None: all
        self.experts_held = held_experts(self.n_routed_experts,
                                         experts_held)
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even (rotary pairs)")
        self.weight_dtype = str(weight_dtype)
        self.activation_dtype = str(activation_dtype)
        self.router_dtype = str(router_dtype)
        self.cache_dtype = str(cache_dtype)

    @property
    def cache_width(self):
        """Values a token caches in a layer: the latent and the key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_row(self):
        """Lanes a cached row is stored in: ``cache_width`` rounded up
        to whole 128-lane tiles. The chip's tiling pads a row to that
        anyway; stating it keeps the pool's device layout row-major
        (left to choose, the runtime lays a ``[.., 16, 576]`` array out
        with the BLOCKS minor-most to save the padding, and every step
        would copy the pool into the kernels' layout and back)."""
        return -(-self.cache_width // 128) * 128

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def cache_entry(self):
        return CacheEntry((("latent", (self.cache_row,)),),
                          self.cache_dtype)


def _is_expert_layer(block, i):
    return i >= block.first_k_dense


def leaf_shapes(config):
    """{weight name: (shape, dtype name)}: the serving layout."""
    blk = config.block
    D, V, H, F = (config.d_model, config.vocab_size, config.n_heads,
                  config.d_ff)
    dn, dr, dv, r = (blk.qk_nope_head_dim, blk.qk_rope_head_dim,
                     blk.v_head_dim, blk.kv_lora_rank)
    E, Eh, Fe = (blk.n_routed_experts, len(blk.experts_held), blk.moe_d_ff)
    Fs = blk.n_shared_experts * Fe
    w, f32 = blk.weight_dtype, "float32"
    out = {"embedding": ((V, D), w), "lm_head": ((D, V), w),
           "final_norm": ((D,), f32)}
    for i in range(config.n_layers):
        p = "l%d/" % i
        out.update({
            p + "attn_norm": ((D,), f32),
            p + "wq": ((D, H * (dn + dr)), w),
            p + "wkv_a": ((D, r + dr), w),
            p + "kv_norm": ((r,), f32),
            p + "w_uk": ((H, dn, r), w),
            p + "w_uv": ((H, r, dv), w),
            p + "wo": ((H * dv, D), w),
            p + "ffn_norm": ((D,), f32)})
        if _is_expert_layer(blk, i):
            out.update({
                p + "router": ((D, E), f32),
                p + "router_bias": ((E,), f32),
                p + "we_gate": ((Eh, D, Fe), w),
                p + "we_up": ((Eh, D, Fe), w),
                p + "we_down": ((Eh, Fe, D), w)})
            if Fs:
                out.update({p + "ws_gate": ((D, Fs), w),
                            p + "ws_up": ((D, Fs), w),
                            p + "ws_down": ((Fs, D), w)})
        else:
            out.update({p + "w_gate": ((D, F), w), p + "w_up": ((D, F), w),
                        p + "w_down": ((F, D), w)})
    return out


def weight_names(config):
    return list(leaf_shapes(config))


def random_weights(config, seed=0, scale=0.1):
    """Deterministic random weights in the serving layout (tests, the
    chip smoke) of any block whose gains end in ``norm`` and whose
    router bias is ``router_bias``: N(0, scale) matrices, gains 1,
    router bias 0. Made on
    the default device, a leaf at a time: at published widths an expert
    layer is a gigabyte."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)
    out = {}
    shapes = config.block.leaf_shapes(config)   # this block's, or another's
    for n, (name, (shape, dtype)) in enumerate(shapes.items()):
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, dtype)
        elif name.endswith("router_bias"):
            out[name] = jnp.zeros(shape, dtype)
        else:
            out[name] = _normal(jax.random.fold_in(key, n), shape, dtype,
                                scale)
    return out


def _normal(key, shape, dtype, scale):
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def make(key, shape, dtype):
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(dtype)

    return make(key, shape, dtype)


# ---------------------------------------------------------------------------
# pieces
# ---------------------------------------------------------------------------


def _rms_norm(x, gain, eps):
    import jax
    import jax.numpy as jnp

    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * gain


def _operands(act_dtype, *xs):
    """Matmul operands rounded to the activation dtype. Off the TPU
    they go back to float32 afterwards: the same values, and XLA:CPU
    has no bf16 x bf16 -> f32 dot."""
    import jax.numpy as jnp

    from ..core import device

    xs = [x.astype(act_dtype) for x in xs]
    if not device.on_tpu():
        xs = [x.astype(jnp.float32) for x in xs]
    return xs


def _dot(x, w, act_dtype):
    """``x @ w``: operands in the activation dtype (a float32 weight
    stays float32 when the activations do), float32 accumulate."""
    import jax.numpy as jnp

    x, w = _operands(act_dtype, x, w)
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def rope_interleaved(x, positions, theta):
    """Rotary embedding over the last axis of ``x`` (even width), the
    pairs being the ADJACENT lanes (2i, 2i+1); ``positions`` broadcasts
    against ``x``'s leading axes. float32. Written with lane rolls, not
    a ``[.., d/2, 2]`` view: a minor axis of two is a copy on the chip."""
    import jax.numpy as jnp

    d = x.shape[-1]
    lane = jnp.arange(d)
    inv_freq = theta ** (-(lane // 2).astype(jnp.float32) / (d // 2))
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    even = lane % 2 == 0
    # lane 2i gets -x[2i+1], lane 2i+1 gets x[2i]
    partner = jnp.where(even, -jnp.roll(x, -1, axis=-1),
                        jnp.roll(x, 1, axis=-1))
    return x * jnp.cos(angle) + partner * jnp.sin(angle)


def _swiglu(x, w_gate, w_up, w_down, act_dtype):
    import jax

    h = jax.nn.silu(_dot(x, w_gate, act_dtype)) * _dot(x, w_up, act_dtype)
    return _dot(h, w_down, act_dtype)


def narrowed(dtype):
    """``a -> a`` rounded to a router's ``dtype`` where that is narrower
    than float32, and kept so: left to itself the chip's compiler
    carries a value it has in float32 through a bfloat16 intermediate
    unrounded (its excess precision)."""
    import jax
    import jax.numpy as jnp

    if dtype == jnp.float32:
        return lambda a: a
    info = jnp.finfo(dtype)
    return lambda a: jax.lax.reduce_precision(a, info.nexp, info.nmant)


def route(block, x, router_w, router_bias):
    """The router: ``(idx [T, k] int32, w [T, k] float32)``. Sigmoid
    scores in ``router_dtype`` (float32: the dot at the highest
    precision, so a near-tie falls the way the reference's does; a
    narrower type: logits and scores rounded to it); the
    bias takes part in the choice only; the chosen scores are
    renormalised and scaled. A block that states ``n_group`` > 1
    chooses among the experts of each token's best ``topk_group``
    groups only (:func:`group_limited`)."""
    import jax
    import jax.numpy as jnp

    rd = jnp.dtype(block.router_dtype)
    stored = narrowed(rd)
    logits = stored(jnp.dot(x.astype(rd), router_w.astype(rd),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=rd))
    scores = stored(jax.nn.sigmoid(logits)).astype(jnp.float32)
    choice = scores + router_bias[None, :]
    n_group = getattr(block, "n_group", 1)
    if n_group > 1:
        choice = group_limited(choice, n_group, block.topk_group)
    _top, idx = jax.lax.top_k(choice, block.experts_per_token)
    picked = jnp.take_along_axis(scores, idx, axis=1)
    w = picked / (jnp.sum(picked, axis=1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), w * block.routed_scaling_factor


def group_limited(choice, n_group, topk_group):
    """``choice [T, E]`` (score + bias) with every expert outside a
    token's best ``topk_group`` of ``n_group`` groups set to ``-inf``.
    The experts are grouped in order (``E / n_group`` consecutive ids a
    group); a group's score is the sum of its two largest entries."""
    import jax
    import jax.numpy as jnp

    T, E = choice.shape
    grouped = choice.reshape(T, n_group, E // n_group)
    best2, _ = jax.lax.top_k(grouped, 2)
    _top, keep = jax.lax.top_k(jnp.sum(best2, axis=-1), topk_group)
    kept = jnp.any(keep[:, :, None]
                   == jnp.arange(n_group, dtype=keep.dtype)[None, None, :],
                   axis=1)                                  # [T, n_group]
    return jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(T, E)


def expert_layer(block, x, valid, idx, w, we_gate, we_up, we_down,
                 act_dtype, use_gmm, block_m=None):
    """The held experts' part of the routed result, ``[T, D]`` float32,
    and this layer's counters (int32, :data:`COUNTERS`).

    x: ``[T, D]`` tokens (the normalised residual); valid: ``[T]`` bool
    (rows that hold no token route nowhere); idx, w: the router's
    choice over ALL experts. Pairs whose expert is held here are
    counted into expert order (a counting sort: each pair's rank within
    its expert from a running count), laid out so that every tile of
    ``block_m`` rows belongs to one expert, pushed through one grouped
    matmul a projection, gathered back and combined with ``w``. The
    layout has room for every pair, so nothing is dropped;
    ``expert_pairs`` counts what was placed."""
    import jax
    import jax.numpy as jnp

    from ..ops import pallas_kernels as pk

    T, D = x.shape
    k = idx.shape[1]
    held = np.asarray(block.experts_held, np.int32)
    Eh = len(held)
    tm = int(block_m or pk.GMM_BLOCK_M)
    # global expert id -> local id; Eh: not held here
    local_of = np.full(block.n_routed_experts, Eh, np.int32)
    local_of[held] = np.arange(Eh, dtype=np.int32)
    pair_e = jnp.asarray(local_of)[idx]                     # [T, k]
    pair_e = jnp.where(valid[:, None], pair_e, Eh).reshape(-1)
    P = T * k                                               # pairs, at most
    onehot = (pair_e[:, None]
              == jnp.arange(Eh, dtype=jnp.int32)[None, :])  # [P, Eh]
    counts = jnp.cumsum(onehot.astype(jnp.int32), axis=0)
    sizes = counts[-1]                                      # [Eh]
    rank = jnp.sum(jnp.where(onehot, counts, 0), axis=1) - 1
    # With four pairs or more a held expert, routing that is anywhere
    # near balanced reaches every expert but e**-4 of them, so every held
    # expert gets a tile and the step streams the same weight bytes
    # whatever the tokens are: a step's time then does not follow the
    # routing (nor, on seeded weights, the seed: PERF.md, PR 27), for at
    # most that share of the weights read in vain. Below that, an expert
    # without a row has no tile and is never read. (A layer that holds a
    # share of the router's experts gets that share of the pairs: with 32
    # of 256 held, a decode step of 48 rows x 4 passes the rule on 192
    # pairs and places 24, so about half of the held bytes it streams
    # reach no row. Without the floor the step's time followed the seed's
    # routing skew and the cell's runs spread 0.86 %: PERF.md, PR 37.)
    floor = 1 if P >= 4 * Eh else 0
    tiles = jnp.maximum(-(-sizes // tm), floor)
    tile_end = jnp.cumsum(tiles)
    start = (tile_end - tiles) * tm                         # padded starts
    M = -(-(T * k + Eh * (tm - 1 + floor)) // tm) * tm
    placed = pair_e < Eh             # the layout has room for every pair
    dest = jnp.where(placed,
                     jnp.take(start, jnp.minimum(pair_e, Eh - 1)) + rank,
                     M)                                     # M: nowhere
    # each layout row's source token (T: the zero row)
    src = jnp.full((M,), T, jnp.int32).at[dest].set(
        jnp.arange(P, dtype=jnp.int32) // k, mode="drop")
    x_rows = jnp.concatenate(
        [x.astype(act_dtype), jnp.zeros((1, D), act_dtype)])[src]
    n_tiles = M // tm
    n_used = tile_end[-1]
    # the expert of each tile; tiles past the used ones repeat the last
    tile_ids = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32),
                           jnp.maximum(n_used - 1, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, tile_ids, side="right"),
        Eh - 1).astype(jnp.int32)
    if use_gmm:
        def mm(rows, w):
            return pk.gmm(rows, w.astype(act_dtype), tile_expert, n_used,
                          block_m=tm)
    else:
        def mm(rows, w):
            return pk.gmm_reference(*_operands(act_dtype, rows, w),
                                    tile_expert, n_used, block_m=tm)
    h = (jax.nn.silu(mm(x_rows, we_gate)) * mm(x_rows, we_up)) \
        .astype(act_dtype)
    y_rows = mm(h, we_down)                                 # [M, D] f32
    y_pair = jnp.where(placed[:, None],
                       y_rows[jnp.minimum(dest, M - 1)], 0.0)
    y = jnp.sum(y_pair.reshape(T, k, D) * w[:, :, None], axis=1)
    counters = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0),
                          jnp.max(sizes), jnp.int32(Eh)]).astype(jnp.int32)
    return y, counters


def mla_absorbed(q_nope, q_pe, c_ctx, kpe_ctx, w_uk, w_uv, mask,
                 sm_scale):
    """Absorbed MLA of one row: q_nope ``[C, H, dn]``, q_pe ``[C, H,
    dr]``, the cached rows c_ctx ``[T, r]`` / kpe_ctx ``[T, dr]``,
    w_uk ``[H, dn, r]``, w_uv ``[H, r, dv]``, mask ``[C, T]`` ->
    ``[C, H, dv]``. float32."""
    import jax.numpy as jnp

    q_lat = jnp.einsum("chn,hnr->chr", q_nope, w_uk)
    s = (jnp.einsum("chr,tr->cht", q_lat, c_ctx)
         + jnp.einsum("chd,td->cht", q_pe, kpe_ctx)) * sm_scale
    s = jnp.where(mask[:, None, :], s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    ctx = jnp.einsum("cht,tr->chr", p, c_ctx)
    return jnp.einsum("chr,hrv->chv", ctx, w_uv)


def mla_expanded(q_nope, q_pe, c_ctx, kpe_ctx, w_uk, w_uv, mask,
                 sm_scale):
    """Expanded MLA of one row, same operands: per-head keys
    ``[k_nope | k_pe]`` and values rebuilt from the cached rows."""
    import jax.numpy as jnp

    k_nope = jnp.einsum("tr,hnr->thn", c_ctx, w_uk)
    v = jnp.einsum("tr,hrv->thv", c_ctx, w_uv)
    s = (jnp.einsum("chn,thn->cht", q_nope, k_nope)
         + jnp.einsum("chd,td->cht", q_pe, kpe_ctx)) * sm_scale
    s = jnp.where(mask[:, None, :], s, -jnp.inf)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    p = p / jnp.sum(p, axis=-1, keepdims=True)
    return jnp.einsum("cht,thv->chv", p, v)


def latent_rows(block, c_new, kpe_new):
    """The cache rows of ``[T]`` tokens, float32 ``[T, cache_row]``: the
    normalised latent, the rotated shared key, zeros up to whole lane
    tiles."""
    import jax.numpy as jnp

    pad = block.cache_row - block.cache_width
    return jnp.concatenate(
        [c_new, kpe_new, jnp.zeros((c_new.shape[0], pad), jnp.float32)],
        axis=-1)


def absorbed_queries(block, q_nope, q_pe, w_uk, act_dtype):
    """The queries of the absorbed form in the cache's own space,
    ``[T, H, cache_row]`` float32, scaled: ``q_nope @ W_UK`` beside the
    rotated ``q_pe``, zeros over the row's padding."""
    import jax.numpy as jnp

    T, H = q_pe.shape[:2]
    sm_scale = float(block.qk_head_dim) ** -0.5
    q_lat = jnp.einsum(
        "thn,hnr->thr", *_operands(act_dtype, q_nope, w_uk),
        preferred_element_type=jnp.float32)
    return jnp.concatenate(
        [q_lat * sm_scale, q_pe * sm_scale,
         jnp.zeros((T, H, block.cache_row - block.cache_width),
                   jnp.float32)], axis=-1)


def context_to_heads(ctx, w_uv, act_dtype):
    """The latent-space context ``[T, H, r]`` brought back to value
    heads ``[T, H, dv]`` (``@ W_UV``)."""
    import jax.numpy as jnp

    return jnp.einsum(
        "thr,hrv->thv", *_operands(act_dtype, ctx, w_uv),
        preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# the forward over a [B, C] window (C = 1: the decode step)
# ---------------------------------------------------------------------------


def _forward(model, weights, tok, pos0, lengths, block_tables, active,
             latent, max_tokens):
    """``tok`` [B, C] through every layer: the window's cache rows are
    written, then attended. Returns (latent, logits [B, V] at each
    row's last valid slot, counters int32 [len(COUNTERS)]).

    Only the attention has to see the ``[B, C]`` window. Everything a
    token does alone (projections, rotary, norms, router, experts,
    shared expert) runs over the window's real tokens, COMPACTED to
    ``max_tokens`` rows (the caller's promise of how many tokens a
    window can hold; ``None`` or ``C == 1``: every slot): a mixed step
    of 128 x 16 slots holds some 140 tokens, and its dense work
    follows the tokens, not the slots."""
    import jax
    import jax.numpy as jnp

    from ..ops.kernel_registry import choose
    from ..ops.pallas_kernels import (latent_paged_attention,
                                      latent_paged_attention_reference,
                                      latent_write, latent_write_reference,
                                      _gathered_context)

    cfg, blk = model.config, model.config.block
    act = jnp.dtype(blk.activation_dtype)
    B, C = tok.shape
    H, D = cfg.n_heads, cfg.d_model
    dn, dr, dv, r = (blk.qk_nope_head_dim, blk.qk_rope_head_dim,
                     blk.v_head_dim, blk.kv_lora_rank)
    eps = blk.rms_norm_eps
    bs = latent.shape[2]
    sm_scale = float(blk.qk_head_dim) ** -0.5
    T = B * C

    slots = jnp.arange(C, dtype=jnp.int32)[None, :]
    pos2d = pos0[:, None] + slots                          # [B, C]
    lengths = jnp.where(active, lengths, 0)    # an inactive row: no token
    valid = (slots < lengths[:, None]).reshape(T)
    last = jnp.arange(B, dtype=jnp.int32) * C + jnp.clip(lengths - 1, 0,
                                                         C - 1)
    if C == 1 or max_tokens is None or max_tokens >= T:
        Tc = T

        def to_window(a):
            return a.reshape((B, C) + a.shape[1:])

        def from_window(a):
            return a.reshape((T,) + a.shape[2:])
    else:
        # the window's tokens in slot order, then `T` for "none": a
        # row's tokens stay together and in order
        Tc = int(max_tokens)
        where = jnp.nonzero(valid, size=Tc, fill_value=T)[0] \
            .astype(jnp.int32)                             # [Tc]
        at = jnp.minimum(where, T - 1)
        slot_of = jnp.full((T + 1,), Tc, jnp.int32).at[where].set(
            jnp.arange(Tc, dtype=jnp.int32))[:T]           # slot -> token

        def to_window(a):
            zero = jnp.zeros((1,) + a.shape[1:], a.dtype)
            return jnp.concatenate([a, zero])[slot_of].reshape(
                (B, C) + a.shape[1:])

        def from_window(a):
            return a.reshape((T,) + a.shape[2:])[at]

        valid = where < T
        last = jnp.minimum(slot_of[last], Tc - 1)
        tok, pos2d = tok.reshape(T)[at], pos2d.reshape(T)[at]
    tok, pos = tok.reshape(Tc), pos2d.reshape(Tc)

    # one dispatch decision a kernel a forward (trace time)
    write = (latent_write if choose("latent_write", width=blk.cache_row,
                                    block_size=bs, window=C)
             else latent_write_reference)
    use_attn = choose("latent_decode" if C == 1 else "latent_window",
                      width=blk.cache_row, v_width=r, block_size=bs,
                      window=C)
    use_gmm = (cfg.n_layers > blk.first_k_dense
               and choose("gmm", k=D, n=blk.moe_d_ff))

    x = jnp.take(weights["embedding"], tok, axis=0).astype(jnp.float32)
    counters = jnp.zeros((len(COUNTERS),), jnp.int32)
    for i in range(cfg.n_layers):
        p = "l%d/" % i
        a = _rms_norm(x, weights[p + "attn_norm"], eps)
        q = _dot(a, weights[p + "wq"], act).reshape(Tc, H, dn + dr)
        kva = _dot(a, weights[p + "wkv_a"], act)           # [Tc, r + dr]
        c_new = _rms_norm(kva[:, :r], weights[p + "kv_norm"], eps)
        kpe_new = rope_interleaved(kva[:, r:], pos, blk.rope_theta)
        q_nope = q[..., :dn]
        q_pe = rope_interleaved(q[..., dn:], pos[:, None], blk.rope_theta)
        with jax.named_scope("latent_write"):
            # the pool goes to the kernels whole, never `latent[i]`
            row = latent_rows(blk, c_new, kpe_new)
            latent = write(latent, to_window(row.astype(latent.dtype)),
                           block_tables, pos0, lengths, layer=i)
        w_uk, w_uv = weights[p + "w_uk"], weights[p + "w_uv"]
        with jax.named_scope("mla_attention"):
            if use_attn or C == 1:
                # absorbed: queries into latent space, scaled here
                q_abs = absorbed_queries(blk, q_nope, q_pe, w_uk, act)
                attend = (latent_paged_attention if use_attn
                          else latent_paged_attention_reference)
                ctx = attend(latent, to_window(q_abs.astype(latent.dtype)),
                             block_tables, pos0, lengths, layer=i,
                             v_width=r)                    # [B, C, H, r]
                o = context_to_heads(from_window(ctx), w_uv, act)
            else:
                # lax chunk path: expanded over the gathered rows
                rows = _gathered_context(latent, i, block_tables) \
                    .astype(jnp.float32)                   # [B, Tk, row]
                t_ids = jnp.arange(rows.shape[1])[None, None, :]
                mask = t_ids <= (pos0[:, None] + slots)[:, :, None]
                o = from_window(jax.vmap(
                    lambda qn, qp, rw, m: mla_expanded(
                        qn, qp, rw[:, :r], rw[:, r:r + dr],
                        w_uk.astype(jnp.float32),
                        w_uv.astype(jnp.float32), m, sm_scale))(
                    to_window(q_nope), to_window(q_pe), rows, mask))
            x = x + _dot(o.reshape(Tc, H * dv), weights[p + "wo"], act)
        f = _rms_norm(x, weights[p + "ffn_norm"], eps)
        if not _is_expert_layer(blk, i):
            with jax.named_scope("ffn"):
                x = x + _swiglu(f, weights[p + "w_gate"],
                                weights[p + "w_up"], weights[p + "w_down"],
                                act)
            continue
        with jax.named_scope("router"):
            idx, w = route(blk, f, weights[p + "router"],
                           weights[p + "router_bias"])
        with jax.named_scope("experts"):
            y, c = expert_layer(blk, f, valid, idx, w,
                                weights[p + "we_gate"],
                                weights[p + "we_up"],
                                weights[p + "we_down"], act, use_gmm)
            counters = counters + c
        if p + "ws_gate" in weights:
            with jax.named_scope("shared_expert"):
                y = y + _swiglu(f, weights[p + "ws_gate"],
                                weights[p + "ws_up"],
                                weights[p + "ws_down"], act)
        x = x + y

    with jax.named_scope("head"):
        x_last = _rms_norm(x[last], weights["final_norm"], eps)
        return latent, _dot(x_last, weights["lm_head"], act), counters


def make_decode_step(model, return_logits=False):
    """The one-token step of the block, the engine's calling convention
    with ONE cache array:

        step(weights, latent, prompt_feed, use_prompt, prev_tokens,
             positions, block_tables, active)
          -> (latent', next_tokens, counters[, logits])"""
    import jax
    import jax.numpy as jnp

    cfg = model.config

    def decode_step(weights, latent, prompt_feed, use_prompt, prev_tokens,
                    positions, block_tables, active):
        model.trace_count += 1
        tok = jnp.where(use_prompt, prompt_feed, prev_tokens)
        tok = jnp.clip(tok, 0, cfg.vocab_size - 1)[:, None]
        latent, logits, counters = _forward(
            model, weights, tok, positions,
            jnp.ones_like(positions), block_tables, active, latent, None)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if return_logits:
            return latent, nxt, counters, logits
        return latent, nxt, counters

    return jax.jit(decode_step, donate_argnums=(1,))


def make_window_step(model, window, return_logits=False, max_tokens=None):
    """The ``[max_batch, window]`` mixed prefill/decode step:

        step(weights, latent, window_tokens[B, C], use_prompt[B],
             prev_tokens[B], positions[B], lengths[B],
             block_tables[B, Mb], active[B])
          -> (latent', next_tokens[B], counters[, logits])"""
    import jax
    import jax.numpy as jnp

    cfg = model.config

    def chunk_step(weights, latent, window_tokens, use_prompt, prev_tokens,
                   positions, lengths, block_tables, active):
        model.trace_count += 1
        tok0 = jnp.where(use_prompt, window_tokens[:, 0], prev_tokens)
        tok = jnp.concatenate([tok0[:, None], window_tokens[:, 1:]], axis=1)
        tok = jnp.clip(tok, 0, cfg.vocab_size - 1)
        latent, logits, counters = _forward(
            model, weights, tok, positions, lengths, block_tables, active,
            latent, max_tokens)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if return_logits:
            return latent, nxt, counters, logits
        return latent, nxt, counters

    return jax.jit(chunk_step, donate_argnums=(1,))
