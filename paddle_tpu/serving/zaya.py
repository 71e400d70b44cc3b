"""The compressed-convolutional-attention / top-1 routed decoder block of
the serving runtime (the ``zaya`` family of layer equations;
docs/SERVING.md, "A fourth block").

``GenerationConfig(block=ZayaBlock(...))`` selects it; the engine,
scheduler, pool accounting, in-flight window and step log are the ones
every model uses. What it states that the other three blocks do not:

  * a ROW STATE beside the K/V pages (``row_state``; kv_cache.RowState):
    queries and keys pass through two causal convolutions of two taps
    over the sequence (one depthwise, one grouped by head) and half of
    the value is the PREVIOUS token's, so a token needs three vectors of
    its predecessor: the pre-convolution latent ``z``, the first
    convolution's output ``c`` and the shifted value's projection ``u
    W_v2``. Inside a chunk the predecessor is the token row before (a
    row's chunk tokens are consecutive in the compacted rows of
    ``model._chunk_layout``); for the first token of a row's chunk, and
    for every decode token, it is what the row's last step left in the
    carry ``[max_batch, n_layers, 2 * (H + Hkv) * Dh + Dh]`` float32,
    which both steps take beside the pools, donated, and hand back with
    the rows they computed rewritten. A token at position 0 has no
    predecessor and reads zeros, so a slot's stale carry needs no reset
    when a new request is admitted to it;
  * grouped-query attention IN THE COMPRESSED LATENT: ``n_heads`` query
    heads on ``n_kv_heads`` = 2 cache heads through afmoe's paged window
    (``afmoe._PagedWindow``: ``gqa_paged_decode_attention``,
    ``gqa_paged_chunk_attention``, ``kv_page_write``), one page kind
    named ``global``, no window; queries and keys get the mean of the
    pre-convolution query and key heads added, are L2-normalised a head
    (keys times a learned temperature a cache head) and rotated on the
    first ``partial_rotary`` of each head's lanes (half-split pairs);
    what is cached is K and V after all of that;
  * a learned scale and bias on both addends of every residual sum;
  * the router: a three-layer MLP on a ``router_hidden``-wide projection
    of the normalised stream, MIXED WITH THE LAYER BEFORE'S router
    state (a second value beside the residual stream passes from layer
    to layer), softmax over all experts, the ONE expert with the largest
    ``p + bias``, weighed by its ``p``; no shared expert. The experts
    are the latent block's (``latent_moe.expert_layer`` and ``gmm``);
  * the head is the embedding (tied).

Weights bfloat16 (gains, biases, the depthwise taps and the whole
router float32); matmul operands rounded to ``activation_dtype`` and
accumulated in float32; the residual stream, the carry, norms,
convolution sums, rotary, router and softmax statistics float32
(latent_moe's precision plan).

Not built, and refused with one error each: speculative, tree and draft
windows (``GenerationModel._no_such_step``), the prefix cache (the
engine: no carry exists at an adopted page boundary), ``quantized()``.
"""

from .afmoe import _PagedWindow, decode_pages_per_run, rope_half_split
from .kv_cache import CacheEntry, PageKind
from .latent_moe import (COUNTERS, BlockDescription, _dot, _normal,
                         _operands, _rms_norm, expert_layer, held_experts,
                         narrowed)

__all__ = ["ZayaBlock", "leaf_shapes", "random_weights",
           "make_decode_step", "make_window_step", "route"]

RESIDUAL_LEAVES = tuple(s + "_" + r for s in ("attn", "ffn") for r in
                        ("res_scale", "res_bias", "out_scale", "out_bias"))
# float32 leaves that random weights leave at 1 and at 0
ONES = ("norm", "scale", "k_temp", "router_mix")
ZEROS = ("bias", "conv0_b", "conv1_b", "router_b1", "router_b2")


class ZayaBlock(BlockDescription):
    """The block's description, carried by ``GenerationConfig.block``
    (``d_model``, ``n_heads``, ``n_layers`` and ``vocab_size`` stay on
    the configuration; it has no dense width)."""

    kind = "zaya"
    # the expert layers' four, then the token rows of a step whose
    # predecessor came from the carry (not from the same chunk)
    step_counters = COUNTERS + ("carry_rows",)
    returns_top_logit = True
    tied_head = True        # logits = h E^T: the embedding is a dot operand
    FIELDS = ("n_kv_heads", "head_dim", "conv_taps", "partial_rotary",
              "rope_theta", "rms_norm_eps", "router_hidden",
              "n_routed_experts", "experts_per_token", "moe_d_ff",
              "experts_held", "weight_dtype", "activation_dtype",
              "router_dtype", "cache_dtype", "ignore_carry")

    def __init__(self, n_kv_heads, head_dim, router_hidden,
                 n_routed_experts, moe_d_ff, conv_taps=(2, 2),
                 partial_rotary=0.5, rope_theta=10000.0, rms_norm_eps=1e-5,
                 experts_per_token=1, experts_held=None,
                 weight_dtype="bfloat16", activation_dtype="bfloat16",
                 router_dtype="float32", cache_dtype="bfloat16",
                 ignore_carry=False):
        self.n_kv_heads = int(n_kv_heads)
        self.head_dim = int(head_dim)
        self.conv_taps = tuple(int(n) for n in conv_taps)
        self.partial_rotary = float(partial_rotary)
        self.rope_theta = float(rope_theta)
        self.rms_norm_eps = float(rms_norm_eps)
        self.router_hidden = int(router_hidden)
        self.n_routed_experts = int(n_routed_experts)
        self.experts_per_token = int(experts_per_token)
        self.moe_d_ff = int(moe_d_ff)
        self.experts_held = held_experts(self.n_routed_experts,
                                         experts_held)
        if self.n_kv_heads != 2:
            raise ValueError("the value shift fills two cache heads: this "
                             "token's value and the one before's")
        if self.conv_taps != (2, 2):
            raise NotImplementedError(
                "convolutions of %r taps: the row state holds one "
                "position" % (self.conv_taps,))
        if self.experts_per_token != 1:
            raise NotImplementedError("the router picks one expert")
        if self.rotary_lanes % 2:
            raise ValueError("partial_rotary * head_dim must be even "
                             "(rotary pairs)")
        self.weight_dtype = str(weight_dtype)
        self.activation_dtype = str(activation_dtype)
        self.router_dtype = str(router_dtype)
        self.cache_dtype = str(cache_dtype)
        # the benchmark's control: the steps read every row's carry as
        # zero, as if each chunk and each decode token began a sequence
        self.ignore_carry = bool(ignore_carry)

    @property
    def cache_width(self):
        """Values of K (and of V) a token caches in a layer."""
        return self.n_kv_heads * self.head_dim

    @property
    def rotary_lanes(self):
        return int(self.head_dim * self.partial_rotary)

    def cache_entry(self):
        return CacheEntry((("k", (self.cache_width,)),
                           ("v", (self.cache_width,))), self.cache_dtype)

    decode_pages_per_run = staticmethod(decode_pages_per_run)

    def page_kinds(self, config):
        """One kind, every layer, every position; named, so that the
        step log carries ``global_pages_walked`` and its kin."""
        return (PageKind("global", range(config.n_layers)),)

    def latent_width(self, config):
        """``z``: the query and key heads side by side."""
        return (config.n_heads + self.n_kv_heads) * self.head_dim

    def row_state(self, config):
        """What a batch row carries from step to step beside its pages:
        (shape a row, dtype). A layer's ``[z | c | u W_v2]`` of the
        row's last token."""
        return ((config.n_layers,
                 2 * self.latent_width(config) + self.head_dim), "float32")

    def random_weights(self, config, seed=0, scale=0.1):
        return random_weights(config, seed, scale)


def leaf_shapes(config):
    """{weight name: (shape, dtype name)}: the serving layout."""
    blk = config.block
    D, V, H = config.d_model, config.vocab_size, config.n_heads
    Dh, Hkv, R = blk.head_dim, blk.n_kv_heads, blk.router_hidden
    Z = blk.latent_width(config)
    E, Eh, Fe = (blk.n_routed_experts, len(blk.experts_held), blk.moe_d_ff)
    n0, n1 = blk.conv_taps
    w, f32 = blk.weight_dtype, "float32"
    out = {"embedding": ((V, D), w), "final_norm": ((D,), f32)}
    for i in range(config.n_layers):
        p = "l%d/" % i
        out.update({
            p + "attn_norm": ((D,), f32),
            p + "wq": ((D, H * Dh), w), p + "wk": ((D, Hkv * Dh), w),
            p + "wv1": ((D, Dh), w), p + "wv2": ((D, Dh), w),
            p + "wo": ((H * Dh, D), w),
            p + "conv0_w": ((n0, Z), f32), p + "conv0_b": ((Z,), f32),
            p + "conv1_w": ((n1, H + Hkv, Dh, Dh), w),
            p + "conv1_b": ((Z,), f32), p + "k_temp": ((Hkv,), f32),
            p + "ffn_norm": ((D,), f32),
            p + "router_down": ((D, R), f32),
            p + "router_mix": ((R,), f32), p + "router_norm": ((R,), f32),
            p + "router_w1": ((R, R), f32), p + "router_b1": ((R,), f32),
            p + "router_w2": ((R, R), f32), p + "router_b2": ((R,), f32),
            p + "router_w3": ((R, E), f32), p + "router_bias": ((E,), f32),
            p + "we_gate": ((Eh, D, Fe), w), p + "we_up": ((Eh, D, Fe), w),
            p + "we_down": ((Eh, Fe, D), w)})
        out.update({p + n: ((D,), f32) for n in RESIDUAL_LEAVES})
    return out


def random_weights(config, seed=0, scale=0.1):
    """Deterministic random weights in the serving layout (tests, the
    chip smoke): N(0, scale) matrices and taps, gains 1, biases 0. Made
    on the default device, a leaf at a time."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)
    out = {}
    for n, (name, (shape, dtype)) in enumerate(leaf_shapes(config).items()):
        if name.endswith(ONES):
            out[name] = jnp.ones(shape, dtype)
        elif name.endswith(ZEROS):
            out[name] = jnp.zeros(shape, dtype)
        else:
            out[name] = _normal(jax.random.fold_in(key, n), shape, dtype,
                                scale)
    return out


def route(block, x, weights, p, prev):
    """The router of layer ``p`` over the normalised stream ``x [T, D]``:
    ``(idx [T, 1] int32, w [T, 1] float32, r [T, R])``. ``r`` is this
    layer's router state, its projection mixed with the layer before's
    (``prev``; None: the first layer held); the MLP, the softmax over ALL
    experts and the choice in ``router_dtype`` (float32: dots at the
    highest precision, so a near-tie falls the way the reference's does;
    a narrower type: every intermediate rounded to it). The bias takes
    part in the choice only; the one chosen expert is weighed by its
    ``p``."""
    import jax
    import jax.numpy as jnp

    rd = jnp.dtype(block.router_dtype)
    stored = narrowed(rd)

    def dot(a, name):
        return stored(jnp.dot(a.astype(rd), weights[p + name].astype(rd),
                              precision=jax.lax.Precision.HIGHEST,
                              preferred_element_type=rd))

    def gelu(a):
        return stored(jax.nn.gelu(a.astype(jnp.float32), approximate=False))

    r = dot(x, "router_down").astype(jnp.float32)
    if prev is not None:
        r = stored(r + weights[p + "router_mix"] * prev)
    y = stored(_rms_norm(r, weights[p + "router_norm"], block.rms_norm_eps))
    y = gelu(dot(y, "router_w1") + weights[p + "router_b1"])
    y = gelu(dot(y, "router_w2") + weights[p + "router_b2"])
    probs = stored(jax.nn.softmax(
        dot(y, "router_w3").astype(jnp.float32), axis=-1))
    _top, idx = jax.lax.top_k(probs + weights[p + "router_bias"][None, :], 1)
    return (idx.astype(jnp.int32), jnp.take_along_axis(probs, idx, axis=1),
            r)


def _l2_normalised(x, scale):
    import jax
    import jax.numpy as jnp

    return x * (scale * jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + 1e-12))


def _partial_rope(x, positions, lanes, theta):
    import jax.numpy as jnp

    return jnp.concatenate(
        [rope_half_split(x[..., :lanes], positions, theta), x[..., lanes:]],
        axis=-1)


def _forward(model, weights, tok, pos0, lengths, tables, active, pools,
             carry, max_tokens):
    """``tok`` [B, C] through every layer (afmoe's paged window: each
    layer's new K and V written into its pages, then attended), the
    convolutions and the value shift reaching one token back: into the
    row before where that is the same window row's, into ``carry [B, L,
    W]`` otherwise. Returns (pools, carry, logits [B, V] at each row's
    last valid slot, counters int32 [len(step_counters)])."""
    import jax
    import jax.numpy as jnp

    from ..ops.kernel_registry import choose

    cfg, blk = model.config, model.config.block
    act = jnp.dtype(blk.activation_dtype)
    B, C = tok.shape
    H, D, Dh, Hkv = cfg.n_heads, cfg.d_model, blk.head_dim, blk.n_kv_heads
    G, Z = H // Hkv, blk.latent_width(cfg)
    eps, lanes = blk.rms_norm_eps, blk.rotary_lanes
    win = _PagedWindow(model, tok, pos0, lengths, tables, active, pools,
                       max_tokens)
    Tc, tok, pos, valid = win.Tc, win.tok, win.pos, win.valid
    if win.at is None:                  # the decode step: row b, one token
        row, first = jnp.arange(B), jnp.ones((B,), bool)
    else:                               # the first slot of a window row
        row, first = win.at // C, win.at % C == 0
    has_prev = (pos > 0)[:, None]
    carry_in = jnp.zeros_like(carry) if blk.ignore_carry else carry

    def previous(x, carried):
        """``x [Tc, W]`` one token back: the token row before, or
        ``carried [B, W]`` for a row's first token of the step; zeros at
        position 0."""
        if win.at is None:
            prev = carried
        else:
            prev = jnp.where(first[:, None], carried[row],
                             jnp.roll(x, 1, axis=0))
        return jnp.where(has_prev, prev, 0.0)

    use_gmm = choose("gmm", k=D, n=blk.moe_d_ff)
    x = jnp.take(weights["embedding"], tok, axis=0).astype(jnp.float32)
    expert_counters = jnp.zeros((len(COUNTERS),), jnp.int32)
    r, left = None, []
    for i in range(cfg.n_layers):
        p = "l%d/" % i

        def residual(h, y, sub):
            return ((h + weights[p + sub + "_res_bias"])
                    * weights[p + sub + "_res_scale"]
                    + (y + weights[p + sub + "_out_bias"])
                    * weights[p + sub + "_out_scale"])

        with jax.named_scope("cca_prepare"):
            a = _rms_norm(x, weights[p + "attn_norm"], eps)
            q0 = _dot(a, weights[p + "wq"], act)
            k0 = _dot(a, weights[p + "wk"], act)
            z = jnp.concatenate([q0, k0], axis=-1)              # [Tc, Z]
            taps = weights[p + "conv0_w"]
            c = (taps[0] * previous(z, carry_in[:, i, :Z]) + taps[1] * z
                 + weights[p + "conv0_b"])
            c_prev = previous(c, carry_in[:, i, Z:2 * Z])
            conv = sum(jnp.einsum(
                "tgi,gio->tgo", *_operands(act, ci.reshape(Tc, H + Hkv, Dh),
                                           weights[p + "conv1_w"][j]),
                preferred_element_type=jnp.float32)
                for j, ci in enumerate((c_prev, c))).reshape(Tc, Z) \
                + weights[p + "conv1_b"]
            q0 = q0.reshape(Tc, H, Dh)
            mean = 0.5 * (q0 + jnp.repeat(k0.reshape(Tc, Hkv, Dh), G,
                                          axis=1))
            q = conv[:, :H * Dh].reshape(Tc, H, Dh) + mean
            k = conv[:, H * Dh:].reshape(Tc, Hkv, Dh) \
                + jnp.mean(mean.reshape(Tc, Hkv, G, Dh), axis=2)
            shifted = _dot(a, weights[p + "wv2"], act)          # [Tc, Dh]
            v = jnp.concatenate(
                [_dot(a, weights[p + "wv1"], act),
                 previous(shifted, carry_in[:, i, 2 * Z:])], axis=-1)
            q = _l2_normalised(q, float(Dh) ** 0.5)
            k = _l2_normalised(k, float(Dh) ** 0.5) \
                * weights[p + "k_temp"][None, :, None]
            q = _partial_rope(q, pos[:, None], lanes, blk.rope_theta)
            k = _partial_rope(k, pos[:, None], lanes, blk.rope_theta)
            # what the row's next step reads: its last token's
            left.append(jnp.concatenate([z, c, shifted], axis=-1)[win.last])
        with jax.named_scope("cca_attend"):
            win.write(i, k.reshape(Tc, Hkv * Dh), v)
            o = win.attend(i, q).reshape(Tc, H * Dh)
            x = residual(x, _dot(o, weights[p + "wo"], act), "attn")
        f = _rms_norm(x, weights[p + "ffn_norm"], eps)
        with jax.named_scope("zaya_router"):
            idx, w, r = route(blk, f, weights, p, r)
        with jax.named_scope("experts"):
            y, n = expert_layer(blk, f, valid, idx, w,
                                weights[p + "we_gate"],
                                weights[p + "we_up"],
                                weights[p + "we_down"], act, use_gmm)
            expert_counters = expert_counters + n
        x = residual(x, y, "ffn")

    moved = win.lens > 0
    carry = jnp.where(moved[:, None, None], jnp.stack(left, axis=1), carry)
    counters = jnp.concatenate([expert_counters, jnp.sum(
        valid & first & (pos > 0), dtype=jnp.int32)[None]])
    with jax.named_scope("head"):
        x_last = _rms_norm(x[win.last], weights["final_norm"], eps)
        logits = jnp.einsum(
            "bd,vd->bv", *_operands(act, x_last, weights["embedding"]),
            preferred_element_type=jnp.float32)
        return tuple(win.pools), carry, logits, counters


def _steps(model, window_step, return_logits, max_tokens):
    """Both compiled steps: K, V and the carry donated, then the engine's
    arguments (the chunk step's with its window and lengths)."""
    import jax
    import jax.numpy as jnp

    cfg = model.config

    def step(weights, k_pool, v_pool, carry, feed, use_prompt, prev_tokens,
             positions, *rest):
        model.trace_count += 1
        if window_step:
            lengths, block_tables, active = rest
            tok0 = jnp.where(use_prompt, feed[:, 0], prev_tokens)
            tok = jnp.concatenate([tok0[:, None], feed[:, 1:]], axis=1)
        else:
            block_tables, active = rest
            lengths = jnp.ones_like(positions)
            tok = jnp.where(use_prompt, feed, prev_tokens)[:, None]
        tok = jnp.clip(tok, 0, cfg.vocab_size - 1)
        pools, carry, logits, counters = _forward(
            model, weights, tok, positions, lengths, block_tables, active,
            (k_pool, v_pool), carry, max_tokens if window_step else None)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = pools + (carry, nxt, counters, jnp.max(logits, axis=-1))
        return out + (logits,) if return_logits else out

    step.__name__ = "chunk_step" if window_step else "decode_step"
    return jax.jit(step, donate_argnums=(1, 2, 3))


def make_decode_step(model, return_logits=False):
    """The one-token step of the block, the engine's calling convention
    with the row state after the pools:

        step(weights, k_pool, v_pool, carry, prompt_feed, use_prompt,
             prev_tokens, positions, block_tables[B, Mb], active)
          -> (k_pool', v_pool', carry', next_tokens, counters,
              top_logit[, logits])"""
    return _steps(model, False, return_logits, None)


def make_window_step(model, window, return_logits=False, max_tokens=None):
    """The ``[max_batch, window]`` mixed prefill/decode step:

        step(weights, k_pool, v_pool, carry, window_tokens[B, C],
             use_prompt[B], prev_tokens[B], positions[B], lengths[B],
             block_tables[B, Mb], active[B])
          -> (k_pool', v_pool', carry', next_tokens[B], counters,
              top_logit[B][, logits])"""
    return _steps(model, True, return_logits, max_tokens)
