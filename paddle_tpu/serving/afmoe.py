"""The grouped-query window/global decoder block of the serving runtime
(the ``afmoe`` family of layer equations; docs/SERVING.md, "A third
block").

``GenerationConfig(block=AfmoeBlock(...))`` selects it; the engine,
scheduler, pool accounting, in-flight window and step log are the ones
every model uses. What it states that the other two blocks do not:

  * GROUPED-QUERY attention: ``n_heads`` query heads of ``head_dim`` on
    ``n_kv_heads`` cache heads (query head ``n`` reads cache head ``n //
    (n_heads // n_kv_heads)``); ``head_dim`` is the block's own, not
    ``d_model // n_heads``. A token caches K and V of ``n_kv_heads *
    head_dim`` values a layer, bfloat16, a page ``[block_size, n_kv_heads
    * head_dim]`` (the cache heads side by side in the lanes: a packed
    2-D tile, whatever the number of heads);
  * WINDOW and GLOBAL layers mixed (``layer_types``): a
    ``sliding_attention`` layer's position ``t`` sees ``t -
    sliding_window < s <= t``, a ``full_attention`` layer's every ``s <=
    t``. They keep their cache in two KINDS of page
    (``page_kinds``; kv_cache.PageKind): the window kind's pages are
    released as a sequence's positions slide out, and a step gets a
    block table a kind;
  * rotary embedding on the window layers ONLY, over the whole head in
    HALF-SPLIT pairs ``(x_j, x_{j + head_dim/2})``; a global layer's
    queries and keys are not rotated at all;
  * an RMSNorm over ``head_dim`` on every query and key head (one gain
    vector each, shared by the heads), a sigmoid OUTPUT GATE ``g = x
    W_g`` multiplied into the attention's context in front of ``W_o``;
  * four RMSNorms a layer: before AND after each sublayer, the residual
    added after the second (``h += N2(Attn(N1(h)))``, ``h +=
    N4(FFN(N3(h)))``);
  * the embedding scaled by ``sqrt(d_model)`` (``mup_enabled``);
  * ``n_dense_layers`` leading SwiGLU layers, then expert layers: the
    router, the held experts and the counters are the latent block's
    (``latent_moe.route``, ``latent_moe.expert_layer``: sigmoid scores,
    the top ``experts_per_token`` of ``score + bias``, renormalised and
    scaled; the layer computes the experts it HOLDS and its shared
    expert, and that partial result goes on).

Weights bfloat16 (norm gains, the router and its bias float32); matmul
operands rounded to ``activation_dtype`` and accumulated in float32; the
residual stream, the norms, rotary, router, softmax statistics, the gate
and the combine float32 (latent_moe's precision plan).

Not built, and refused with one error each: speculative, tree and draft
windows (``GenerationModel._no_such_step``), the prefix cache (the
engine: a released page cannot be adopted), ``quantized()``.
"""

from .kv_cache import CacheEntry, PageKind
from .latent_moe import (COUNTERS, BlockDescription, _dot, _rms_norm,
                         _swiglu, expert_layer, held_experts,
                         random_weights, route)

__all__ = ["AfmoeBlock", "leaf_shapes", "random_weights",
           "make_decode_step", "make_window_step", "rope_half_split",
           "ATTN_TILE"]

# Query tokens in one attention tile of the chunk step: a tile's rows in
# the kernel are ``tile * (n_heads // n_kv_heads)`` a cache head
ATTN_TILE = 128
SLIDING, FULL = "sliding_attention", "full_attention"


def decode_pages_per_run(pool, table_len):
    """The pages of one run of the page pipe of the decode kernel that
    :class:`_PagedWindow` calls, over ``pool``
    (``pk.gqa_pages_per_run``, which the kernel asks too). A block that
    attends through that class states it as its own
    ``decode_pages_per_run``, and the step log counts the runs by it
    (``engine._decode_pipe_walked``)."""
    from ..ops.pallas_kernels import gqa_pages_per_run

    return gqa_pages_per_run(pool, table_len)


class AfmoeBlock(BlockDescription):
    """The block's description, carried by ``GenerationConfig.block``
    (``d_model``, ``n_heads``, ``n_layers``, ``vocab_size`` and the
    dense width ``d_ff`` stay on the configuration)."""

    kind = "afmoe"
    step_counters = COUNTERS
    # the steps return each row's chosen token's logit after the counters
    returns_top_logit = True
    FIELDS = ("n_kv_heads", "head_dim", "layer_types", "sliding_window",
              "rope_theta", "rms_norm_eps", "n_dense_layers",
              "n_routed_experts", "experts_per_token", "n_shared_experts",
              "moe_d_ff", "routed_scaling_factor", "experts_held",
              "mup_enabled", "weight_dtype", "activation_dtype",
              "router_dtype", "cache_dtype")

    def __init__(self, n_kv_heads, head_dim, layer_types, sliding_window,
                 n_routed_experts, experts_per_token, n_shared_experts,
                 moe_d_ff, rope_theta=10000.0, rms_norm_eps=1e-5,
                 n_dense_layers=1, routed_scaling_factor=1.0,
                 experts_held=None, mup_enabled=True,
                 weight_dtype="bfloat16", activation_dtype="bfloat16",
                 router_dtype="float32", cache_dtype="bfloat16"):
        self.n_kv_heads = int(n_kv_heads)
        self.head_dim = int(head_dim)
        self.layer_types = tuple(str(t) for t in layer_types)
        if set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError("layer_types are %r or %r, got %r"
                             % (SLIDING, FULL, self.layer_types))
        self.sliding_window = int(sliding_window)
        self.rope_theta = float(rope_theta)
        self.rms_norm_eps = float(rms_norm_eps)
        self.n_dense_layers = int(n_dense_layers)
        self.n_routed_experts = int(n_routed_experts)
        self.experts_per_token = int(experts_per_token)
        self.n_shared_experts = int(n_shared_experts)
        self.moe_d_ff = int(moe_d_ff)
        self.routed_scaling_factor = float(routed_scaling_factor)
        # the experts this chip holds (global ids, ascending); None: all
        self.experts_held = held_experts(self.n_routed_experts,
                                         experts_held)
        if self.head_dim % 2:
            raise ValueError("head_dim must be even (rotary pairs)")
        self.mup_enabled = bool(mup_enabled)
        self.weight_dtype = str(weight_dtype)
        self.activation_dtype = str(activation_dtype)
        self.router_dtype = str(router_dtype)
        self.cache_dtype = str(cache_dtype)

    @property
    def cache_width(self):
        """Values of K (and of V) a token caches in a layer."""
        return self.n_kv_heads * self.head_dim

    def cache_entry(self):
        return CacheEntry((("k", (self.cache_width,)),
                           ("v", (self.cache_width,))), self.cache_dtype)

    decode_pages_per_run = staticmethod(decode_pages_per_run)

    def page_kinds(self, config):
        """The global layers' pages first (they keep every position),
        then the window layers'; None where every layer is global (one
        kind: the pool every model had)."""
        self._check_depth(config)
        by_type = {t: [i for i, lt in enumerate(self.layer_types)
                       if lt == t] for t in (FULL, SLIDING)}
        if not by_type[SLIDING]:
            return None
        if not by_type[FULL]:
            raise NotImplementedError(
                "a model of window layers only: the pool's first page "
                "kind keeps every position (kv_cache.KVBlockPool)")
        return (PageKind("global", by_type[FULL]),
                PageKind("window", by_type[SLIDING],
                         window=self.sliding_window))

    def _check_depth(self, config):
        if len(self.layer_types) != config.n_layers:
            raise ValueError("%d layer_types for %d layers"
                             % (len(self.layer_types), config.n_layers))


def _is_expert_layer(block, i):
    return i >= block.n_dense_layers


def leaf_shapes(config):
    """{weight name: (shape, dtype name)}: the serving layout."""
    blk = config.block
    blk._check_depth(config)
    D, V, H, F = (config.d_model, config.vocab_size, config.n_heads,
                  config.d_ff)
    Dh, KV = blk.head_dim, blk.cache_width
    E, Eh, Fe = (blk.n_routed_experts, len(blk.experts_held), blk.moe_d_ff)
    Fs = blk.n_shared_experts * Fe
    w, f32 = blk.weight_dtype, "float32"
    out = {"embedding": ((V, D), w), "lm_head": ((D, V), w),
           "final_norm": ((D,), f32)}
    for i in range(config.n_layers):
        p = "l%d/" % i
        out.update({
            p + "attn_norm": ((D,), f32),
            p + "wq": ((D, H * Dh), w), p + "wk": ((D, KV), w),
            p + "wv": ((D, KV), w), p + "wg": ((D, H * Dh), w),
            p + "q_norm": ((Dh,), f32), p + "k_norm": ((Dh,), f32),
            p + "wo": ((H * Dh, D), w),
            p + "attn_post_norm": ((D,), f32),
            p + "ffn_norm": ((D,), f32),
            p + "ffn_post_norm": ((D,), f32)})
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


def rope_half_split(x, positions, theta):
    """Rotary embedding over the last axis of ``x`` (even width ``d``),
    the pairs being the lanes ``(j, j + d/2)``; ``positions`` broadcasts
    against ``x``'s leading axes. float32. One lane roll by half the
    head, no ``[.., 2, d/2]`` view."""
    import jax.numpy as jnp

    d = x.shape[-1]
    lane = jnp.arange(d)
    inv_freq = theta ** (-(lane % (d // 2)).astype(jnp.float32) / (d // 2))
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    # lane j < d/2 gets -x[j + d/2], lane j >= d/2 gets x[j - d/2]
    partner = jnp.roll(x, d // 2, axis=-1) * jnp.where(lane < d // 2,
                                                       -1.0, 1.0)
    return x * jnp.cos(angle) + partner * jnp.sin(angle)


def _write_units(jnp, pos0, lens, first_row, bs, n_units, n_rows):
    """The pages a step's new K/V rows fall into, one UNIT a page
    (``kv_page_write``): for unit ``u`` the batch row ``row`` and table
    slot ``slot`` of its page (``used`` false: no page), the page rows
    ``lo <= r < hi`` it rewrites, and ``src [U, bs]`` the token row that
    page row ``r`` takes. The same for every layer and page kind; a
    kind's table turns (row, slot) into its page id."""
    i32 = jnp.int32
    B = pos0.shape[0]
    p0 = pos0 // bs
    touched = jnp.where(lens > 0, (pos0 + lens - 1) // bs - p0 + 1, 0)
    end = jnp.cumsum(touched)
    u = jnp.arange(n_units, dtype=i32)
    row = jnp.minimum(jnp.searchsorted(end, u, side="right"),
                      B - 1).astype(i32)
    used = u < end[-1]
    slot = p0[row] + u - (end - touched)[row]
    first = slot * bs
    lo = jnp.where(used, jnp.clip(pos0[row] - first, 0, bs), 0)
    hi = jnp.where(used, jnp.clip(pos0[row] + lens[row] - first, 0, bs), 0)
    src = (first_row[row] + first - pos0[row])[:, None] \
        + jnp.arange(bs, dtype=i32)[None, :]
    return {"row": row, "slot": slot, "used": used, "lo": lo, "hi": hi,
            "src": jnp.clip(src, 0, n_rows - 1)}


class _PagedWindow:
    """The paged grouped-query attention of one step over a ``[B, C]``
    window of tokens, shared by every layer: where the window's token
    rows come from, the pages their new K and V fall into, and the
    kernels chosen. ``pools`` is ``(k, v)`` a page kind (kept as a list
    and replaced by every :meth:`write`), ``tables`` ``[kinds, B, Mb]``
    (or ``[B, Mb]``: one kind).

    C == 1 is the decode step: every row one token. Otherwise the
    window's real tokens are COMPACTED to ``max_tokens`` token rows
    (``model._chunk_layout``, the XGLM chunk step's) for everything a
    token does alone, and the attention sees them as query tiles of
    :data:`ATTN_TILE` tokens.

    ``tok [Tc]``, ``pos [Tc]``, ``valid [Tc]``: the token rows; ``lens
    [B]`` the tokens a window row holds and ``last [B]`` its last token
    row; ``at [Tc]`` the window slot ``b * C + c`` a token row holds
    (None: the decode step, row ``b``)."""

    def __init__(self, model, tok, pos0, lengths, tables, active, pools,
                 max_tokens):
        import jax.numpy as jnp

        from ..ops import pallas_kernels as pk
        from ..ops.kernel_registry import choose
        from .model import _chunk_layout, chunk_tile_count

        cfg, blk = model.config, model.config.block
        B, C = tok.shape
        H, Dh, Hkv = cfg.n_heads, blk.head_dim, blk.n_kv_heads
        self.pools = pools = list(pools)
        bs = pools[0].shape[2]
        if tables.ndim == 2:
            tables = tables[None]
        Mb = tables.shape[2]
        self.kinds = kinds = blk.page_kinds(cfg) or (
            PageKind("all", range(cfg.n_layers)),)
        # layer -> (its kind, its index in the kind's arrays)
        self.where = {layer: (k, j) for k, kind in enumerate(kinds)
                      for j, layer in enumerate(kind.layers)}
        self.lens = lens = jnp.where(
            active, jnp.clip(lengths, 0, C), 0).astype(jnp.int32)
        pos0 = jnp.maximum(pos0, 0).astype(jnp.int32)

        use_write = choose("kv_page_write", head_dim=Dh, block_size=bs)
        self._write = (pk.kv_page_write if use_write
                       else pk.kv_page_write_reference)
        if C == 1:
            self.Tc, self.at = B, None
            self.tok, self.pos, self.valid = tok.reshape(B), pos0, lens > 0
            valid = self.valid
            slot = jnp.clip(pos0 // bs, 0, Mb - 1)
            rows = jnp.arange(B)
            self._units = {"lo": jnp.where(valid, pos0 % bs, 0),
                           "hi": jnp.where(valid, pos0 % bs + 1, 0)}
            self._unit_pages = [jnp.where(valid, t[rows, slot], 0)
                                for t in tables]
            use_attn = choose("gqa_decode", head_dim=Dh, block_size=bs)

            def new_rows(a):                    # [B, W] -> one row a unit
                return a[:, None, :]

            def attend(q, k, j, kind):
                fn = (pk.gqa_paged_decode_attention if use_attn
                      else pk.gqa_paged_decode_attention_reference)
                return fn(pools[2 * k], pools[2 * k + 1], q, tables[k],
                          pos0, layer=j, window=kind.window, active=valid)

            self.last = rows
        else:
            T = B * C
            self.Tc = Tc = T if max_tokens is None \
                else min(int(max_tokens), T)
            # a power of two of slots (the kernels find a stacked row's
            # token by a bit mask)
            Cq = 1 << (min(ATTN_TILE, C).bit_length() - 1)
            n_tiles = chunk_tile_count(B, C, None if Tc == T else Tc,
                                       tile=Cq)
            layouts = [_chunk_layout(jnp, pos0, lens, active, t, C, Tc, Cq,
                                     n_tiles, bs) for t in tables]
            lay = layouts[0]
            self.at = lay["at"]
            self.tok, self.pos, self.valid = (tok.reshape(T)[lay["at"]],
                                              lay["pos"], lay["live"])
            first_row = (jnp.cumsum(lens) - lens if Tc < T
                         else jnp.arange(B, dtype=jnp.int32) * C)
            n_units = min(B * ((C - 1) // bs + 2), Tc // bs + 2 * B)
            self._units = units = _write_units(jnp, pos0, lens, first_row,
                                               bs, n_units, Tc)
            self._unit_pages = [jnp.where(units["used"], t[
                units["row"], jnp.clip(units["slot"], 0, Mb - 1)], 0)
                for t in tables]
            use_attn = choose("gqa_chunk", head_dim=Dh, block_size=bs,
                              window=Cq)
            use_one = choose("gqa_decode", head_dim=Dh, block_size=bs)
            # a tile of ONE token (every decode row of a mixed step, and
            # a chunk's tail of one) is a decode query: the decode
            # kernel takes it, grouped by cache head, and the chunk
            # kernel skips it
            one_token = lay["tile_len"] == 1
            chunk_len = jnp.where(one_token, 0, lay["tile_len"])

            def new_rows(a):                    # [Tc, W] -> [U, bs, W]
                return a[units["src"]]

            def attend(q, k, j, kind):
                fn = (pk.gqa_paged_chunk_attention if use_attn
                      else pk.gqa_paged_attention_reference)
                one = (pk.gqa_paged_decode_attention if use_one
                       else pk.gqa_paged_decode_attention_reference)
                ly = layouts[k]
                tiles = q[ly["tile_rows"]]               # [n, Cq, H, Dh]
                on = (pools[2 * k], pools[2 * k + 1])
                ctx = fn(*on, tiles, ly["tile_tables"], ly["tile_pos"],
                         chunk_len, layer=j, window=kind.window)
                first = one(*on, tiles[:, 0], ly["tile_tables"],
                            ly["tile_pos"], layer=j, window=kind.window,
                            active=one_token)
                ctx = ctx.at[:, 0].set(jnp.where(
                    one_token[:, None, None], first, ctx[:, 0]))
                return ctx.reshape(n_tiles * Cq, H, Dh)[ly["back"]]

            self.last = lay["last"]
        self._new_rows, self._attend = new_rows, attend

    def write(self, layer, k, v):
        """Layer ``layer``'s new ``k`` and ``v`` ``[Tc, Hkv * Dh]`` into
        its kind's pages (the pools go to the kernels whole, never
        ``pool[j]``)."""
        pools, (k_i, j_i) = self.pools, self.where[layer]
        dt = pools[2 * k_i].dtype
        pools[2 * k_i], pools[2 * k_i + 1] = self._write(
            pools[2 * k_i], pools[2 * k_i + 1],
            self._new_rows(k.astype(dt)), self._new_rows(v.astype(dt)),
            self._unit_pages[k_i], self._units["lo"], self._units["hi"],
            layer=j_i)

    def attend(self, layer, q):
        """``q [Tc, H, Dh]`` against layer ``layer``'s pages ->
        ``[Tc, H, Dh]`` float32."""
        k_i, j_i = self.where[layer]
        return self._attend(q, k_i, j_i, self.kinds[k_i])


def _forward(model, weights, tok, pos0, lengths, tables, active, pools,
             max_tokens):
    """``tok`` [B, C] through every layer: each layer's new K and V are
    written into its kind's pages, then attended (:class:`_PagedWindow`).
    Returns (pools, logits [B, V] at each row's last valid slot,
    counters int32 [len(COUNTERS)])."""
    import jax
    import jax.numpy as jnp

    from ..ops.kernel_registry import choose

    cfg, blk = model.config, model.config.block
    act = jnp.dtype(blk.activation_dtype)
    H, D, Dh, Hkv = cfg.n_heads, cfg.d_model, blk.head_dim, blk.n_kv_heads
    eps = blk.rms_norm_eps
    win = _PagedWindow(model, tok, pos0, lengths, tables, active, pools,
                       max_tokens)
    Tc, tok, pos, valid = win.Tc, win.tok, win.pos, win.valid
    use_gmm = (cfg.n_layers > blk.n_dense_layers
               and choose("gmm", k=D, n=blk.moe_d_ff))

    x = jnp.take(weights["embedding"], tok, axis=0).astype(jnp.float32)
    if blk.mup_enabled:
        x = x * float(D) ** 0.5
    counters = jnp.zeros((len(COUNTERS),), jnp.int32)
    for i in range(cfg.n_layers):
        p = "l%d/" % i
        a = _rms_norm(x, weights[p + "attn_norm"], eps)
        q = _rms_norm(_dot(a, weights[p + "wq"], act).reshape(Tc, H, Dh),
                      weights[p + "q_norm"], eps)
        k = _rms_norm(_dot(a, weights[p + "wk"], act).reshape(Tc, Hkv, Dh),
                      weights[p + "k_norm"], eps)
        v = _dot(a, weights[p + "wv"], act)
        gate = jax.nn.sigmoid(_dot(a, weights[p + "wg"], act))
        if blk.layer_types[i] == SLIDING:
            q = rope_half_split(q, pos[:, None], blk.rope_theta)
            k = rope_half_split(k, pos[:, None], blk.rope_theta)
        with jax.named_scope("kv_write"):
            win.write(i, k.reshape(Tc, Hkv * Dh), v)
        with jax.named_scope("gqa_attention"):
            o = win.attend(i, q).reshape(Tc, H * Dh) * gate
            x = x + _rms_norm(_dot(o, weights[p + "wo"], act),
                              weights[p + "attn_post_norm"], eps)
        f = _rms_norm(x, weights[p + "ffn_norm"], eps)
        if not _is_expert_layer(blk, i):
            with jax.named_scope("ffn"):
                y = _swiglu(f, weights[p + "w_gate"], weights[p + "w_up"],
                            weights[p + "w_down"], act)
        else:
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
        x = x + _rms_norm(y, weights[p + "ffn_post_norm"], eps)

    with jax.named_scope("head"):
        x_last = _rms_norm(x[win.last], weights["final_norm"], eps)
        return (tuple(win.pools), _dot(x_last, weights["lm_head"], act),
                counters)


def _n_arrays(model):
    """K and V a page kind: how many pool arrays a step takes."""
    kinds = model.config.block.page_kinds(model.config)
    return 2 * len(kinds or (None,))


def make_decode_step(model, return_logits=False):
    """The one-token step of the block, the engine's calling convention
    with K and V a page kind (``n`` arrays) and a block table a kind:

        step(weights, *pools, prompt_feed, use_prompt, prev_tokens,
             positions, block_tables[kinds, B, Mb], active)
          -> (*pools', next_tokens, counters, top_logit[, logits])

    ``top_logit`` is each row's chosen token's own logit."""
    import jax
    import jax.numpy as jnp

    cfg, n = model.config, _n_arrays(model)

    def decode_step(weights, *args):
        model.trace_count += 1
        pools = args[:n]
        (prompt_feed, use_prompt, prev_tokens, positions, block_tables,
         active) = args[n:]
        tok = jnp.where(use_prompt, prompt_feed, prev_tokens)
        tok = jnp.clip(tok, 0, cfg.vocab_size - 1)[:, None]
        pools, logits, counters = _forward(
            model, weights, tok, positions, jnp.ones_like(positions),
            block_tables, active, pools, None)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = pools + (nxt, counters, jnp.max(logits, axis=-1))
        return out + (logits,) if return_logits else out

    return jax.jit(decode_step, donate_argnums=tuple(range(1, 1 + n)))


def make_window_step(model, window, return_logits=False, max_tokens=None):
    """The ``[max_batch, window]`` mixed prefill/decode step:

        step(weights, *pools, window_tokens[B, C], use_prompt[B],
             prev_tokens[B], positions[B], lengths[B],
             block_tables[kinds, B, Mb], active[B])
          -> (*pools', next_tokens[B], counters, top_logit[B][, logits])"""
    import jax
    import jax.numpy as jnp

    cfg, n = model.config, _n_arrays(model)

    def chunk_step(weights, *args):
        model.trace_count += 1
        pools = args[:n]
        (window_tokens, use_prompt, prev_tokens, positions, lengths,
         block_tables, active) = args[n:]
        tok0 = jnp.where(use_prompt, window_tokens[:, 0], prev_tokens)
        tok = jnp.concatenate([tok0[:, None], window_tokens[:, 1:]], axis=1)
        tok = jnp.clip(tok, 0, cfg.vocab_size - 1)
        pools, logits, counters = _forward(
            model, weights, tok, positions, lengths, block_tables, active,
            pools, max_tokens)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = pools + (nxt, counters, jnp.max(logits, axis=-1))
        return out + (logits,) if return_logits else out

    return jax.jit(chunk_step, donate_argnums=tuple(range(1, 1 + n)))
