"""The delta-rule linear-attention / latent-attention / routed-expert
decoder block of the serving runtime (the ``bailing_hybrid`` family of
layer equations; docs/SERVING.md, "A fifth block").

``GenerationConfig(block=LingBlock(...))`` selects it; the engine,
scheduler, pool accounting, in-flight window and step log are the ones
every model uses. What it states that the other four blocks do not:

  * TWO KINDS OF LAYER in one model (``layer_types``, a kind a layer):
    ``"kda"`` layers keep NO page. Their attention is a SCAN: a head
    carries a ``[dk, dv]`` float32 matrix over the whole sequence,

        S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
        o_t = S_t^T q_t

    (the delta rule with a decay a channel, ``a_t = exp(g_t)``, ``g_t =
    kda_lower_bound * sigmoid(exp(A_h) * (x W_a + b_a))`` in
    ``(kda_lower_bound, 0)``), and ``q``, ``k``, ``v`` pass a causal
    convolution of ``conv_kernel`` taps a channel and a SiLU first.
    ``"mla"`` layers are the latent block's: one cached row a token,
    ``[c | k_pe]``, absorbed attention through ``latent_moe``'s
    functions and kernels (``latent_write``, ``latent_paged_attention``),
    with an RMSNorm on each query head and on the shared rotary key;
  * a ROW STATE OF TWO NAMED PARTS beside the latent pages
    (``row_state``; kv_cache.RowState): ``scan`` ``[kda layers, H, dk,
    dv]`` in ``state_dtype`` (float32: 2 MB a row a layer at the
    published widths, more than the row's pages) and ``conv``, the
    convolutions' last inputs ``[kda layers, conv_kernel - 1, 3 * H *
    dk]`` a row as ONE flat line (the device tiles an array's last two
    axes: with the batch rows and the line they are whole tiles, with
    ``conv_kernel - 1`` = 3 second to last each would be padded to 16).
    The pool holds the MLA layers' pages only
    (``page_kinds``: one kind, named ``global``, over those layers);
  * CHUNKED PREFILL OF A SCAN: a prompt's chunks carry the state from
    chunk to chunk through the row state. A mixed step holds rows that
    continue a scan, rows that start one (their first token at position
    0: the stored state is ignored, so a slot's stale state needs no
    reset) and one-token rows; the chunk's tokens go through
    ``kda_chunk`` in tiles of :data:`KDA_TILE`, the one-token rows of
    either step through ``kda_decode`` (ops/pallas_kernels.py);
  * both attentions' outputs pass a sigmoid gate a HEAD (``x W_g``, one
    value a head) before ``W_o``; the scan's also an RMSNorm a head;
  * the router chooses inside each token's best ``topk_group`` of
    ``n_group`` groups of experts (``latent_moe.route``); the experts
    are the latent block's (``expert_layer``, ``gmm``), with a held
    share and a shared expert.

Weights bfloat16 (gains, the convolutions' taps, the decay's bias and
``A`` and the router float32); matmul operands rounded to
``activation_dtype`` and accumulated in float32; the residual stream,
the scan state, the decays, norms, rotary, router and softmax float32
(latent_moe's precision plan). The convolutions' inputs are rounded to
``activation_dtype`` where they are made, so that a token reads the
same three predecessors from the row state as from its own chunk.

Not built, and refused with one error each: speculative, tree and draft
windows (``GenerationModel._no_such_step``), the prefix cache (the
engine: no scan state exists at an adopted page boundary),
``quantized()``.
"""

from .kv_cache import PageKind
from .latent_moe import (COUNTERS, LatentMoEBlock, _dot, _normal,
                         _rms_norm, _swiglu, absorbed_queries,
                         context_to_heads, expert_layer, latent_rows,
                         rope_interleaved, route)

__all__ = ["LingBlock", "leaf_shapes", "random_weights",
           "make_decode_step", "make_window_step", "KDA", "MLA",
           "MLA_TILE"]

KDA, MLA = "kda", "mla"
# query slots a tile of the MLA layers' chunk attention holds (a tile is
# one row of ``latent_paged_attention``: it walks its row's pages once)
MLA_TILE = 16
SCAN_COUNTERS = ("state_rows", "scan_tokens", "scan_fresh_rows")


class LingBlock(LatentMoEBlock):
    """The block's description, carried by ``GenerationConfig.block``
    (``d_model``, ``n_heads``, ``n_layers``, ``vocab_size`` and the
    dense width ``d_ff`` stay on the configuration). The latent block's
    fields describe the MLA layers and the expert layers; ``head_dim``
    is the scan's ``dk = dv``."""

    kind = "ling"
    # the expert layers' four; then the rows whose scan state the step
    # read, the tokens through the chunked scan, the rows that started one
    step_counters = COUNTERS + SCAN_COUNTERS
    returns_top_logit = True
    FIELDS = LatentMoEBlock.FIELDS + (
        "head_dim", "layer_types", "conv_kernel", "kda_lower_bound",
        "n_group", "topk_group", "state_dtype",
        "scan_restarts_each_chunk")

    def __init__(self, head_dim, layer_types, conv_kernel=4,
                 kda_lower_bound=-5.0, n_group=1, topk_group=1,
                 state_dtype="float32", scan_restarts_each_chunk=False,
                 **latent):
        super().__init__(**latent)
        self.head_dim = int(head_dim)
        self.layer_types = tuple(str(t) for t in layer_types)
        self.conv_kernel = int(conv_kernel)
        self.kda_lower_bound = float(kda_lower_bound)
        self.n_group, self.topk_group = int(n_group), int(topk_group)
        self.state_dtype = str(state_dtype)
        # the benchmark's control: every step reads every row's scan
        # state as zero, as if each chunk began a sequence
        self.scan_restarts_each_chunk = bool(scan_restarts_each_chunk)
        if set(self.layer_types) - {KDA, MLA}:
            raise ValueError("layer_types %r: each is %r or %r"
                             % (self.layer_types, KDA, MLA))
        if MLA not in self.layer_types:
            raise NotImplementedError(
                "no %r layer: the pool would hold no page" % MLA)
        if self.conv_kernel < 2:
            raise ValueError("conv_kernel must be at least 2")
        if self.kda_lower_bound >= 0:
            raise ValueError("kda_lower_bound must be negative (it "
                             "bounds the log of the decay)")
        if self.n_routed_experts % self.n_group \
                or not 1 <= self.topk_group <= self.n_group:
            raise ValueError("n_group must divide n_routed_experts and "
                             "topk_group lie in 1..n_group")

    def layers_of(self, kind):
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == kind)

    def page_kinds(self, config):
        """One kind over the MLA layers alone; named, so that the step
        log carries ``global_pages_walked`` and its kin."""
        self._check_depth(config)
        return (PageKind("global", self.layers_of(MLA)),)

    def row_state(self, config):
        """What a batch row carries from step to step beside its pages,
        ``kv_cache.RowState``'s arguments: the scan's matrices and the
        convolutions' last inputs, a KDA layer each."""
        self._check_depth(config)
        n, H, d = len(self.layers_of(KDA)), config.n_heads, self.head_dim
        return ((("scan", (n, H, d, d), self.state_dtype),
                 ("conv", (n * (self.conv_kernel - 1) * 3 * H * d,),
                  self.activation_dtype)),)

    def _check_depth(self, config):
        if len(self.layer_types) != config.n_layers:
            raise ValueError("layer_types names %d layers, the "
                             "configuration has %d"
                             % (len(self.layer_types), config.n_layers))

    def random_weights(self, config, seed=0, scale=0.1):
        return random_weights(config, seed, scale)


def leaf_shapes(config):
    """{weight name: (shape, dtype name)}: the serving layout."""
    blk = config.block
    D, V, H, F = (config.d_model, config.vocab_size, config.n_heads,
                  config.d_ff)
    d = blk.head_dim
    dn, dr, dv, r = (blk.qk_nope_head_dim, blk.qk_rope_head_dim,
                     blk.v_head_dim, blk.kv_lora_rank)
    E, Eh, Fe = (blk.n_routed_experts, len(blk.experts_held), blk.moe_d_ff)
    Fs = blk.n_shared_experts * Fe
    w, f32 = blk.weight_dtype, "float32"
    out = {"embedding": ((V, D), w), "lm_head": ((D, V), w),
           "final_norm": ((D,), f32)}
    for i, kind in enumerate(blk.layer_types):
        p = "l%d/" % i
        out.update({p + "attn_norm": ((D,), f32),
                    p + "w_ogate": ((D, H), w),
                    p + "ffn_norm": ((D,), f32)})
        if kind == KDA:
            out.update({
                p + "wq": ((D, H * d), w), p + "wk": ((D, H * d), w),
                p + "wv": ((D, H * d), w),
                p + "conv_w": ((blk.conv_kernel, 3 * H * d), f32),
                p + "w_alpha": ((D, H * d), w),
                p + "alpha_bias": ((H * d,), f32),
                p + "a_log": ((H,), f32),
                p + "w_beta": ((D, H), w),
                p + "o_norm": ((d,), f32),
                p + "wo": ((H * d, D), w)})
        else:
            out.update({
                p + "wq": ((D, H * (dn + dr)), w),
                p + "q_norm": ((dn + dr,), f32),
                p + "wkv_a": ((D, r + dr), w),
                p + "kv_norm": ((r,), f32),
                p + "k_norm": ((dr,), f32),
                p + "w_uk": ((H, dn, r), w),
                p + "w_uv": ((H, r, dv), w),
                p + "wo": ((H * dv, D), w)})
        if i >= blk.first_k_dense:
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


def random_weights(config, seed=0, scale=0.1):
    """Deterministic random weights in the serving layout (tests, the
    chip smoke): N(0, scale) matrices, the convolutions' taps N(0, 1 /
    taps), gains 1, the router's and the decay's biases and ``A`` 0.
    Made on the default device, a leaf at a time."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(seed)
    out = {}
    for n, (name, (shape, dtype)) in enumerate(leaf_shapes(config).items()):
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, dtype)
        elif name.endswith(("bias", "a_log")):
            out[name] = jnp.zeros(shape, dtype)
        else:
            std = shape[0] ** -0.5 if name.endswith("conv_w") else scale
            out[name] = _normal(jax.random.fold_in(key, n), shape, dtype,
                                std)
    return out


def _l2_normalised(x, scale):
    import jax
    import jax.numpy as jnp

    return x * (scale * jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + 1e-12))


class _Window:
    """The index arithmetic of one step over a ``[B, C]`` window of
    tokens, shared by every layer. C == 1 is the decode step: token row
    ``b`` is batch row ``b``. Otherwise the window's real tokens are
    COMPACTED to ``max_tokens`` token rows (``model._chunk_layout``), a
    row's tokens together and in order, and cut three ways: into query
    tiles of :data:`MLA_TILE` slots for the MLA layers' attention, into
    page units for their cache rows (``afmoe._write_units``), and into
    tiles of ``KDA_TILE`` tokens for the chunked scan.

    ``tok``, ``pos``, ``valid`` ``[Tc]``: the token rows; ``row``,
    ``col`` ``[Tc]``: the batch row and the window slot of each;
    ``lens``, ``first``, ``last`` ``[B]``: the tokens a batch row holds,
    its first and its last token row."""

    def __init__(self, model, tok, pos0, lengths, tables, active, latent,
                 max_tokens):
        import jax.numpy as jnp

        from ..ops import pallas_kernels as pk
        from ..ops.kernel_registry import choose
        from .afmoe import _write_units
        from .model import _chunk_layout, chunk_tile_count

        cfg, blk = model.config, model.config.block
        B, C = tok.shape
        bs, Mb = latent.shape[2], tables.shape[1]
        i32 = jnp.int32
        self.B, self.C = B, C
        self.pos0 = pos0 = jnp.maximum(pos0, 0).astype(i32)
        self.lens = lens = jnp.where(
            active, jnp.clip(lengths, 0, C), 0).astype(i32)
        self.tables = tables
        # the rows whose scan starts from zero: a sequence's first
        # token, or every row under the benchmark's control
        self.fresh = fresh = (pos0 == 0) | blk.scan_restarts_each_chunk
        kda = dict(head_dim=blk.head_dim, n_heads=cfg.n_heads)
        self.decode_scan = (pk.kda_decode if choose("kda_decode", **kda)
                            else pk.kda_decode_reference)
        self._write = (pk.latent_write if choose(
            "latent_write", width=blk.cache_row, block_size=bs, window=C)
            else pk.latent_write_reference)
        if C == 1:
            self.Tc = B
            self.tok, self.pos, self.valid = tok.reshape(B), pos0, lens > 0
            self.row = jnp.arange(B, dtype=i32)
            self.first = self.last = self.row
            self.one_token = self.valid
            self._attend = (pk.latent_paged_attention if choose(
                "latent_decode", width=blk.cache_row,
                v_width=blk.kv_lora_rank, block_size=bs, window=1)
                else pk.latent_paged_attention_reference)
            return
        T = B * C
        self.Tc = Tc = T if max_tokens is None else min(int(max_tokens), T)
        Cq = min(MLA_TILE, C)
        n_tiles = chunk_tile_count(B, C, None if Tc == T else Tc, tile=Cq)
        self.lay = lay = _chunk_layout(jnp, pos0, lens, active, tables, C,
                                       Tc, Cq, n_tiles, bs)
        self.Cq, self.n_tiles = Cq, n_tiles
        self.tok, self.pos, self.valid = (tok.reshape(T)[lay["at"]],
                                          lay["pos"], lay["live"])
        self.row = lay["at"] // C
        self.first = (jnp.cumsum(lens) - lens if Tc < T
                      else jnp.arange(B, dtype=i32) * C)
        self.last = lay["last"]
        self.one_token = lens == 1
        self._attend = (pk.latent_paged_attention if choose(
            "latent_window", width=blk.cache_row, v_width=blk.kv_lora_rank,
            block_size=bs, window=Cq)
            else pk.latent_paged_attention_reference)
        # the cache rows, a page a unit: unit u is a window of up to
        # `bs` slots that starts at row `lo` of its page
        n_units = min(B * ((C - 1) // bs + 2), Tc // bs + 2 * B)
        units = _write_units(jnp, pos0, lens, self.first, bs, n_units, Tc)
        self._unit_pages = jnp.where(units["used"], tables[
            units["row"], jnp.clip(units["slot"], 0, Mb - 1)], 0)[:, None]
        self._unit_lo = units["lo"]
        self._unit_len = units["hi"] - units["lo"]
        self._unit_src = jnp.take_along_axis(
            units["src"], jnp.clip(units["lo"][:, None] + jnp.arange(
                bs, dtype=i32)[None, :], 0, bs - 1), axis=1)
        # the chunked scan's tiles: the rows that hold more than one
        # token, `KDA_TILE` tokens a tile
        Ck = pk.KDA_TILE
        self.chunk_scan = (pk.kda_chunk if choose("kda_chunk", **kda)
                           else pk.kda_chunk_reference)
        tiles_of = jnp.where(lens > 1, (lens + Ck - 1) // Ck, 0)
        tile_end = jnp.cumsum(tiles_of)
        n = jnp.arange(chunk_tile_count(B, C, None if Tc == T else Tc,
                                        tile=Ck), dtype=i32)
        t_row = jnp.minimum(jnp.searchsorted(tile_end, n, side="right"),
                            B - 1).astype(i32)
        t_off = (n - (tile_end - tiles_of)[t_row]) * Ck
        used = n < tile_end[-1]
        self.scan_tiles = (
            self.first[t_row] + t_off,
            jnp.where(used, jnp.clip(lens[t_row] - t_off, 0, Ck), 0),
            t_row,
            jnp.where(t_off == 0, jnp.where(fresh[t_row], 2, 1), 0),
            (t_off + Ck >= lens[t_row]).astype(i32))

    def write(self, latent, rows, layer):
        """The token rows' cache rows ``[Tc, cache_row]`` into the pool's
        ``layer`` (the pool goes to the kernel whole)."""
        rows = rows.astype(latent.dtype)
        if self.C == 1:
            return self._write(latent, rows[:, None, :], self.tables,
                               self.pos0, self.lens, layer=layer)
        return self._write(latent, rows[self._unit_src], self._unit_pages,
                           self._unit_lo, self._unit_len, layer=layer)

    def attend(self, latent, q_abs, layer, v_width):
        """``q_abs [Tc, H, cache_row]`` against the pool's ``layer`` ->
        the latent-space context ``[Tc, H, v_width]`` float32."""
        import jax.numpy as jnp

        q_abs = q_abs.astype(latent.dtype)
        if self.C == 1:
            return self._attend(latent, q_abs[:, None], self.tables,
                                self.pos0, self.lens, layer=layer,
                                v_width=v_width)[:, 0]
        lay = self.lay
        # a tile that holds no token walks one page, not its row's
        used = lay["tile_len"] > 0
        ctx = self._attend(latent, q_abs[lay["tile_rows"]],
                           lay["tile_tables"],
                           jnp.where(used, lay["tile_pos"], 0),
                           lay["tile_len"], layer=layer, v_width=v_width)
        return ctx.reshape((self.n_tiles * self.Cq,)
                           + ctx.shape[2:])[lay["back"]]


def _kda_attention(blk, win, weights, p, a, li, scan, conv, act):
    """The scan sublayer of KDA layer ``li`` (its index among the KDA
    layers) over the normalised stream ``a [Tc, D]``: ``(o [Tc, H * d],
    scan', conv')``."""
    import jax
    import jax.numpy as jnp

    Tc, H, d = a.shape[0], weights[p + "a_log"].shape[0], blk.head_dim
    taps = blk.conv_kernel
    f32 = jnp.float32
    with jax.named_scope("kda_prepare"):
        z = jnp.concatenate(
            [_dot(a, weights[p + n], act) for n in ("wq", "wk", "wv")],
            axis=-1).astype(act).astype(f32)                # [Tc, 3 H d]
        # the row state's line: slot m of this layer holds the input
        # `taps - 1 - m` tokens before the row's next one
        W = z.shape[1]
        slots = [(li * (taps - 1) + m) * W for m in range(taps - 1)]
        carried = [conv[:, o:o + W].astype(f32) for o in slots]   # [B, W]
        w = weights[p + "conv_w"]

        def taps_over(at, pos):
            """The convolution of tokens at ``pos``, ``at(j)`` their
            input ``j`` tokens back (zero before position 0)."""
            return sum(w[taps - 1 - j] * jnp.where(
                (pos >= j)[..., None], at(j), 0.0) for j in range(taps))

        if win.C == 1:
            y = taps_over(lambda j: z if j == 0 else carried[taps - 1 - j],
                          win.pos)
        else:
            # a token's predecessors are the token rows before it (its
            # own chunk's, the rows of one sequence being adjacent) ...
            before = jnp.pad(z, ((taps - 1, 0), (0, 0)))
            y = taps_over(lambda j: before[taps - 1 - j:taps - 1 - j + Tc],
                          win.pos)
            # ... but for a chunk's first `taps - 1` tokens, which reach
            # into the row state: their few rows are convolved again over
            # the carried inputs followed by the chunk's first tokens
            # (gathering the carried inputs for every token row, which
            # XLA compiles as one-hot matmuls, and choosing among them
            # was 13 ms of a 97 ms mixed step)
            n = taps - 1
            c = jnp.arange(n, dtype=jnp.int32)
            head = win.first[:, None] + c[None, :]               # [B, n]
            line = jnp.concatenate(
                [jnp.stack(carried, axis=1),
                 z[jnp.clip(head, 0, Tc - 1)]], axis=1)          # [B, 2n, W]
            first = jnp.stack([taps_over(
                lambda j: line[:, n + i - j], win.pos0 + i)
                for i in range(n)], axis=1)                      # [B, n, W]
            y = y.at[jnp.where(c[None, :] < win.lens[:, None], head,
                               Tc).reshape(-1)].set(
                first.reshape(-1, W), mode="drop")
        y = jax.nn.silu(y).reshape(Tc, 3, H, d)
        q = _l2_normalised(y[:, 0], float(d) ** -0.5)
        k = _l2_normalised(y[:, 1], 1.0)
        v = y[:, 2]
        g = blk.kda_lower_bound * jax.nn.sigmoid(
            jnp.repeat(jnp.exp(weights[p + "a_log"]), d)
            * (_dot(a, weights[p + "w_alpha"], act)
               + weights[p + "alpha_bias"])).reshape(Tc, H, d)
        beta = jax.nn.sigmoid(_dot(a, weights[p + "w_beta"], act))
        # the convolutions' inputs the row's next step reads: the last
        # `taps - 1` of what it held and what this step added
        for m in range(taps - 1):
            c = win.lens - (taps - 1) + m
            new = z[jnp.clip(win.first + c, 0, Tc - 1)]
            for old in range(m + 1, taps - 1):
                new = jnp.where((win.lens + m == old)[:, None],
                                carried[old], new)
            new = jnp.where((win.lens > 0)[:, None], new, carried[m])
            conv = jax.lax.dynamic_update_slice(
                conv, new.astype(conv.dtype), (0, slots[m]))
    with jax.named_scope("kda"):
        fresh = win.fresh
        if win.C == 1:
            alpha = jnp.where(fresh[:, None, None], 0.0, jnp.exp(g))
            scan, o = win.decode_scan(scan, q, k, v, alpha, beta,
                                      win.valid, layer=li)
        else:
            scan, o = win.chunk_scan(scan, q, k, v, g, beta,
                                     *win.scan_tiles, layer=li)
            # the rows of one token (every decode row of a mixed step)
            # take the one-token step
            at = win.last
            alpha = jnp.where(fresh[:, None, None], 0.0, jnp.exp(g[at]))
            scan, one = win.decode_scan(scan, q[at], k[at], v[at], alpha,
                                        beta[at], win.one_token, layer=li)
            o = jnp.where(win.one_token[win.row][:, None, None],
                          one[win.row], o)
        o = _rms_norm(o, weights[p + "o_norm"], blk.rms_norm_eps)
    return o.reshape(Tc, H * d), scan, conv


def _mla_attention(blk, win, weights, p, a, j, latent, act):
    """The latent-attention sublayer over the normalised stream ``a``,
    the pool's layer ``j``: ``(o [Tc, H * dv], latent')``."""
    import jax
    import jax.numpy as jnp

    Tc, eps = a.shape[0], blk.rms_norm_eps
    H = weights[p + "w_uk"].shape[0]
    dn, r = blk.qk_nope_head_dim, blk.kv_lora_rank
    q = _rms_norm(_dot(a, weights[p + "wq"], act).reshape(Tc, H, -1),
                  weights[p + "q_norm"], eps)
    kva = _dot(a, weights[p + "wkv_a"], act)                # [Tc, r + dr]
    c_new = _rms_norm(kva[:, :r], weights[p + "kv_norm"], eps)
    kpe_new = rope_interleaved(
        _rms_norm(kva[:, r:], weights[p + "k_norm"], eps), win.pos,
        blk.rope_theta)
    q_pe = rope_interleaved(q[..., dn:], win.pos[:, None], blk.rope_theta)
    with jax.named_scope("latent_write"):
        latent = win.write(latent, latent_rows(blk, c_new, kpe_new), j)
    with jax.named_scope("latent_attention"):
        q_abs = absorbed_queries(blk, q[..., :dn], q_pe,
                                 weights[p + "w_uk"], act)
        ctx = win.attend(latent, q_abs, j, r)
        o = context_to_heads(ctx, weights[p + "w_uv"], act)
    return o.reshape(Tc, -1), latent


def _forward(model, weights, tok, pos0, lengths, tables, active, latent,
             scan, conv, max_tokens):
    """``tok`` [B, C] through every layer. Returns (latent, scan, conv,
    logits [B, V] at each row's last valid slot, counters int32
    [len(step_counters)])."""
    import jax
    import jax.numpy as jnp

    from ..ops.kernel_registry import choose

    cfg, blk = model.config, model.config.block
    act = jnp.dtype(blk.activation_dtype)
    H, D, eps = cfg.n_heads, cfg.d_model, blk.rms_norm_eps
    win = _Window(model, tok, pos0, lengths, tables, active, latent,
                  max_tokens)
    Tc, valid = win.Tc, win.valid
    use_gmm = (cfg.n_layers > blk.first_k_dense
               and choose("gmm", k=D, n=blk.moe_d_ff))
    kda_layers, mla_layers = blk.layers_of(KDA), blk.layers_of(MLA)

    x = jnp.take(weights["embedding"], win.tok, axis=0).astype(jnp.float32)
    expert_counters = jnp.zeros((len(COUNTERS),), jnp.int32)
    for i, kind in enumerate(blk.layer_types):
        p = "l%d/" % i
        a = _rms_norm(x, weights[p + "attn_norm"], eps)
        gate = jax.nn.sigmoid(_dot(a, weights[p + "w_ogate"], act))
        if kind == KDA:
            o, scan, conv = _kda_attention(
                blk, win, weights, p, a, kda_layers.index(i), scan, conv,
                act)
        else:
            o, latent = _mla_attention(
                blk, win, weights, p, a, mla_layers.index(i), latent, act)
        o = (o.reshape(Tc, H, -1) * gate[:, :, None]).reshape(Tc, -1)
        x = x + _dot(o, weights[p + "wo"], act)
        f = _rms_norm(x, weights[p + "ffn_norm"], eps)
        if i < blk.first_k_dense:
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
            expert_counters = expert_counters + c
        if p + "ws_gate" in weights:
            with jax.named_scope("shared_expert"):
                y = y + _swiglu(f, weights[p + "ws_gate"],
                                weights[p + "ws_up"],
                                weights[p + "ws_down"], act)
        x = x + y

    held = win.lens > 0
    fresh, reads = held & win.fresh, held & ~win.fresh
    counters = jnp.concatenate([expert_counters, jnp.stack([
        jnp.sum(reads), jnp.sum(jnp.where(win.lens > 1, win.lens, 0)),
        jnp.sum(fresh)]).astype(jnp.int32)])
    with jax.named_scope("head"):
        x_last = _rms_norm(x[win.last], weights["final_norm"], eps)
        logits = _dot(x_last, weights["lm_head"], act)
    return latent, scan, conv, logits, counters


def _steps(model, window_step, return_logits, max_tokens):
    """Both compiled steps: the latent pool and the row state's two
    parts donated, then the engine's arguments (the chunk step's with
    its window and lengths)."""
    import jax
    import jax.numpy as jnp

    cfg = model.config

    def step(weights, latent, scan, conv, feed, use_prompt, prev_tokens,
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
        latent, scan, conv, logits, counters = _forward(
            model, weights, tok, positions, lengths, block_tables, active,
            latent, scan, conv, max_tokens if window_step else None)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = (latent, scan, conv, nxt, counters, jnp.max(logits, axis=-1))
        return out + (logits,) if return_logits else out

    step.__name__ = "chunk_step" if window_step else "decode_step"
    return jax.jit(step, donate_argnums=(1, 2, 3))


def make_decode_step(model, return_logits=False):
    """The one-token step of the block, the engine's calling convention
    with the row state's parts after the pool:

        step(weights, latent, scan, conv, prompt_feed, use_prompt,
             prev_tokens, positions, block_tables[B, Mb], active)
          -> (latent', scan', conv', next_tokens, counters,
              top_logit[, logits])"""
    return _steps(model, False, return_logits, None)


def make_window_step(model, window, return_logits=False, max_tokens=None):
    """The ``[max_batch, window]`` mixed prefill/decode step:

        step(weights, latent, scan, conv, window_tokens[B, C],
             use_prompt[B], prev_tokens[B], positions[B], lengths[B],
             block_tables[B, Mb], active[B])
          -> (latent', scan', conv', next_tokens[B], counters,
              top_logit[B][, logits])"""
    return _steps(model, True, return_logits, max_tokens)
