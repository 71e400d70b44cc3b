"""Plain reference for the ZAYA1 decoder (Zyphra/ZAYA1-8B, ``model_type``
``zaya``).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the full causal forward of
one sequence, the convolutions and the value shift as shifts of the
whole sequence by one position, every expert computed for every token
and summed under a mask of the router's weights; no cache, no carry, no
page, no kernel, no sorting. It imports nothing of ``paddle_tpu`` and
takes nothing the program has made: the weights come from ``init_layer``
/ ``init_top``, which is also what the benchmark hands to the program.

The block, from the published ``config.json`` and, where the keys alone
do not fix it, the two public descriptions of the family (Compressed
Convolutional Attention, arXiv:2510.04476; the ZAYA1 technical report,
arXiv:2511.17127); each such choice is under ``assumed`` in the
configuration file. ``h`` is the residual stream ``[T, hidden_size]``:

* ``h0 = E[token]`` (no embedding scale);
* CCA sublayer on ``u = RMSNorm(h)``: ``q~ = u W_q``
  (``num_attention_heads`` x ``head_dim``), ``k~ = u W_k``
  (``num_key_value_heads`` x ``head_dim``), ``z = [q~ ; k~]``. Two causal
  convolutions over the sequence, zero to the left of position 0: a
  depthwise one of ``cca_time0`` taps, ``c_t = sum_j a_j * z_{t - (n-1)
  + j} + b``, then one grouped by head (a group a query or key head) of
  ``cca_time1`` taps, ``d_t = sum_j c_{t - (n-1) + j} A_j + b'``. The
  q-k mean before the convolutions, ``m^(i) = (q~^(i) + k~^(i div G)) /
  2``: ``q^(i) = d^(q,i) + m^(i)``, ``k^(j) = d^(k,j) + mean_{i div G =
  j} m^(i)``. The value with its shift: ``v_t = [u_t W_v1 ; u_{t-1}
  W_v2]`` (cache head 0 this token's, cache head 1 the previous one's;
  ``u_{-1} = 0``). ``q`` and ``k`` L2-normalised a head and scaled by
  ``sqrt(head_dim)``, ``k`` also by a learned temperature a cache head;
  rotary on the first ``partial_rotary_factor`` of each head's lanes,
  half-split pairs; causal softmax of ``q k^T / sqrt(head_dim)``, query
  head ``i`` on cache head ``i div G``; ``o = concat(heads) W_o``;
* the residual with a learned scale and bias on both addends, after
  either sublayer: ``h = (h + b_r) * s_r + (o + b_o) * s_o``;
* routed sublayer on ``u = RMSNorm(h)``: ``r_l = u W_d`` (to
  ``router_hidden_size``), mixed with the layer before,
  ``r_l += g_l * r_{l-1}`` for ``l > 0`` (``r_{l-1}`` that layer's mixed
  state); ``y = GELU(GELU(RMSNorm(r_l) W_1 + b_1) W_2 + b_2)``; ``p =
  softmax(y W_3)`` over the ``num_experts``; the expert ``e`` with the
  largest ``p + b`` (``b`` picks, it does not weigh); the sublayer's
  result ``p_e * SwiGLU_e(u)`` at ``moe_intermediate_size``. One expert
  a token, no shared expert, no renormalisation;
* final RMSNorm; the head is the embedding (``tie_word_embeddings``).

Beside the logits it reports how near a tie each token's router choice
was (``router_margin``: the gap between the two largest ``p + b``), as
``reference/trinity.py`` does.

The weights are kept in bfloat16 (gains, biases, the depthwise taps and
the whole router float32) and widened where used. Attention runs in
query blocks, the experts in token blocks and in groups, and the head
in blocks of rows, so that a sequence of 12,288 positions fits beside
9.4 GB of weights.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
QUERY_BLOCK = 256
TOKEN_BLOCK = 2048
ROW_BLOCK = 512
EXPERT_GROUP = 4
BALANCE_PROBE = 1024
TOP_LEAVES = ("embed", "norm_f")
RESIDUAL_LEAVES = tuple(s + "_" + r for s in ("attn", "ffn") for r in
                        ("res_scale", "res_bias", "out_scale", "out_bias"))
LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv1", "wv2", "wo", "conv0_w",
                "conv0_b", "conv1_w", "conv1_b", "k_temp", "ffn_norm",
                "router_down", "router_mix", "router_norm", "router_w1",
                "router_b1", "router_w2", "router_b2", "router_w3",
                "router_bias", "e_gate", "e_up", "e_down") + RESIDUAL_LEAVES
# matrices kept in the weights' type; every other leaf is float32
MATRIX_LEAVES = ("embed", "wq", "wk", "wv1", "wv2", "wo", "conv1_w",
                 "e_gate", "e_up", "e_down")
# float32 leaves seeded 1 and 0 (the rest of them are drawn)
ONES = ("norm", "scale", "k_temp", "router_mix")
ZEROS = ("bias", "conv0_b", "conv1_b", "router_b1", "router_b2")


def dims(cfg):
    H, Hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    Dh = int(cfg["head_dim"])
    return dict(D=int(cfg["hidden_size"]), H=H, Hkv=Hkv, Dh=Dh, G=H // Hkv,
                Z=(H + Hkv) * Dh, n0=int(cfg["cca_time0"]),
                n1=int(cfg["cca_time1"]), R=int(cfg["router_hidden_size"]),
                E=int(cfg["num_experts"]),
                Fe=int(cfg["moe_intermediate_size"]),
                V=int(cfg["vocab_size"]),
                rot=int(Dh * float(cfg["partial_rotary_factor"])),
                theta=float(cfg["rope_parameters"]["hybrid"]["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]))


def top_shapes(cfg):
    d = dims(cfg)
    return {"embed": (d["V"], d["D"]), "norm_f": (d["D"],)}


def layer_shapes(cfg, i=0):
    d = dims(cfg)
    D, H, Hkv, Dh, Z, R, E, Fe = (d[k] for k in ("D", "H", "Hkv", "Dh", "Z",
                                                 "R", "E", "Fe"))
    s = {"attn_norm": (D,), "wq": (D, H * Dh), "wk": (D, Hkv * Dh),
         "wv1": (D, Dh), "wv2": (D, Dh), "wo": (H * Dh, D),
         "conv0_w": (d["n0"], Z), "conv0_b": (Z,),
         "conv1_w": (d["n1"], H + Hkv, Dh, Dh), "conv1_b": (Z,),
         "k_temp": (Hkv,), "ffn_norm": (D,), "router_down": (D, R),
         "router_mix": (R,), "router_norm": (R,), "router_w1": (R, R),
         "router_b1": (R,), "router_w2": (R, R), "router_b2": (R,),
         "router_w3": (R, E), "router_bias": (E,), "e_gate": (E, D, Fe),
         "e_up": (E, D, Fe), "e_down": (E, Fe, D)}
    s.update({n: (D,) for n in RESIDUAL_LEAVES})
    return s


def n_params(cfg):
    """The embedding counted once: the head is the same matrix."""
    shapes = [top_shapes(cfg)] + [layer_shapes(cfg, i) for i in
                                  range(int(cfg["num_hidden_layers"]))]
    return sum(int(np.prod(s)) for d in shapes for s in d.values())


def seed_words(seed):
    """``--seed`` as two 31-bit words (it may exceed 32 signed bits);
    pass them into a jitted function as an ARGUMENT, so that a new seed
    does not compile anew."""
    seed = int(seed)
    return np.array([seed & 0x7FFFFFFF, seed >> 31], np.uint32)


def leaf_key(words, layer, name):
    key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
    key = jax.random.fold_in(key, layer + 1)
    return jax.random.fold_in(key, (TOP_LEAVES + LAYER_LEAVES).index(name))


# leaves drawn N(0, 1 / fan-in) whatever ``init_std``: (name, fan-in of
# its shape)
FAN_IN = {"conv0_w": lambda s: s[0], "conv1_w": lambda s: s[0] * s[2],
          "router_w1": lambda s: s[0], "router_w2": lambda s: s[0],
          "router_w3": lambda s: s[0]}


def init_leaf(words, layer, name, shape, cfg):
    """Matrices N(0, ``init_std``). The convolutions' taps and the
    router's three layers N(0, 1 / fan-in): the taps so that the
    convolved latent stands beside the q-k mean it is added to, the
    router so that its softmax is peaked as a trained top-1 router's is
    (at 0.02 every ``p`` would be a sixteenth to three decimals, an
    expert's output weighed by a sixteenth and a token's choice resting
    on the fifth decimal). The embedding N(0, ``embed_init_std``) where
    the configuration states one: with a TIED head a token's own row
    wins the argmax (``|E[t]|^2`` against the other rows' products with
    what the layers added) unless the rows are short beside the layers'
    sum, and greedy generation then repeats one token. The routed
    sublayer's output scale ``routed_out_scale`` where the configuration
    states one: a seeded softmax over 16 experts weighs the chosen
    expert by about a ninth, where a trained top-1 router's ``p`` is
    near 1, and the experts' arithmetic would be a twentieth of the
    stream. Other gains 1 and biases 0, or, where the
    configuration states ``init_gain_noise`` (the tests'), that far off
    them, so that no learned vector is left out of a comparison
    unseen."""
    noise = float(cfg.get("init_gain_noise", 0.0))
    draw = jax.random.normal(leaf_key(words, layer, name), shape,
                             jnp.float32)
    if name == "ffn_out_scale":
        return float(cfg.get("routed_out_scale", 1.0)) + noise * draw
    if name == "norm_f" or name.endswith(ONES):
        return 1.0 + noise * draw
    if name.endswith(ZEROS):
        return noise * draw
    std = float(cfg.get("init_std", INIT_STD))
    if name in FAN_IN:
        x = draw / math.sqrt(FAN_IN[name](shape))
    elif name == "embed":
        x = float(cfg.get("embed_init_std", std)) * draw
    else:
        x = std * draw
    return x.astype(cfg.get("weight_dtype", "bfloat16")) \
        if name in MATRIX_LEAVES else x


def init_top(words, cfg):
    return {n: init_leaf(words, -1, n, s, cfg)
            for n, s in top_shapes(cfg).items()}


def init_layer(words, cfg, i):
    w = {n: init_leaf(words, i, n, s, cfg)
         for n, s in layer_shapes(cfg, i).items()}
    w["router_bias"] = w["router_bias"] + balancing_bias(w, cfg)
    return w


def balancing_bias(w, cfg):
    """The bias load balancing would leave a router with: ``1 /
    num_experts`` less the mean of ``p`` over a fixed probe of
    standard-normal router states (the same for every seed and layer),
    so that ``argmax(p + b)`` picks the experts about equally often. A
    seeded router's third layer carries a constant offset an expert
    (``GELU`` has a positive mean), which at ``b = 0`` sends a quarter
    of the tokens to one expert and starves three or four."""
    probe = jax.random.normal(jax.random.PRNGKey(0),
                              (BALANCE_PROBE, w["router_norm"].shape[0]),
                              jnp.float32)
    with jax.default_matmul_precision("highest"):
        p = router_probs(probe, w, cfg)
    return 1.0 / p.shape[1] - jnp.mean(p, axis=0)


def make_params(seed, cfg):
    """Every leaf on the default device, one jitted call a layer (the
    layer a traced argument: they are all of one kind)."""
    words = seed_words(seed)
    layer = jax.jit(lambda w, i: init_layer(w, cfg, i))
    return {"top": jax.jit(lambda w: init_top(w, cfg))(words),
            "layers": [layer(words, np.int32(i))
                       for i in range(int(cfg["num_hidden_layers"]))]}


def f32(w):
    return w.astype(jnp.float32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def shifted(x, n):
    """``x [T, ...]`` moved ``n`` positions later, zeros in front."""
    if n == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:n]), x[:-n]], axis=0)


def rotary(x, rot, theta):
    """x: ``[T, heads, d]``, the position on the first axis; rotates the
    pairs of lanes ``(j, j + rot/2)`` of the first ``rot`` lanes by ``t *
    theta**(-2j/rot)`` and leaves the rest."""
    T = x.shape[0]
    freq = 1.0 / theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    angle = np.arange(T, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[:, None, :]
    lo, hi = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin,
                            x[..., rot:]], -1)


def blocks_of(n, want):
    """The largest block size up to ``want`` that divides ``n``."""
    return max(b for b in range(1, min(want, n) + 1) if n % b == 0)


def l2_normalised(x, scale):
    return x * (scale * jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + 1e-12))


def cca_qkv(x, w, cfg):
    """``(q [T, H, Dh], k [T, Hkv, Dh], v [T, Hkv, Dh])`` of one layer,
    as the cache would hold ``k`` and ``v``; ``x`` the normalised
    stream."""
    d = dims(cfg)
    T, H, Hkv, Dh, G = x.shape[0], d["H"], d["Hkv"], d["Dh"], d["G"]
    q0 = (x @ f32(w["wq"])).reshape(T, H, Dh)
    k0 = (x @ f32(w["wk"])).reshape(T, Hkv, Dh)
    z = jnp.concatenate([q0, k0], axis=1)              # [T, H + Hkv, Dh]
    taps0 = w["conv0_w"].reshape(d["n0"], H + Hkv, Dh)
    c = sum(taps0[j] * shifted(z, d["n0"] - 1 - j)
            for j in range(d["n0"])) + w["conv0_b"].reshape(H + Hkv, Dh)
    conv = sum(jnp.einsum("tgi,gio->tgo", shifted(c, d["n1"] - 1 - j),
                          f32(w["conv1_w"][j])) for j in range(d["n1"])) \
        + w["conv1_b"].reshape(H + Hkv, Dh)
    mean = 0.5 * (q0 + jnp.repeat(k0, G, axis=1))      # [T, H, Dh]
    q = conv[:, :H] + mean
    k = conv[:, H:] + mean.reshape(T, Hkv, G, Dh).mean(axis=2)
    v = jnp.stack([x @ f32(w["wv1"]), shifted(x @ f32(w["wv2"]), 1)],
                  axis=1)                              # [T, 2, Dh]
    if Hkv != 2:
        raise ValueError("the value shift fills two cache heads")
    q = l2_normalised(q, math.sqrt(Dh))
    k = l2_normalised(k, math.sqrt(Dh)) * w["k_temp"][None, :, None]
    return (rotary(q, d["rot"], d["theta"]),
            rotary(k, d["rot"], d["theta"]), v)


def attention(x, w, cfg):
    """The CCA sublayer's ``o`` of one layer, ``[T, D]``."""
    d = dims(cfg)
    T, H, Hkv, Dh, G = x.shape[0], d["H"], d["Hkv"], d["Dh"], d["G"]
    q, k, v = cca_qkv(x, w, cfg)
    Qb = blocks_of(T, QUERY_BLOCK)
    s_pos = jnp.arange(T)[None, :]

    def block(args):
        qb, t0 = args                                  # [Qb, Hkv, G, Dh]
        seen = s_pos <= (t0 + jnp.arange(Qb))[:, None]
        s = jnp.einsum("tkgd,skd->kgts", qb, k) / math.sqrt(Dh)
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
        return jnp.einsum("kgts,skd->tkgd", p, v)

    o = jax.lax.map(block, (q.reshape(T // Qb, Qb, Hkv, G, Dh),
                            jnp.arange(0, T, Qb)))
    return o.reshape(T, H * Dh) @ f32(w["wo"])


def residual(h, y, w, sub):
    """``(h + b_r) * s_r + (y + b_o) * s_o`` of sublayer ``sub``."""
    return ((h + w[sub + "_res_bias"]) * w[sub + "_res_scale"]
            + (y + w[sub + "_out_bias"]) * w[sub + "_out_scale"])


def router_state(x, w, prev):
    """``r_l``: this layer's projection mixed with the layer before's
    state (``prev`` None: the first layer held)."""
    r = x @ w["router_down"]
    return r if prev is None else r + w["router_mix"] * prev


def router_probs(r, w, cfg):
    """``p [T, E]`` from the mixed state."""
    y = rms_norm(r, w["router_norm"], dims(cfg)["eps"])
    y = jax.nn.gelu(y @ w["router_w1"] + w["router_b1"], approximate=False)
    y = jax.nn.gelu(y @ w["router_w2"] + w["router_b2"], approximate=False)
    return jax.nn.softmax(y @ w["router_w3"], axis=-1)


def router_choice(p, w):
    """``(weights [T, E], margin [T])``: ``p`` on the one expert with
    the largest ``p + b`` and zero elsewhere; the gap between the two
    largest ``p + b``."""
    best, idx = jax.lax.top_k(p + w["router_bias"][None, :], 2)
    chosen = jax.nn.one_hot(idx[:, 0], p.shape[1], dtype=p.dtype)
    return p * chosen, best[:, 0] - best[:, 1]


def experts(x, w, weights):
    """Every expert on every token, summed under the router's weights,
    a group of experts at a time."""
    E = weights.shape[1]
    G = math.gcd(E, EXPERT_GROUP)

    def grouped(a):
        return a.reshape((E // G, G) + a.shape[1:])

    def group(y, g):
        gate, up, down, wg = g
        hid = jax.nn.silu(jnp.einsum("td,gdf->gtf", x, f32(gate))) \
            * jnp.einsum("td,gdf->gtf", x, f32(up))
        out = jnp.einsum("gtf,gfd->gtd", hid, f32(down))
        return y + jnp.einsum("tg,gtd->td", wg, out), None

    y, _ = jax.lax.scan(
        group, jnp.zeros_like(x),
        (grouped(w["e_gate"]), grouped(w["e_up"]), grouped(w["e_down"]),
         weights.reshape(-1, E // G, G).transpose(1, 0, 2)))
    return y


def routed(x, w, cfg, prev):
    """``(y, r_l, margin)`` over the normalised stream ``[T, D]``, a
    block of tokens at a time."""
    T = x.shape[0]
    Tb = blocks_of(T, TOKEN_BLOCK)
    r = router_state(x, w, prev)

    def block(args):
        xb, rb = args
        weights, margin = router_choice(router_probs(rb, w, cfg), w)
        return experts(xb, w, weights), margin

    y, margin = jax.lax.map(block, (x.reshape(T // Tb, Tb, -1),
                                    r.reshape(T // Tb, Tb, -1)))
    return y.reshape(x.shape), r, margin.reshape(T)


def layer(h, w, cfg, prev):
    eps = dims(cfg)["eps"]
    h = residual(h, attention(rms_norm(h, w["attn_norm"], eps), w, cfg),
                 w, "attn")
    y, r, margin = routed(rms_norm(h, w["ffn_norm"], eps), w, cfg, prev)
    return residual(h, y, w, "ffn"), r, margin


def hidden(params, tokens, cfg):
    """Final-RMSNorm output ``[T, D]`` of one sequence of token ids, and
    ``[T]`` the least ``router_margin`` a token met in any layer."""
    h = f32(params["top"]["embed"][tokens])
    least = jnp.full(h.shape[:1], jnp.inf, jnp.float32)
    r = None
    for w in params["layers"]:
        h, r, margin = layer(h, w, cfg, r)
        least = jnp.minimum(least, margin)
    return rms_norm(h, params["top"]["norm_f"], dims(cfg)["eps"]), least


def logits_and_margin_at(params, tokens, rows, cfg):
    """Logits ``[len(rows), V]`` at the given positions of one sequence,
    and ``[len(rows)]`` the least router margin of the token there."""
    with jax.default_matmul_precision("highest"):
        h, least = hidden(params, tokens, cfg)
        return h[rows] @ f32(params["top"]["embed"]).T, least[rows]


def logits_at(params, tokens, rows, cfg):
    return logits_and_margin_at(params, tokens, rows, cfg)[0]


def served_gaps_at(params, tokens, rows, served, cfg):
    """Of the tokens ``served [len(rows)]`` at the positions ``rows``:
    how far each one's logit lies below the best logit there, the least
    router margin of the position, and the served token's own logit. The
    head in blocks of rows: at 262,272 columns the logits of 4,096 rows
    are 4.3 GB."""
    with jax.default_matmul_precision("highest"):
        h, least = hidden(params, tokens, cfg)
        n = rows.shape[0]
        Rb = blocks_of(n, ROW_BLOCK)

        def block(args):
            hb, tok = args
            z = jnp.einsum("rd,vd->rv", hb, params["top"]["embed"],
                           preferred_element_type=jnp.float32)
            picked = jnp.take_along_axis(z, tok[:, None], axis=1)[:, 0]
            return jnp.max(z, axis=-1) - picked, picked

        gap, picked = jax.lax.map(
            block, (h[rows].reshape(n // Rb, Rb, -1),
                    served.reshape(n // Rb, Rb)))
        return gap.reshape(n), least[rows], picked.reshape(n)
