"""Plain reference for the Trinity decoder (arcee-ai/Trinity-Large-Preview,
``model_type`` ``afmoe``).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the full causal forward of
one sequence, every held expert computed for every token and summed
under a mask of the router's weights; no cache, no page, no kernel, no
sorting. It imports nothing of ``paddle_tpu`` and takes nothing the
program has made: the weights come from ``init_layer`` / ``init_top``,
which is also what the benchmark hands to the program.

The block, from the published ``config.json`` and, where the keys alone
do not fix it, the public ``modeling_afmoe.py`` (each such choice is
under ``assumed`` in the configuration file). ``h`` is the residual
stream ``[T, hidden_size]``, no bias anywhere:

* ``h0 = E[token] * sqrt(hidden_size)`` (``mup_enabled``);
* every layer, four RMSNorms (``rms_norm_eps``): ``h += N2(Attn(N1(h)))``
  then ``h += N4(FFN(N3(h)))``;
* ``Attn(x)``: ``q = x W_q`` (``num_attention_heads`` x ``head_dim``),
  ``k = x W_k``, ``v = x W_v`` (``num_key_value_heads`` x ``head_dim``),
  ``g = x W_g`` (as wide as ``q``); ``q`` and ``k`` each through an
  RMSNorm over ``head_dim`` (one gain vector each); on a
  ``sliding_attention`` layer ``q`` and ``k`` are rotated
  (``rope_theta``, no scaling, the half-split pairing ``(x_j, x_{j +
  head_dim/2})``), on a ``full_attention`` layer not at all; causal
  softmax of ``q k^T / sqrt(head_dim)``, query head ``n`` on cache head
  ``n // (heads / kv heads)``; on a sliding layer position ``t`` sees
  ``t - sliding_window < s <= t`` only; ``Attn = ((p v) * sigmoid(g))
  W_o``;
* ``FFN`` of the first ``num_dense_layers`` layers: SwiGLU at
  ``intermediate_size``;
* of the others: ``s = sigmoid(x W_r)`` over ALL ``router_experts``; the
  ``num_experts_per_tok`` experts with the largest ``s + b`` (``n_group``
  = ``topk_group`` = 1; ``b`` picks, it does not weigh); ``w = s[idx] /
  (sum + 1e-20) * route_scale`` (``route_norm``); ``FFN = sum_k w_k
  E_k(x) + S(x)``, the sum over the experts this chip HOLDS
  (``held(cfg)``: ``num_experts`` of them from ``experts_held_from``;
  a chosen expert held elsewhere adds nothing here), ``E`` and ``S``
  SwiGLUs of ``moe_intermediate_size``;
* final RMSNorm and an untied head over the ``vocab_size`` rows of the
  vocabulary this chip holds.

Beside the logits it reports how near a tie each token's router choice
was (``router_margin``), as ``reference/kanana.py`` does.

The weights are kept in bfloat16 and widened where used. Attention runs
in query blocks and the FFN in token blocks (``lax.map``), the held
experts in groups (``lax.scan``), so that a sequence of 34,816 positions
fits beside 8.6 GB of weights.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
QUERY_BLOCK = 256
TOKEN_BLOCK = 2048
EXPERT_GROUP = 8
TOP_LEAVES = ("embed", "norm_f", "head")
LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "wg", "q_norm", "k_norm",
                "wo", "attn_post_norm", "ffn_norm", "ffn_post_norm",
                "w_gate", "w_up", "w_down", "router", "router_bias",
                "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down")
FLOAT32_LEAVES = ("router", "router_bias")   # with every ``*norm*`` gain


def is_expert_layer(cfg, i):
    return i >= int(cfg["num_dense_layers"])


def held(cfg):
    """Global ids of the routed experts this chip holds."""
    first = int(cfg.get("experts_held_from", 0))
    return list(range(first, first + int(cfg["num_experts"])))


def router_experts(cfg):
    """How many experts the router chooses among (all of the model's)."""
    return int(cfg.get("router_experts", cfg["num_experts"]))


def top_shapes(cfg):
    V, D = int(cfg["vocab_size"]), int(cfg["hidden_size"])
    return {"embed": (V, D), "norm_f": (D,), "head": (D, V)}


def layer_shapes(cfg, i):
    D, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    Hkv, Dh = int(cfg["num_key_value_heads"]), int(cfg["head_dim"])
    s = {"attn_norm": (D,), "wq": (D, H * Dh), "wk": (D, Hkv * Dh),
         "wv": (D, Hkv * Dh), "wg": (D, H * Dh), "q_norm": (Dh,),
         "k_norm": (Dh,), "wo": (H * Dh, D), "attn_post_norm": (D,),
         "ffn_norm": (D,), "ffn_post_norm": (D,)}
    if is_expert_layer(cfg, i):
        Eh, Fe = int(cfg["num_experts"]), int(cfg["moe_intermediate_size"])
        Fs = int(cfg["num_shared_experts"]) * Fe
        s.update({"router": (D, router_experts(cfg)),
                  "router_bias": (router_experts(cfg),),
                  "e_gate": (Eh, D, Fe), "e_up": (Eh, D, Fe),
                  "e_down": (Eh, Fe, D), "s_gate": (D, Fs), "s_up": (D, Fs),
                  "s_down": (Fs, D)})
    else:
        F = int(cfg["intermediate_size"])
        s.update({"w_gate": (D, F), "w_up": (D, F), "w_down": (F, D)})
    return s


def n_params(cfg):
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


def init_leaf(words, layer, name, shape, cfg):
    if "norm" in name:
        return jnp.ones(shape, jnp.float32)
    if name == "router_bias":
        return jnp.zeros(shape, jnp.float32)
    x = float(cfg.get("init_std", INIT_STD)) * jax.random.normal(
        leaf_key(words, layer, name), shape, jnp.float32)
    return x if name in FLOAT32_LEAVES else x.astype(
        cfg.get("weight_dtype", "bfloat16"))


def init_top(words, cfg):
    return {n: init_leaf(words, -1, n, s, cfg)
            for n, s in top_shapes(cfg).items()}


def init_layer(words, cfg, i):
    """Layer ``i``'s leaves (``i`` a Python int: it decides the kind)."""
    return {n: init_leaf(words, i, n, s, cfg)
            for n, s in layer_shapes(cfg, i).items()}


def make_params(seed, cfg):
    """Every leaf on the default device, one jitted call a layer (the
    two kinds of layer compile once each)."""
    words = seed_words(seed)
    layer = jax.jit(lambda w, i: init_layer(w, cfg, i), static_argnums=1)
    return {"top": jax.jit(lambda w: init_top(w, cfg))(words),
            "layers": [layer(words, i)
                       for i in range(int(cfg["num_hidden_layers"]))]}


def f32(w):
    return w.astype(jnp.float32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def rotary(x, theta):
    """x: ``[T, heads, d]``, the position on the first axis; rotates the
    pair of lanes ``(j, j + d/2)`` by ``t * theta**(-2j/d)``."""
    T, d = x.shape[0], x.shape[-1]
    freq = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    angle = np.arange(T, dtype=np.float64)[:, None] * freq[None, :]
    cos = jnp.asarray(np.cos(angle), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(angle), jnp.float32)[:, None, :]
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], -1)


def blocks_of(n, want):
    """The largest block size up to ``want`` that divides ``n``."""
    return max(b for b in range(1, min(want, n) + 1) if n % b == 0)


def attention(x, w, cfg, sliding):
    """``Attn(x)`` of one layer, ``x`` the normalised stream ``[T, D]``."""
    T = x.shape[0]
    H, Hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    Dh, G = int(cfg["head_dim"]), H // Hkv
    eps, window = float(cfg["rms_norm_eps"]), int(cfg["sliding_window"])
    q = rms_norm((x @ f32(w["wq"])).reshape(T, H, Dh), w["q_norm"], eps)
    k = rms_norm((x @ f32(w["wk"])).reshape(T, Hkv, Dh), w["k_norm"], eps)
    v = (x @ f32(w["wv"])).reshape(T, Hkv, Dh)
    gate = jax.nn.sigmoid(x @ f32(w["wg"]))
    if sliding:
        q, k = rotary(q, float(cfg["rope_theta"])), \
            rotary(k, float(cfg["rope_theta"]))
    Qb = blocks_of(T, QUERY_BLOCK)
    s_pos = jnp.arange(T)[None, :]

    def block(args):
        qb, t0 = args                                  # [Qb, Hkv, G, Dh]
        t_pos = (t0 + jnp.arange(Qb))[:, None]
        seen = s_pos <= t_pos
        if sliding:
            seen = seen & (s_pos > t_pos - window)
        s = jnp.einsum("tkgd,skd->kgts", qb, k) / math.sqrt(Dh)
        p = jax.nn.softmax(jnp.where(seen[None, None], s, -jnp.inf), -1)
        return jnp.einsum("kgts,skd->tkgd", p, v)

    o = jax.lax.map(block, (q.reshape(T // Qb, Qb, Hkv, G, Dh),
                            jnp.arange(0, T, Qb)))
    return (o.reshape(T, H * Dh) * gate) @ f32(w["wo"])


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ f32(w_gate)) * (x @ f32(w_up))) @ f32(w_down)


def router_scores(x, w):
    return jax.nn.sigmoid(x @ w["router"])


def router_margin(x, w, cfg):
    """``[T]``: the gap between the last expert chosen and the first one
    left out (``score + bias``, what the choice is made on)."""
    k = int(cfg["num_experts_per_tok"])
    best, _idx = jax.lax.top_k(
        router_scores(x, w) + w["router_bias"][None, :], k + 1)
    return best[:, k - 1] - best[:, k]


def router_weights(x, w, cfg):
    """``[T, E]``: each token's weight on every expert of the MODEL, zero
    on the experts it did not choose."""
    k = int(cfg["num_experts_per_tok"])
    s = router_scores(x, w)
    _best, idx = jax.lax.top_k(s + w["router_bias"][None, :], k)
    picked = jnp.take_along_axis(s, idx, axis=1)
    weight = picked / (jnp.sum(picked, axis=1, keepdims=True) + 1e-20) \
        * float(cfg["route_scale"])
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(weight)


def experts(x, w, cfg):
    """Every HELD expert on every token, summed under the router's
    weights on them, a group of experts at a time."""
    ids = np.asarray(held(cfg))
    Eh = len(ids)
    G = math.gcd(Eh, EXPERT_GROUP)
    dense_w = router_weights(x, w, cfg)[:, ids]            # [T, Eh]

    def grouped(a):
        return a.reshape((Eh // G, G) + a.shape[1:])

    def group(y, g):
        gate, up, down, wg = g
        hid = jax.nn.silu(jnp.einsum("td,gdf->gtf", x, f32(gate))) \
            * jnp.einsum("td,gdf->gtf", x, f32(up))
        out = jnp.einsum("gtf,gfd->gtd", hid, f32(down))
        return y + jnp.einsum("tg,gtd->td", wg, out), None

    y, _ = jax.lax.scan(
        group, jnp.zeros_like(x),
        (grouped(w["e_gate"]), grouped(w["e_up"]), grouped(w["e_down"]),
         dense_w.reshape(-1, Eh // G, G).transpose(1, 0, 2)))
    return y


def ffn(x, w, cfg, i):
    """``(FFN(x), margin)`` over the normalised stream ``[T, D]``, a
    block of tokens at a time (``margin`` None of a dense layer)."""
    T = x.shape[0]
    Tb = blocks_of(T, TOKEN_BLOCK)
    xb = x.reshape(T // Tb, Tb, -1)
    if not is_expert_layer(cfg, i):
        y = jax.lax.map(lambda b: swiglu(b, w["w_gate"], w["w_up"],
                                         w["w_down"]), xb)
        return y.reshape(x.shape), None

    def block(b):
        return (experts(b, w, cfg) + swiglu(b, w["s_gate"], w["s_up"],
                                            w["s_down"]),
                router_margin(b, w, cfg))

    y, margin = jax.lax.map(block, xb)
    return y.reshape(x.shape), margin.reshape(T)


def layer(h, w, cfg, i):
    eps = float(cfg["rms_norm_eps"])
    sliding = cfg["layer_types"][i] == "sliding_attention"
    h = h + rms_norm(attention(rms_norm(h, w["attn_norm"], eps), w, cfg,
                               sliding), w["attn_post_norm"], eps)
    y, margin = ffn(rms_norm(h, w["ffn_norm"], eps), w, cfg, i)
    return h + rms_norm(y, w["ffn_post_norm"], eps), margin


def hidden(params, tokens, cfg):
    """Final-RMSNorm output ``[T, D]`` of one sequence of token ids, and
    ``[T]`` the least ``router_margin`` a token met in any expert layer
    (``inf`` where there is none)."""
    h = f32(params["top"]["embed"][tokens])
    if cfg.get("mup_enabled"):
        h = h * math.sqrt(int(cfg["hidden_size"]))
    least = jnp.full(h.shape[:1], jnp.inf, jnp.float32)
    for i, w in enumerate(params["layers"]):
        h, margin = layer(h, w, cfg, i)
        if margin is not None:
            least = jnp.minimum(least, margin)
    return (rms_norm(h, params["top"]["norm_f"], float(cfg["rms_norm_eps"])),
            least)


def logits_and_margin_at(params, tokens, rows, cfg):
    """Logits ``[len(rows), V]`` at the given positions of one sequence,
    and ``[len(rows)]`` the least router margin of the token there."""
    with jax.default_matmul_precision("highest"):
        h, least = hidden(params, tokens, cfg)
        return h[rows] @ f32(params["top"]["head"]), least[rows]


def logits_at(params, tokens, rows, cfg):
    return logits_and_margin_at(params, tokens, rows, cfg)[0]
