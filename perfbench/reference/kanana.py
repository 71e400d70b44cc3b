"""Plain reference for the Kanana-2 decoder (kakaocorp/kanana-2-30b-a3b,
``model_type`` ``deepseek_v3``).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the full causal forward of
one sequence, expanded attention only, every expert computed for every
token and summed under a mask of the router's weights; no cache, no
kernel, no sorting. It imports nothing of ``paddle_tpu`` and takes
nothing the program has made: the weights come from ``init_layer`` /
``init_top``, which is also what the benchmark hands to the program.

The block, from the published ``config.json`` (``h`` the residual
stream ``[T, hidden_size]``, no bias anywhere):

* attention, every layer: ``x = RMSNorm(h)``; ``q = x W_q`` split per
  head into ``q_nope`` (``qk_nope_head_dim``) and ``q_pe``
  (``qk_rope_head_dim``); ``[c_raw | k_pe] = x W_kva``; ``c =
  RMSNorm(c_raw)`` (``kv_lora_rank`` wide); rotary embedding on ``q_pe``
  and ``k_pe`` (theta ``rope_theta``, ``rope_interleave``: the pairs
  are the adjacent lanes, ``rope_scaling`` null); ``[k_nope | v] = c
  W_kvb`` per head; ``k = [k_nope | k_pe]``, the one ``k_pe`` shared by
  all heads; causal softmax of ``q k^T / sqrt(qk_head_dim)``; ``h +=
  (p v) W_o``;
* the first ``first_k_dense_replace`` layers: ``h += W_down(silu(x
  W_gate) * x W_up)`` at ``intermediate_size``;
* the others: ``s = sigmoid(x W_r)``; the ``num_experts_per_tok``
  experts with the largest ``s + b`` (``n_group`` = ``topk_group`` = 1:
  no group restriction; ``b`` chooses, it does not weigh); ``w = s[idx]
  / (sum + 1e-20) * routed_scaling_factor`` (``norm_topk_prob``); ``h +=
  sum_k w_k E_k(x) + S(x)``, ``E`` a SwiGLU of ``moe_intermediate_size``
  and ``S`` one of ``n_shared_experts`` times that;
* final RMSNorm and an untied head.

Beside the logits it reports how near a tie each token's router choice
was (``router_margin``, ``logits_and_margin_at``): with seeded weights
128 sigmoid scores lie ~0.01 apart, a served path that rounds its matmul
operands to bfloat16 moves a score by ~0.001-0.003, and a token whose
choice falls the other way has a sixth of its routed output changed.
The benchmark judges the arithmetic over the tokens that stand clear of
that (runner ``serve_latent``).

Departures (each configuration file lists them under ``departures``):
seeded random weights, N(0, ``init_std``) matrices, gains 1, ``b`` 0;
the depth the file gives.

The weights are kept in the storage type the configuration states
(``weight_dtype``, bfloat16: the published type, and 5 G parameters
would not fit the chip in float32) and each is taken to float32 where
it is used; the experts are computed in groups of ``EXPERT_GROUP`` under
``lax.scan`` so that one group's float32 copies are alive at a time.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
EXPERT_GROUP = 16
TOP_LEAVES = ("embed", "norm_f", "head")
LAYER_LEAVES = ("attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wo",
                "ffn_norm", "w_gate", "w_up", "w_down", "router",
                "router_bias", "e_gate", "e_up", "e_down", "s_gate",
                "s_up", "s_down")
FLOAT32_LEAVES = ("router", "router_bias")   # with every ``*norm*`` gain


def is_expert_layer(cfg, i):
    return i >= int(cfg["first_k_dense_replace"])


def top_shapes(cfg):
    V, D = int(cfg["vocab_size"]), int(cfg["hidden_size"])
    return {"embed": (V, D), "norm_f": (D,), "head": (D, V)}


def layer_shapes(cfg, i):
    D, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    dn, dr = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    dv, r = int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"])
    s = {"attn_norm": (D,), "wq": (D, H * (dn + dr)), "wkv_a": (D, r + dr),
         "kv_norm": (r,), "wkv_b": (r, H * (dn + dv)), "wo": (H * dv, D),
         "ffn_norm": (D,)}
    if is_expert_layer(cfg, i):
        E, Fe = int(cfg["n_routed_experts"]), int(cfg["moe_intermediate_size"])
        Fs = int(cfg["n_shared_experts"]) * Fe
        s.update({"router": (D, E), "router_bias": (E,),
                  "e_gate": (E, D, Fe), "e_up": (E, D, Fe),
                  "e_down": (E, Fe, D), "s_gate": (D, Fs), "s_up": (D, Fs),
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
    """One PRNG key per leaf: the seed's words, the layer (-1: the top
    leaves), the leaf's index."""
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


def init_params(words, cfg):
    """``{"top": leaves, "layers": [leaves, ...]}`` from
    ``seed_words(seed)``. Trace it inside a jit, the words an argument;
    at the published widths jit a layer at a time (``make_params``)."""
    return {"top": init_top(words, cfg),
            "layers": [init_layer(words, cfg, i)
                       for i in range(int(cfg["num_hidden_layers"]))]}


def make_params(seed, cfg):
    """``init_params`` on the default device, one jitted call a layer
    (the two kinds of layer compile once each)."""
    words = seed_words(seed)
    layer = jax.jit(lambda w, i: init_layer(w, cfg, i), static_argnums=1)
    return {"top": jax.jit(lambda w: init_top(w, cfg))(words),
            "layers": [layer(words, i)
                       for i in range(int(cfg["num_hidden_layers"]))]}


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def rotary(x, theta):
    """x: ``[T, ..., d]`` with the position on the first axis; rotates
    each adjacent pair of lanes ``(2i, 2i+1)`` by ``t * theta**(-2i/d)``."""
    T, d = x.shape[0], x.shape[-1]
    freq = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    angle = np.arange(T, dtype=np.float64)[:, None] * freq[None, :]
    shape = (T,) + (1,) * (x.ndim - 2) + (d // 2,)
    cos = jnp.asarray(np.cos(angle), jnp.float32).reshape(shape)
    sin = jnp.asarray(np.sin(angle), jnp.float32).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], -1)
    return out.reshape(x.shape)


def f32(w):
    return w.astype(jnp.float32)


def attention(h, w, cfg):
    T = h.shape[0]
    H = int(cfg["num_attention_heads"])
    dn, dr = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    dv, r = int(cfg["v_head_dim"]), int(cfg["kv_lora_rank"])
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    x = rms_norm(h, w["attn_norm"], eps)
    q = (x @ f32(w["wq"])).reshape(T, H, dn + dr)
    q_nope, q_pe = q[..., :dn], rotary(q[..., dn:], theta)
    kva = x @ f32(w["wkv_a"])
    c = rms_norm(kva[:, :r], w["kv_norm"], eps)
    k_pe = rotary(kva[:, r:], theta)                       # [T, dr]
    kv = (c @ f32(w["wkv_b"])).reshape(T, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    s = (jnp.einsum("thn,shn->hts", q_nope, k_nope)
         + jnp.einsum("thd,sd->hts", q_pe, k_pe)) / math.sqrt(dn + dr)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hts,shv->thv", p, v).reshape(T, H * dv)
    return h + o @ f32(w["wo"])


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ f32(w_gate)) * (x @ f32(w_up))) @ f32(w_down)


def router_margin(x, w, cfg):
    """``[T]``: how far the router's choice is from falling otherwise,
    the gap between the last expert chosen and the first one left out
    (``score + bias``, what the choice is made on). A served path whose
    router input differs from this reference's by less than that takes
    the same experts; below it the two may part, and a sixth of the
    token's routed output with them."""
    k = int(cfg["num_experts_per_tok"])
    s = jax.nn.sigmoid(x @ w["router"]) + w["router_bias"][None, :]
    best, _idx = jax.lax.top_k(s, k + 1)
    return best[:, k - 1] - best[:, k]


def router_weights(x, w, cfg):
    """``[T, E]``: each token's weight on every expert, zero on the
    experts it did not choose."""
    k = int(cfg["num_experts_per_tok"])
    s = jax.nn.sigmoid(x @ w["router"])
    _best, idx = jax.lax.top_k(s + w["router_bias"][None, :], k)
    picked = jnp.take_along_axis(s, idx, axis=1)
    weight = picked / (jnp.sum(picked, axis=1, keepdims=True) + 1e-20) \
        * float(cfg["routed_scaling_factor"])
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(weight)


def experts(x, w, cfg):
    """Every expert on every token, summed under the router's weights,
    a group of experts at a time."""
    E = int(cfg["n_routed_experts"])
    G = math.gcd(E, EXPERT_GROUP)
    dense_w = router_weights(x, w, cfg)                    # [T, E]

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
         dense_w.reshape(-1, E // G, G).transpose(1, 0, 2)))
    return y


def layer(h, w, cfg, i):
    """``(h', margin)``: the layer's output and, of an expert layer,
    each token's ``router_margin`` (``None`` of a dense one)."""
    h = attention(h, w, cfg)
    x = rms_norm(h, w["ffn_norm"], float(cfg["rms_norm_eps"]))
    if not is_expert_layer(cfg, i):
        return h + swiglu(x, w["w_gate"], w["w_up"], w["w_down"]), None
    return (h + experts(x, w, cfg) + swiglu(x, w["s_gate"], w["s_up"],
                                            w["s_down"]),
            router_margin(x, w, cfg))


def hidden(params, tokens, cfg):
    """Final-RMSNorm output ``[T, D]`` of one sequence of token ids, and
    ``[T]`` the least ``router_margin`` a token met in any expert layer
    (``inf`` where there is none)."""
    h = f32(params["top"]["embed"][tokens])
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
    """Logits ``[len(rows), V]`` at the given positions of one sequence."""
    return logits_and_margin_at(params, tokens, rows, cfg)[0]
