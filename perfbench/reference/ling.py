"""Plain reference for the Ling-3.0 decoder (inclusionAI/Ling-3.0-flash,
``model_type`` ``bailing_hybrid``).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: the full causal forward of
one sequence; the linear-attention layers as the token-by-token
recurrence and nothing cleverer, the convolutions as shifts of the whole
sequence, latent attention expanded to per-head keys and values, every
held expert computed for every token and summed under a mask of the
router's weights; no cache, no state carried between calls, no chunk, no
page, no kernel, no sorting. It imports nothing of ``paddle_tpu`` and
takes nothing the program has made: the weights come from ``init_layer``
/ ``init_top``, which is also what the benchmark hands to the program.

The block, from the published ``config.json`` and, where the keys alone
do not fix it, the public description of KDA (Kimi Linear,
arXiv:2510.26692) and of latent attention (DeepSeek-V2/V3); each such
choice is under ``assumed`` in the configuration file. ``h`` is the
residual stream ``[T, hidden_size]``, no bias but the decay's:

* ``h0 = E[token]``; a layer: ``h += Attn(N1(h)); h += FFN(N2(h))``,
  RMSNorm (``rms_norm_eps``); final norm, untied head over the
  ``vocab_size`` rows this chip holds;
* a KDA layer (published index ``i`` with ``(i + 1) % layer_group_size
  != 0``; H heads of ``head_dim`` = dk = dv): ``q~, k~, v~ = x W_q, x
  W_k, x W_v``; every channel through its own causal convolution of
  ``short_conv_kernel_size`` taps (inputs before position 0 are zero),
  then SiLU; a head: ``q = l2norm(q) / sqrt(dk)``, ``k = l2norm(k)``;
  the decay a channel ``g = kda_lower_bound * sigmoid(exp(A_h) * (x W_a
  + b_a))``, ``a = exp(g)``; ``b = sigmoid(x W_b)`` one a head; ``S_t =
  (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T``, ``S_0 = 0``,
  ``o_t = S_t^T q_t``; ``Attn = (RMSNorm_head(o) * sigmoid(x W_g)) W_o``,
  the gate one value a head;
* an MLA layer (``(i + 1) % layer_group_size == 0``): ``q = RMSNorm(x
  W_q)`` a head of ``qk_nope_head_dim + qk_rope_head_dim``; ``[c | k^R]
  = x W_kva``, ``c`` through its RMSNorm, ``k^R`` through its own, then
  ``k^R`` and ``q``'s last ``qk_rope_head_dim`` lanes through the
  interleaved rotary (``rope_theta``); keys ``[c W_uk | k^R]``, values
  ``c W_uv`` a head; causal softmax at ``(nope + rope)^-1/2``; ``Attn =
  (o * sigmoid(x W_g)) W_o``;
* ``FFN`` of the first ``first_k_dense_replace`` layers held: SwiGLU at
  ``intermediate_size``; of the others ``s = sigmoid(x W_r)`` over ALL
  ``router_experts``; the choice is made on ``s + b``: a group's score
  is the sum of its two largest, the best ``topk_group`` of ``n_group``
  groups are kept, the ``num_experts_per_tok`` largest inside them are
  chosen; ``w = s[chosen] / (sum + 1e-20) * routed_scaling_factor``;
  ``FFN = sum_k w_k E_k(x) + S(x)``, the sum over the experts this chip
  HOLDS (``held(cfg)``; a chosen expert held elsewhere adds nothing
  here), ``E`` and ``S`` SwiGLUs of ``moe_intermediate_size``.

Beside the logits it reports how near a tie each token's router choice
was (``router_margin``: the lesser of the gap between the last expert
chosen and the first left out among the kept groups, and the gap between
the last group kept and the first dropped), and each KDA layer's scan
state after a given token (``hidden``'s ``stop``).

The weights are kept in bfloat16 and widened where used. Attention runs
in query blocks and the FFN in token blocks (``lax.map``), the held
experts in groups (``lax.scan``), the head in blocks of rows, so that a
long sequence fits beside 10 GB of weights.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02
QUERY_BLOCK = 128
TOKEN_BLOCK = 2048
ROW_BLOCK = 512
EXPERT_GROUP = 8
HEAD_GROUP = 8
KDA, MLA = "kda", "mla"
TOP_LEAVES = ("embed", "norm_f", "head")
LAYER_LEAVES = ("attn_norm", "wq", "wk", "wv", "conv_w", "w_alpha",
                "alpha_bias", "a_log", "w_beta", "o_norm", "w_ogate", "wo",
                "q_norm", "wkv_a", "kv_norm", "k_norm", "w_uk", "w_uv",
                "ffn_norm", "w_gate", "w_up", "w_down", "router",
                "router_bias", "e_gate", "e_up", "e_down", "s_gate", "s_up",
                "s_down")
# float32 leaves beside every ``*norm`` gain
FLOAT32_LEAVES = ("router", "router_bias", "conv_w", "alpha_bias", "a_log")
# the decay's bias is drawn uniformly between these
ALPHA_BIAS = (-6.0, -2.0)


def dims(cfg):
    H, d = int(cfg["num_attention_heads"]), int(cfg["head_dim"])
    return dict(D=int(cfg["hidden_size"]), H=H, d=d,
                taps=int(cfg["short_conv_kernel_size"]),
                dn=int(cfg["qk_nope_head_dim"]),
                dr=int(cfg["qk_rope_head_dim"]),
                dv=int(cfg["v_head_dim"]), r=int(cfg["kv_lora_rank"]),
                V=int(cfg["vocab_size"]), eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]),
                bound=float(cfg["kda_lower_bound"]))


def first_layer(cfg):
    """The published index of the first layer held."""
    return int(cfg.get("published", {}).get("layers_held", [0])[0])


def layer_types(cfg):
    """``"kda"`` or ``"mla"`` a layer held: published layer ``i`` is
    latent attention where ``(i + 1) % layer_group_size == 0``."""
    group = int(cfg["layer_group_size"])
    return [MLA if (first_layer(cfg) + j + 1) % group == 0 else KDA
            for j in range(int(cfg["num_hidden_layers"]))]


def is_expert_layer(cfg, i):
    return i >= int(cfg["first_k_dense_replace"])


def held(cfg):
    """Global ids of the routed experts this chip holds."""
    first = int(cfg.get("experts_held_from", 0))
    return list(range(first, first + int(cfg["num_experts"])))


def router_experts(cfg):
    """How many experts the router chooses among (all of the model's)."""
    return int(cfg.get("router_experts", cfg["num_experts"]))


def scan_state_shape(cfg):
    """``[KDA layers, H, dk, dv]``: the scan states of one sequence."""
    m = dims(cfg)
    return (layer_types(cfg).count(KDA), m["H"], m["d"], m["d"])


def top_shapes(cfg):
    d = dims(cfg)
    return {"embed": (d["V"], d["D"]), "norm_f": (d["D"],),
            "head": (d["D"], d["V"])}


def layer_shapes(cfg, i):
    m = dims(cfg)
    D, H, d = m["D"], m["H"], m["d"]
    s = {"attn_norm": (D,), "w_ogate": (D, H), "ffn_norm": (D,)}
    if layer_types(cfg)[i] == KDA:
        s.update({"wq": (D, H * d), "wk": (D, H * d), "wv": (D, H * d),
                  "conv_w": (m["taps"], 3 * H * d), "w_alpha": (D, H * d),
                  "alpha_bias": (H * d,), "a_log": (H,), "w_beta": (D, H),
                  "o_norm": (d,), "wo": (H * d, D)})
    else:
        s.update({"wq": (D, H * (m["dn"] + m["dr"])),
                  "q_norm": (m["dn"] + m["dr"],),
                  "wkv_a": (D, m["r"] + m["dr"]), "kv_norm": (m["r"],),
                  "k_norm": (m["dr"],), "w_uk": (H, m["dn"], m["r"]),
                  "w_uv": (H, m["r"], m["dv"]), "wo": (H * m["dv"], D)})
    if is_expert_layer(cfg, i):
        Eh, Fe = int(cfg["num_experts"]), int(cfg["moe_intermediate_size"])
        Fs = int(cfg["num_shared_experts"]) \
            * int(cfg["moe_shared_expert_intermediate_size"])
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
    """Matrices N(0, ``init_std``); the convolutions' taps N(0, 1 /
    taps), so that a convolved channel stands as large as its input; the
    decay's bias uniform in :data:`ALPHA_BIAS` (a channel's decay then
    lies between 0.55 and 0.99 a token: memories of two to a hundred
    tokens side by side, as a trained layer has them; at a bias of 0
    every channel would forget within two tokens and the state carried
    between steps would hardly be measured), ``A`` = 0 (``exp`` 1); the
    router's bias 0; gains 1, or, where the configuration states
    ``init_gain_noise`` (the tests'), that far off them."""
    noise = float(cfg.get("init_gain_noise", 0.0))
    key = leaf_key(words, layer, name)
    if name.endswith("norm") or name == "norm_f":
        return 1.0 + noise * jax.random.normal(key, shape, jnp.float32)
    if name in ("router_bias", "a_log"):
        return noise * jax.random.normal(key, shape, jnp.float32)
    if name == "alpha_bias":
        return jax.random.uniform(key, shape, jnp.float32, *ALPHA_BIAS)
    std = shape[0] ** -0.5 if name == "conv_w" \
        else float(cfg.get("init_std", INIT_STD))
    x = std * jax.random.normal(key, shape, jnp.float32)
    return x if name in FLOAT32_LEAVES else x.astype(
        cfg.get("weight_dtype", "bfloat16"))


def init_top(words, cfg):
    return {n: init_leaf(words, -1, n, s, cfg)
            for n, s in top_shapes(cfg).items()}


def init_layer(words, cfg, i, like=None):
    """Layer ``i``'s leaves. ``like`` (a Python int, ``i`` itself where
    it is left out) is a layer with the same leaves: it decides the
    kind, so that ``i`` may be traced."""
    return {n: init_leaf(words, i, n, s, cfg)
            for n, s in layer_shapes(cfg, i if like is None else like
                                     ).items()}


def layer_maker(cfg):
    """``make(words, i)``: layer ``i``'s leaves on the default device,
    one jitted call a layer and one program a kind of layer (the index
    is an argument of it: five of the seven layers held are alike, and
    a program of an expert layer's leaves takes the v5e's compiler
    ~20 s)."""
    shapes = [layer_shapes(cfg, i)
              for i in range(int(cfg["num_hidden_layers"]))]
    make = jax.jit(lambda w, i, like: init_layer(w, cfg, i, like),
                   static_argnums=2)
    return lambda words, i: make(words, np.int32(i),
                                 shapes.index(shapes[i]))


def make_params(seed, cfg):
    """Every leaf on the default device (:func:`layer_maker`)."""
    words = seed_words(seed)
    layer = layer_maker(cfg)
    return {"top": jax.jit(lambda w: init_top(w, cfg))(words),
            "layers": [layer(words, i)
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


def rotary_interleaved(x, theta):
    """x: ``[T, ..., d]``, the position on the first axis; rotates the
    ADJACENT pairs of lanes ``(2j, 2j + 1)`` by ``t * theta**(-2j/d)``."""
    T, d = x.shape[0], x.shape[-1]
    freq = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    angle = np.arange(T, dtype=np.float64)[:, None] * freq[None, :]
    shape = (T,) + (1,) * (x.ndim - 2) + (d // 2,)
    cos = jnp.asarray(np.cos(angle), jnp.float32).reshape(shape)
    sin = jnp.asarray(np.sin(angle), jnp.float32).reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def blocks_of(n, want):
    """The largest block size up to ``want`` that divides ``n``."""
    return max(b for b in range(1, min(want, n) + 1) if n % b == 0)


def l2_normalised(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-12)


def kda_inputs(x, w, cfg):
    """``(q, k, v, g, beta)`` of some heads of one KDA layer over the
    normalised stream ``x [T, D]``, ``w`` those heads' columns of the
    layer's leaves (``conv_w [taps, 3, heads * d]``): ``q``, ``k``, ``g``
    ``[T, heads, dk]``, ``v`` ``[T, heads, dv]``, ``beta`` ``[T,
    heads]``."""
    m = dims(cfg)
    T, d, taps = x.shape[0], m["d"], m["taps"]
    z = jnp.stack([x @ w["wq"], x @ w["wk"], x @ w["wv"]], axis=1)
    y = sum(w["conv_w"][taps - 1 - j] * shifted(z, j) for j in range(taps))
    y = jax.nn.silu(y).reshape(T, 3, -1, d)
    q = l2_normalised(y[:, 0]) / math.sqrt(d)
    k = l2_normalised(y[:, 1])
    g = m["bound"] * jax.nn.sigmoid(
        jnp.repeat(jnp.exp(w["a_log"]), d)
        * (x @ w["w_alpha"] + w["alpha_bias"])).reshape(T, -1, d)
    return q, k, y[:, 2], g, jax.nn.sigmoid(x @ w["w_beta"])


def kda_scan(q, k, v, g, beta, stop=None):
    """The recurrence, a token at a time from ``S = 0``: ``(o [T, heads,
    dv], S [heads, dk, dv])``, ``S`` the state after the last token, or
    after token ``stop - 1`` where ``stop`` is given (a sequence padded
    beyond its end)."""
    T, H, dk, dv = q.shape[0], q.shape[1], q.shape[2], v.shape[2]
    live = jnp.arange(T) < (T if stop is None else stop)

    def token(carry, t):
        S, kept = carry
        qt, kt, vt, gt, bt, on = t
        S = jnp.exp(gt)[:, :, None] * S
        u = bt[:, None] * (vt - jnp.einsum("hk,hkv->hv", kt, S))
        S = S + kt[:, :, None] * u[:, None, :]
        return (S, jnp.where(on, S, kept)), jnp.einsum("hk,hkv->hv", qt, S)

    zero = jnp.zeros((H, dk, dv), jnp.float32)
    (_, kept), o = jax.lax.scan(token, (zero, zero),
                                (q, k, v, g, beta, live))
    return o, kept


def kda_attention(x, w, cfg, stop=None):
    """The KDA sublayer's result before the output gate, ``[T, H,
    dv]``: the scan's read-out through its RMSNorm a head; and the
    scan's state ``[H, dk, dv]`` after token ``stop - 1`` (the last
    where ``stop`` is None). The heads are independent; they are taken a
    group at a time so that a long sequence's ``q``, ``k``, ``v`` and
    ``g`` fit."""
    m = dims(cfg)
    T, D, H, d, taps = x.shape[0], m["D"], m["H"], m["d"], m["taps"]
    G = math.gcd(H, HEAD_GROUP)
    n = H // G

    def columns(a):
        """``[D, H * c]`` -> ``[n, D, G * c]``, a group's columns."""
        return f32(a).reshape(D, n, -1).transpose(1, 0, 2)

    groups = {name: columns(w[name])
              for name in ("wq", "wk", "wv", "w_alpha", "w_beta")}
    groups["conv_w"] = w["conv_w"].reshape(taps, 3, n, G * d) \
        .transpose(2, 0, 1, 3)
    groups["alpha_bias"] = w["alpha_bias"].reshape(n, G * d)
    groups["a_log"] = w["a_log"].reshape(n, G)
    o, S = jax.lax.map(lambda g: kda_scan(*kda_inputs(x, g, cfg), stop),
                       groups)
    o = o.transpose(1, 0, 2, 3).reshape(T, H, d)
    return rms_norm(o, w["o_norm"], m["eps"]), S.reshape(H, d, d)


def mla_attention(x, w, cfg):
    """The MLA sublayer's result before the output gate, ``[T, H,
    dv]``."""
    m = dims(cfg)
    T, H, dn, dr, r, eps = x.shape[0], m["H"], m["dn"], m["dr"], m["r"], \
        m["eps"]
    q = rms_norm((x @ f32(w["wq"])).reshape(T, H, dn + dr), w["q_norm"], eps)
    kva = x @ f32(w["wkv_a"])
    c = rms_norm(kva[:, :r], w["kv_norm"], eps)
    k_pe = rotary_interleaved(rms_norm(kva[:, r:], w["k_norm"], eps),
                              m["theta"])                  # [T, dr]
    q_pe = rotary_interleaved(q[..., dn:], m["theta"])
    k_nope = jnp.einsum("tr,hnr->thn", c, f32(w["w_uk"]))
    v = jnp.einsum("tr,hrv->thv", c, f32(w["w_uv"]))
    Qb = blocks_of(T, QUERY_BLOCK)
    s_pos = jnp.arange(T)[None, :]

    def block(args):
        qn, qp, t0 = args                                  # [Qb, H, .]
        seen = s_pos <= (t0 + jnp.arange(Qb))[:, None]
        s = (jnp.einsum("thn,shn->hts", qn, k_nope)
             + jnp.einsum("thd,sd->hts", qp, k_pe)) / math.sqrt(dn + dr)
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
        return jnp.einsum("hts,shv->thv", p, v)

    o = jax.lax.map(block, (q[..., :dn].reshape(T // Qb, Qb, H, dn),
                            q_pe.reshape(T // Qb, Qb, H, dr),
                            jnp.arange(0, T, Qb)))
    return o.reshape(T, H, m["dv"])


def attention(x, w, cfg, kind, stop=None):
    """``(Attn(x), S)`` of one layer, ``x`` the normalised stream ``[T,
    D]``, ``S`` a KDA layer's scan state (None of an MLA layer)."""
    o, S = kda_attention(x, w, cfg, stop) if kind == KDA \
        else (mla_attention(x, w, cfg), None)
    gate = jax.nn.sigmoid(x @ f32(w["w_ogate"]))           # [T, H]
    return (o * gate[:, :, None]).reshape(x.shape[0], -1) @ f32(w["wo"]), S


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ f32(w_gate)) * (x @ f32(w_up))) @ f32(w_down)


def router_scores(x, w):
    return jax.nn.sigmoid(x @ w["router"])


def kept_groups(choice, cfg):
    """``(choice [T, E] with the experts outside each token's best
    ``topk_group`` groups at -inf, group margin [T])``: a group's score
    is the sum of its two largest ``s + b``; the margin is the gap
    between the last group kept and the first dropped (``inf`` where
    every group is kept)."""
    n, keep = int(cfg.get("n_group", 1)), int(cfg.get("topk_group", 1))
    T, E = choice.shape
    if n <= 1 or keep >= n:
        return choice, jnp.full((T,), jnp.inf, jnp.float32)
    grouped = choice.reshape(T, n, E // n)
    best2, _ = jax.lax.top_k(grouped, 2)
    score = jnp.sum(best2, axis=-1)                        # [T, n]
    top, idx = jax.lax.top_k(score, keep + 1)
    kept = jnp.any(idx[:, :keep, None] == jnp.arange(n)[None, None, :],
                   axis=1)
    return (jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(T, E),
            top[:, keep - 1] - top[:, keep])


def router_margin(x, w, cfg):
    """``[T]``: how near a tie the choice was."""
    k = int(cfg["num_experts_per_tok"])
    choice, group_margin = kept_groups(
        router_scores(x, w) + w["router_bias"][None, :], cfg)
    best, _idx = jax.lax.top_k(choice, k + 1)
    return jnp.minimum(best[:, k - 1] - best[:, k], group_margin)


def router_weights(x, w, cfg):
    """``[T, E]``: each token's weight on every expert of the MODEL, zero
    on the experts it did not choose."""
    k = int(cfg["num_experts_per_tok"])
    s = router_scores(x, w)
    choice, _ = kept_groups(s + w["router_bias"][None, :], cfg)
    _best, idx = jax.lax.top_k(choice, k)
    picked = jnp.take_along_axis(s, idx, axis=1)
    weight = picked / (jnp.sum(picked, axis=1, keepdims=True) + 1e-20) \
        * float(cfg["routed_scaling_factor"])
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


def layer(h, w, cfg, i, stop=None):
    eps = dims(cfg)["eps"]
    y, S = attention(rms_norm(h, w["attn_norm"], eps), w, cfg,
                     layer_types(cfg)[i], stop)
    h = h + y
    y, margin = ffn(rms_norm(h, w["ffn_norm"], eps), w, cfg, i)
    return h + y, margin, S


def hidden(params, tokens, cfg, stop=None):
    """Final-RMSNorm output ``[T, D]`` of one sequence of token ids,
    ``[T]`` the least ``router_margin`` a token met in any expert layer
    (``inf`` where there is none), and ``[KDA layers, H, dk, dv]`` the
    scan states after token ``stop - 1`` (the last where ``stop`` is
    None)."""
    h = f32(params["top"]["embed"][tokens])
    least = jnp.full(h.shape[:1], jnp.inf, jnp.float32)
    states = []
    for i, w in enumerate(params["layers"]):
        h, margin, S = layer(h, w, cfg, i, stop)
        if margin is not None:
            least = jnp.minimum(least, margin)
        if S is not None:
            states.append(S)
    return (rms_norm(h, params["top"]["norm_f"], dims(cfg)["eps"]), least,
            jnp.stack(states))


def logits_and_margin_at(params, tokens, rows, cfg):
    """Logits ``[len(rows), V]`` at the given positions of one sequence,
    and ``[len(rows)]`` the least router margin of the token there."""
    with jax.default_matmul_precision("highest"):
        h, least, _states = hidden(params, tokens, cfg)
        return h[rows] @ f32(params["top"]["head"]), least[rows]


def logits_at(params, tokens, rows, cfg):
    return logits_and_margin_at(params, tokens, rows, cfg)[0]


def served_gaps_at(params, tokens, rows, served, cfg, stop=None):
    """Of the tokens ``served [len(rows)]`` at the positions ``rows``:
    how far each one's logit lies below the best logit there, the least
    router margin of the position, and the served token's own logit;
    then the scan states ``[KDA layers, H, dk, dv]`` after the first
    ``stop`` tokens (all of them where ``stop`` is None). The head in
    blocks of rows."""
    with jax.default_matmul_precision("highest"):
        h, least, states = hidden(params, tokens, cfg, stop)
        n = rows.shape[0]
        Rb = blocks_of(n, ROW_BLOCK)

        def block(args):
            hb, tok = args
            z = jnp.einsum("rd,dv->rv", hb, params["top"]["head"],
                           preferred_element_type=jnp.float32)
            picked = jnp.take_along_axis(z, tok[:, None], axis=1)[:, 0]
            return jnp.max(z, axis=-1) - picked, picked

        gap, picked = jax.lax.map(
            block, (h[rows].reshape(n // Rb, Rb, -1),
                    served.reshape(n // Rb, Rb)))
        return gap.reshape(n), least[rows], picked.reshape(n), states
