"""Plain reference for the XGLM decoder (facebook/xglm-*, fairseq).

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching tricks. It imports nothing of ``paddle_tpu`` and takes nothing
the program has made: the weights come from ``init_params(seed, cfg)``,
which is also what the benchmark hands to the program.

The block, as published (HF ``XGLMForCausalLM``): token embedding scaled
by ``sqrt(d_model)`` plus a fixed sinusoidal position table; pre-LN
decoder layers (LayerNorm eps 1e-5, q/k/v/out projections with biases,
exact-erf GELU feed-forward); a final LayerNorm; a vocabulary head.

Departures, the same ones the program makes (written in each
configuration file under ``departures``):

* the head is its own matrix (``head``), not the transposed embedding;
* the sinusoid is ``[sin | cos]`` of ``t / 10000**(2i/d)``: fairseq
  divides by ``half_dim - 1`` and offsets positions by 2;
* seeded random weights, biases and LayerNorm gains included, so that a
  path that drops a bias or a gain is seen.

Layer weights are stacked on a leading ``n_layers`` axis and the layers
run under ``lax.scan`` (one compiled layer body). ``remat=True`` wraps
the layer and the head chunks in ``jax.checkpoint`` so a 24-layer
backward pass at 2048 tokens fits beside the optimizer state; it changes
no value.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LAYER_LEAVES = ("ln1_g", "ln1_b", "wq", "bq", "wk", "bk", "wv", "bv",
                "wo", "bo", "ln2_g", "ln2_b", "w1", "b1", "w2", "b2")
TOP_LEAVES = ("embed", "lnf_g", "lnf_b", "head")
INIT_STD = 0.02          # XGLM config.json: init_std
LN_EPS = 1e-5
HEAD_CHUNK = 512         # rows of logits alive at once in the loss


def sizes(cfg):
    """(V, D, H, L, F) from a configuration file's published keys."""
    return (int(cfg["vocab_size"]), int(cfg["d_model"]),
            int(cfg["attention_heads"]), int(cfg["num_layers"]),
            int(cfg["ffn_dim"]))


def leaf_shapes(cfg):
    V, D, _H, L, F = sizes(cfg)
    per_layer = {"ln1_g": (D,), "ln1_b": (D,), "wq": (D, D), "bq": (D,),
                 "wk": (D, D), "bk": (D,), "wv": (D, D), "bv": (D,),
                 "wo": (D, D), "bo": (D,), "ln2_g": (D,), "ln2_b": (D,),
                 "w1": (D, F), "b1": (F,), "w2": (F, D), "b2": (D,)}
    shapes = {"embed": (V, D), "lnf_g": (D,), "lnf_b": (D,),
              "head": (D, V)}
    shapes.update({k: (L,) + s for k, s in per_layer.items()})
    return shapes


def n_params(cfg):
    return sum(int(np.prod(s)) for s in leaf_shapes(cfg).values())


def seed_words(seed):
    """``--seed`` as two 31-bit words (it may exceed 32 signed bits).
    Pass the words into a jitted function as an ARGUMENT: a seed closed
    over is a constant of the program, and every new seed would compile
    anew and miss the persistent cache."""
    seed = int(seed)
    return np.array([seed & 0x7FFFFFFF, seed >> 31], np.uint32)


def leaf_key(words, name):
    """One PRNG key per leaf: the seed's words folded with the leaf's
    index. ``words`` is ``seed_words(seed)``, traced or not."""
    key = jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])
    order = TOP_LEAVES + LAYER_LEAVES
    return jax.random.fold_in(key, order.index(name))


def init_leaf(words, name, shape):
    """Gains are 1 + N(0, std), everything else N(0, std)."""
    x = INIT_STD * jax.random.normal(leaf_key(words, name), shape,
                                     jnp.float32)
    return 1.0 + x if name.endswith("_g") else x


def init_params(words, cfg):
    """All leaves, float32, stacked layout, from ``seed_words(seed)``.
    Trace it inside one jit, the words an argument."""
    return {name: init_leaf(words, name, shape)
            for name, shape in leaf_shapes(cfg).items()}


def make_params(seed, cfg):
    """``init_params`` in one jitted call on the default device."""
    return jax.jit(lambda w: init_params(w, cfg))(seed_words(seed))


def position_table(n_pos, d_model):
    pos = np.arange(n_pos)[:, None]
    i = np.arange(d_model // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * i / d_model)
    return np.concatenate([np.sin(angle), np.cos(angle)],
                          axis=1).astype(np.float32)


def layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


def decoder_layer(x, w, n_heads):
    """x: [T, D] (one sequence); w: this layer's leaves."""
    T, D = x.shape
    dh = D // n_heads
    a = layer_norm(x, w["ln1_g"], w["ln1_b"])
    q = (a @ w["wq"] + w["bq"]).reshape(T, n_heads, dh)
    k = (a @ w["wk"] + w["bk"]).reshape(T, n_heads, dh)
    v = (a @ w["wv"] + w["bv"]).reshape(T, n_heads, dh)
    s = jnp.einsum("thd,shd->hts", q, k) * dh ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("hts,shd->thd", p, v).reshape(T, D)
    x = x + ctx @ w["wo"] + w["bo"]
    f = layer_norm(x, w["ln2_g"], w["ln2_b"])
    f = jax.nn.gelu(f @ w["w1"] + w["b1"], approximate=False)
    return x + f @ w["w2"] + w["b2"]


def hidden(params, tokens, cfg, remat=False):
    """Final-LayerNorm output [T, D] of one sequence of token ids."""
    _V, D, H, _L, _F = sizes(cfg)
    T = tokens.shape[0]
    pe = jnp.asarray(position_table(T, D))
    x = params["embed"][tokens] * math.sqrt(D) + pe

    def body(x, w):
        return decoder_layer(x, w, H), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, {k: params[k] for k in LAYER_LEAVES})
    return layer_norm(x, params["lnf_g"], params["lnf_b"])


def logits_at(params, tokens, rows, cfg):
    """Logits [len(rows), V] at the given positions of one sequence."""
    with jax.default_matmul_precision("highest"):
        return hidden(params, tokens, cfg)[rows] @ params["head"]


def sequence_loss_sum(params, tokens, labels, cfg, remat=True):
    """Sum of token cross-entropies of one sequence, the head in chunks
    of HEAD_CHUNK rows."""
    h = hidden(params, tokens, cfg, remat=remat)
    T = h.shape[0]
    chunk = min(HEAD_CHUNK, T)

    def head_chunk(h_c, y_c):
        z = h_c @ params["head"]
        lse = jax.nn.logsumexp(z, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(z, y_c[:, None], 1)[:, 0])

    if remat:
        head_chunk = jax.checkpoint(head_chunk)
    total = 0.0
    for s in range(0, T, chunk):
        total = total + head_chunk(h[s:s + chunk], labels[s:s + chunk])
    return total


def batch_loss(params, tokens, labels, cfg, remat=True):
    """Mean token cross-entropy over a [B, T] batch, row after row."""
    with jax.default_matmul_precision("highest"):
        def row(carry, tl):
            return carry + sequence_loss_sum(params, tl[0], tl[1], cfg,
                                             remat), None
        total, _ = jax.lax.scan(row, jnp.float32(0.0), (tokens, labels))
        return total / tokens.size


@functools.partial(jax.jit, static_argnames=("cfg_key",))
def _loss_and_grad(params, tokens, labels, cfg_key):
    cfg = dict(cfg_key)
    return jax.value_and_grad(batch_loss)(params, tokens, labels, cfg)


def loss_and_grad(params, tokens, labels, cfg):
    key = tuple((k, int(cfg[k])) for k in
                ("vocab_size", "d_model", "attention_heads", "num_layers",
                 "ffn_dim"))
    return _loss_and_grad(params, tokens, labels, key)


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def adam_leaf(p, m, v, g, step, lr, beta1, beta2, eps):
    """One Adam update of one leaf, as Fluid's ``adam`` op defines it
    (Kingma & Ba's algorithm with epsilon added before bias
    correction): ``lr_t = lr * sqrt(1 - b2**t) / (1 - b1**t)``,
    ``p -= lr_t * m / (sqrt(v) + eps)``. ``step`` counts from 1."""
    m = beta1 * m + (1.0 - beta1) * g
    v = beta2 * v + (1.0 - beta2) * g * g
    lr_t = lr * jnp.sqrt(1.0 - beta2 ** step) / (1.0 - beta1 ** step)
    return p - lr_t * m / (jnp.sqrt(v) + eps), m, v


def leaf_norms(tree):
    """{leaf: norms}: one norm for a top leaf, ``n_layers`` norms for a
    stacked leaf (one per layer's tensor)."""
    out = {}
    for k, t in tree.items():
        t = t.astype(jnp.float32)
        if k in TOP_LEAVES:
            out[k] = jnp.sqrt(jnp.sum(t * t))[None]
        else:
            out[k] = jnp.sqrt(jnp.sum(t * t, axis=tuple(range(1, t.ndim))))
    return out
