"""Flagship Transformer LM — TPU-first model math.

This is the model the benchmark + graft entry drive. Unlike the fluid-layer
DSL (which exists for API parity), the flagship is written directly as pure
JAX functions over a param pytree so the SPMD trainer
(paddle_tpu/parallel/transformer.py) can shard it with shard_map:

- weights layout chosen for the MXU: all matmuls are [*, D] x [D, *] dots in
  bfloat16 with fp32 accumulation
- attention heads on the tensor-parallel axis; sequence-parallel residual
  stream (Megatron-SP style all_gather/reduce_scatter seams are in the
  *trainer*, not here — these functions compute on whatever local shard they
  are handed)
- optional mixture-of-experts FFN (expert-parallel over the data axis)

Reference counterpart: Fluid's transformer benchmark model
(benchmark/fluid/models/machine_translation.py + dist_transformer.py) — the
capability target, not the design.
"""

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import device as _device


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    d_ff: int = 2048
    max_seq_len: int = 512
    dtype: Any = jnp.bfloat16
    # MoE: 0 experts = dense. One MoE FFN per pipeline stage when enabled.
    n_experts: int = 0
    expert_capacity_factor: float = 2.0
    dropout: float = 0.0
    tie_embeddings: bool = True
    remat: bool = True

    @property
    def head_dim(self):
        return self.d_model // self.n_heads


def init_params(key, cfg: TransformerConfig):
    """Full (unsharded) parameter pytree. Layer weights carry a leading
    [n_layers] axis so the pipeline axis can shard them directly."""
    D, H, Dh, F, L, V = (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff,
                         cfg.n_layers, cfg.vocab_size)
    k = iter(jax.random.split(key, 16 + L))

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(jnp.float32)

    params = {
        "embed": dense(next(k), (V, D), D),
        "pos_embed": dense(next(k), (cfg.max_seq_len, D), D),
        "final_ln_scale": jnp.ones((D,), jnp.float32),
        "final_ln_bias": jnp.zeros((D,), jnp.float32),
        "layers": {
            "ln1_scale": jnp.ones((L, D), jnp.float32),
            "ln1_bias": jnp.zeros((L, D), jnp.float32),
            "wqkv": dense(next(k), (L, D, 3, H, Dh), D),
            "wo": dense(next(k), (L, H, Dh, D), D),
            "ln2_scale": jnp.ones((L, D), jnp.float32),
            "ln2_bias": jnp.zeros((L, D), jnp.float32),
            "w1": dense(next(k), (L, D, F), D),
            "b1": jnp.zeros((L, F), jnp.float32),
            "w2": dense(next(k), (L, F, D), F),
            "b2": jnp.zeros((L, D), jnp.float32),
        },
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(next(k), (D, V), D)
    if cfg.n_experts:
        E = cfg.n_experts
        params["moe"] = {
            "router": dense(next(k), (D, E), D),
            "w1": dense(next(k), (E, D, F), D),
            "w2": dense(next(k), (E, F, D), F),
        }
    return params


def layer_norm(x, scale, bias, eps=1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), -1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps) * scale + bias
    return out.astype(x.dtype)


def causal_attention(q, k, v, seq_offset=0, use_flash=None):
    """q,k,v: [B, T, H, Dh] (H may be a tp-local slice). fp32 softmax,
    bf16 matmuls on the MXU. On block-aligned self-attention the flash
    kernel dispatcher (ops/pallas_kernels.flash_attention — library TPU
    kernel on-chip, portable Pallas kernel elsewhere) replaces the naive
    [T, T] path — O(block) VMEM instead of materializing scores in HBM."""
    B, Tq, H, Dh = q.shape
    Tk = k.shape[1]
    if use_flash is None:
        use_flash = (_device.on_tpu() and seq_offset == 0
                     and Tq == Tk and Tq >= 256 and Dh >= 64)
    if use_flash:
        from ..ops.pallas_kernels import flash_attention

        ctx = flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), True, 1.0 / math.sqrt(Dh))
        return ctx.transpose(0, 2, 1, 3)
    scale = 1.0 / math.sqrt(Dh)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    qpos = jnp.arange(Tq)[:, None] + seq_offset
    kpos = jnp.arange(Tk)[None, :]
    mask = qpos >= kpos
    logits = jnp.where(mask[None, None], logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attention_block(lp, h_full, dtype):
    """One attention sublayer on an already-gathered [B, T, D] input with
    tp-local head weights. Returns the *partial* output projection (caller
    reduces over tp)."""
    q, k, v = [
        jnp.einsum("btd,dhx->bthx", h_full, lp["wqkv"][:, i].astype(dtype))
        for i in range(3)
    ]
    ctx = causal_attention(q, k, v)
    return jnp.einsum("bthx,hxd->btd", ctx, lp["wo"].astype(dtype))


def ffn_block(lp, h_full, dtype):
    """Dense FFN with tp-local columns of w1 / rows of w2: returns partial
    sums for the caller to reduce."""
    a = jnp.einsum("btd,df->btf", h_full, lp["w1"].astype(dtype))
    a = jax.nn.gelu(a + lp["b1"].astype(dtype))
    return jnp.einsum("btf,fd->btd", a, lp["w2"].astype(dtype))


def embed_tokens(params, tokens, cfg):
    D = cfg.d_model
    h = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    h = h * math.sqrt(D)
    pos = params["pos_embed"][: tokens.shape[1]].astype(cfg.dtype)
    return h + pos[None]


def lm_logits(params, h, cfg):
    w = params.get("lm_head")
    if w is None:
        w = params["embed"].T
    return jnp.einsum("...d,dv->...v", h, w.astype(h.dtype),
                      preferred_element_type=jnp.float32)


def single_chip_hidden(params, tokens, cfg: TransformerConfig):
    """Embed -> layers under lax.scan (one compiled block body, optionally
    rematerialized) -> final LN. Shared by the forward (graft `entry()`)
    and the training loss so architecture changes cannot diverge."""
    h = embed_tokens(params, tokens, cfg)

    def body(h, lp):
        x = layer_norm(h, lp["ln1_scale"], lp["ln1_bias"])
        attn = attention_block(lp, x, cfg.dtype)
        h = h + attn
        x = layer_norm(h, lp["ln2_scale"], lp["ln2_bias"])
        h = h + ffn_block(lp, x, cfg.dtype)
        return h, None

    if cfg.remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, params["layers"])
    return layer_norm(h, params["final_ln_scale"], params["final_ln_bias"])


def single_chip_forward(params, tokens, cfg: TransformerConfig):
    """Plain (unsharded) forward — the graft `entry()` path and single-chip
    bench."""
    return lm_logits(params, single_chip_hidden(params, tokens, cfg), cfg)


def token_cross_entropy(logits, labels):
    """Mean CE over tokens; logits fp32 [B, T, V]."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, labels[..., None], axis=-1)
    return -jnp.mean(picked)


def single_chip_loss(params, tokens, labels, cfg):
    """Forward + chunked memory-lean CE head. The vocab head is computed
    per sequence chunk through the same custom-vjp CE the Fluid path uses
    (ops/loss_ops._hard_label_ce: residual = bf16 logits, backward
    recomputes the softmax elementwise behind a barrier) — the full-seq
    fp32 logits + log-softmax residual otherwise pin ~16G at batch 128,
    capping the batch below the MXU's preferred operating point."""
    from ..ops.loss_ops import _hard_label_ce

    h = single_chip_hidden(params, tokens, cfg)
    T = h.shape[1]
    # ~4 chunks caps the transient while keeping each vocab dot large
    # (over-chunking long sequences serializes many small dots)
    chunk = T if T <= 256 else max(256, T // 4)
    total = 0.0
    for s in range(0, T, chunk):
        logits = lm_logits(params, h[:, s:s + chunk], cfg)
        logits = logits.astype(cfg.dtype)
        total = total + _hard_label_ce(
            logits, labels[:, s:s + chunk], -100).sum()
    return total / (labels.shape[0] * labels.shape[1])


def param_count(params):
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
