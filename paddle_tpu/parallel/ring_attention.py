"""Ring attention — context/sequence parallelism for long sequences
(SURVEY §5.7: "the scale-sequence-length axis of the new framework is new
design work with no reference counterpart").

Each rank of the `axis` ring holds a sequence shard of Q, K, V
([B, H, T/n, D]). K/V blocks rotate around the ring with `ppermute` while
every rank accumulates its Q-shard's attention with the online-softmax
(flash) recurrence, so the full [T, T] score matrix never exists on any
chip and per-chip memory stays O(T/n). The rotation rides ICI neighbor
links; compute on block i overlaps the transfer of block i+1 (XLA schedules
the independent ppermute DMA concurrently with the matmuls).

Differentiable: the whole loop is a lax.scan of pure ops; reverse-mode
routes cotangents back through the reversed ring automatically.
"""

import functools

import jax
import jax.numpy as jnp

from jax.lax import axis_index as _axis_index

_NEG_INF = -1e30


def _block_attn(q, k, v, q_off, k_off, scale, causal):
    """Online-softmax partial update for one (Q-shard, KV-block) pair.
    q: [B, H, Tq, D], k/v: [B, H, Tk, D]. Returns (m, l, acc) deltas."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        Tq, Tk = q.shape[2], k.shape[2]
        qpos = q_off + jnp.arange(Tq)[:, None]
        kpos = k_off + jnp.arange(Tk)[None, :]
        s = jnp.where((qpos >= kpos)[None, None], s, _NEG_INF)
    m_blk = s.max(axis=-1)                                   # [B,H,Tq]
    p = jnp.exp(s - m_blk[..., None])
    # fully-masked rows (possible on far ring ranks): zero, don't count
    p = jnp.where(s > _NEG_INF / 2, p, 0.0)
    l_blk = p.sum(axis=-1)
    acc_blk = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return m_blk, l_blk, acc_blk


def ring_attention(q, k, v, axis_name, causal=True, sm_scale=None):
    """Attention over a sequence sharded on mesh axis `axis_name`.

    Call inside shard_map; q, k, v: [B, H, T_local, D] per-rank shards of a
    length-(n*T_local) sequence laid out contiguously by rank order.
    """
    n = jax.lax.psum(1, axis_name)
    rank = _axis_index(axis_name)
    B, H, Tl, D = q.shape
    scale = sm_scale if sm_scale is not None else D ** -0.5
    q_off = rank * Tl

    # accumulators must be device-varying over the ring axis for the scan
    # carry to type-check under shard_map (vma tracking)
    zero_like_q = jnp.zeros_like(q[..., 0], jnp.float32)
    m0 = zero_like_q + _NEG_INF
    l0 = zero_like_q
    acc0 = jnp.zeros_like(q, jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]  # rotate kv to next rank

    def step(carry, i):
        m, l, acc, kb, vb = carry
        # kv block currently held came from rank (rank - i) mod n
        k_off = ((rank - i) % n) * Tl
        m_blk, l_blk, acc_blk = _block_attn(q, kb, vb, q_off, k_off, scale,
                                            causal)
        m_new = jnp.maximum(m, m_blk)
        alpha = jnp.exp(m - m_new)
        beta = jnp.exp(m_blk - m_new)
        l = l * alpha + l_blk * beta
        acc = acc * alpha[..., None] + acc_blk * beta[..., None]
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        return (m_new, l, acc, kb, vb), None

    (m, l, acc, _, _), _ = jax.lax.scan(
        step, (m0, l0, acc0, k, v), jnp.arange(n))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name="sp", causal=True,
                           sm_scale=None, partial_manual=False):
    """Convenience wrapper: shard_map ring_attention over `mesh` with the
    sequence dimension of [B, H, T, D] partitioned on `axis_name`.

    partial_manual=True makes only `axis_name` manual (other mesh axes
    stay GSPMD-auto) — the form the descriptor-path flash_attention op
    uses inside a jitted step whose dp/tp axes GSPMD manages."""
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    spec = P(None, None, axis_name, None)
    kwargs = ({"axis_names": {axis_name}, "check_vma": False}
              if partial_manual else {})
    fn = shard_map(
        functools.partial(ring_attention, axis_name=axis_name,
                          causal=causal, sm_scale=sm_scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, **kwargs)
    return fn(q, k, v)
