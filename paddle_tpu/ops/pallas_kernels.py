"""Pallas TPU kernel library — the hot ops XLA doesn't fuse optimally
(SURVEY §7 design mapping: "hand-written Pallas kernels only where XLA
underperforms — attention/softmax fusions, top-k/DGC"; the reference
framework's per-op CUDA kernel corpus, re-grown TPU-native).

The kernels (each registered in ops/kernel_registry with its lax
fallback, shape qualification and platform policy — docs/KERNELS.md):

flash_attention: blocked causal attention with online softmax — the
  O(T) -memory replacement for the naive [T, T] score matrix. Forward is a
  Pallas kernel (grid over (batch*heads, q blocks, kv blocks), VMEM
  accumulators carried across the innermost kv dimension); backward is the
  standard recompute formulation via jax.custom_vjp, left to XLA fusion.

paged_attention: decode-side attention that reads the serving
  ``KVBlockPool`` pages THROUGH the block table (the block-sparse gather
  happens inside the kernel via scalar-prefetch BlockSpec index maps, the
  PagedAttention formulation) — the per-step contiguous
  ``kv[block_tables].reshape(...)`` gather the XLA path materializes
  disappears. It takes the pool whole and resolves the layer in the same
  index map, so nothing of a layer's size is sliced out for the call.
  It is the kernel of the speculative verify window (C=k+1,
  ``kernel 'spec_window'``) and, at heads narrower than a 128-lane
  tile, of the one-token decode window (C=1). Its grid is rows x table
  slots, taken whatever the rows hold.

paged_decode_attention: the one-token decode window (``kernel
  'paged_decode'``). At heads of whole lane tiles, as the served
  configurations have them, ONE GRID STEP A ROW: the pool stays in HBM
  and the row's own pages, up to its position's and no further, are
  copied in runs by manual DMA (the copies of ``paged_chunk_attention``);
  the row's heads are the query rows of one product over the run as it
  lies, ``[tokens * H, Dh]``. An inactive row costs nothing. At a
  narrower head it hands over to ``paged_attention``.

int8_matmul: fused int8×int8→int32 matmul for the full-int8 quant path —
  the activation quantizes IN-KERNEL (per-tensor scale), the dot
  accumulates int32 on the MXU int8 path, and the per-output-channel
  dequantize applies on the final K block, so the separate
  quantize/dequantize_linear HLOs around each rewritten matmul vanish.

gmm: the grouped (ragged) matmul of an expert layer. Rows arrive sorted
  by expert in a tile-aligned layout (every tile of ``block_m`` rows
  belongs to ONE expert, named by a scalar-prefetched table), so the
  kernel is a plain tile matmul whose weight block is found by the
  index map: an expert's matrix is streamed once for all its tiles, an
  expert with no row is never read, and tiles past the used count are
  skipped. bf16 in, fp32 accumulate.

latent_paged_attention: attention over a paged LATENT cache (MLA,
  absorbed form): every query head of a row attends the same stored row
  of ``width`` values a token (the normalised latent and the rotated
  shared key), and the value is that row's first ``v_width`` lanes. It
  takes the pool whole like ``paged_attention``, leaves it in HBM and
  copies a row's own pages itself, ``pages_per_step`` to a matmul, so
  a row of hundreds of cached tokens costs a few matmuls and no page
  it does not own; a row's last run starts the next live row's first
  (``_row_pipe``, the hand-over the grouped-query decode kernel shares),
  so the page pipe runs empty once a call, not once a row. One kernel
  serves the one-token decode step (``kernel 'latent_decode'``) and the
  chunk window (``kernel 'latent_window'``). ``latent_write`` puts a
  window's new rows into that pool in place, a page at a time.

paged_chunk_attention: the chunked-prefill window's attention over the
  same fp32 ``KVBlockPool`` (``kernel 'chunk_window'``). The window
  arrives cut into QUERY TILES (a decode row is a tile of one token, a
  prefilling row's chunk several tiles of ``Cq`` slots), each with its
  row's block-table line; the pool stays in HBM and a tile's own pages,
  up to the page of its last token and no further, are copied in runs
  by manual DMA, as ``latent_paged_attention`` does. Nothing of
  ``[B, C, H, T]`` exists. A tile of length 0 is skipped and moves
  nothing: the chunk step hands its one-token tiles to
  ``paged_decode_attention`` and marks them so here.

gqa_paged_decode_attention / gqa_paged_chunk_attention: the same two
  page walks for a GROUPED-QUERY block over a packed bfloat16 pool
  (``kernels 'gqa_decode'``, ``'gqa_chunk'``; one walk, mask and
  online softmax under two page pipes): fewer
  cache heads than query heads, a page ``[block_size, Hkv * Dh]`` whose
  lanes hold the cache heads side by side, the query heads of a group
  one operand against their cache head's keys. A layer states the
  WINDOW of positions it sees (a traced scalar: window and global
  layers share a lowering); the walk starts at the first page the
  tile's earliest query still sees. The decode kernel's pipe never
  runs empty inside a call: a run is sized by its bytes
  (``gqa_pages_per_run``) and a row's last run starts the next live
  row's first (``_row_pipe``). ``kv_page_write`` puts a step's new
  K and V rows into such a pool in place, a whole page a grid step.

kda_decode / kda_chunk: the SCAN of a linear-attention layer (the delta
  rule with a decay a channel; ``kernels 'kda_decode'``, ``'kda_chunk'``),
  whose state is a ``[dk, dv]`` float32 matrix a head that a batch row
  carries beside its pages (``kv_cache.RowState``). Both take every
  row's, layer's and head's matrices as ONE array, aliased to the
  result, with the layer a traced scalar. ``kda_decode`` is one token a
  row: the active rows' states of one layer read, decayed, updated by
  the rank-one delta and read out on the VPU, written back in place, an
  inactive row neither fetched nor written. ``kda_chunk`` is a step's
  prefill tokens in tiles of ``KDA_TILE``: the recurrence in closed form
  (the WY / UT transform), the decays factored over sub-chunks of
  ``KDA_SUB`` tokens so that no ``exp`` of a whole tile's decay is ever
  formed, a row's state carried in VMEM over its tiles.

Whether a kernel compiles or runs in the Pallas interpreter is decided in
one place, ``core.device.pallas_interpret()``: compiled on TPU (a kernel
Mosaic refuses raises), interpreted everywhere else so the CPU test mesh
exercises the same kernel bodies (tests/test_pallas.py).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.flash_attention import (
    BlockSizes as _TpuFlashBlockSizes, flash_attention as _tpu_flash)

from ..core import device as _device


__all__ = ["flash_attention", "flash_attention_portable",
           "attention_reference", "paged_attention",
           "paged_decode_attention",
           "paged_attention_reference", "paged_attention_tree",
           "paged_attention_tree_reference", "int8_matmul",
           "int8_matmul_reference", "gmm", "gmm_reference",
           "latent_paged_attention", "latent_paged_attention_reference",
           "latent_write", "latent_write_reference",
           "paged_chunk_attention", "paged_chunk_attention_reference",
           "gqa_paged_chunk_attention", "gqa_paged_decode_attention",
           "gqa_paged_attention_reference",
           "gqa_paged_decode_attention_reference", "kv_page_write",
           "kv_page_write_reference", "kda_decode", "kda_decode_reference",
           "kda_chunk", "kda_chunk_reference"]

_NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                sm_scale, causal, block_q, block_k, kv_len):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    # skip fully-masked kv blocks (strictly above the causal diagonal)
    run = True
    if causal:
        run = (ki * block_k) <= (qi * block_q + block_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        mask = k_pos < kv_len  # padded keys
        if causal:
            mask = mask & (k_pos <= q_pos)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[:, :1]                      # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)            # [bq, 1]
        l_new = l_scr[:, :1] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, :1]
        o_ref[0] = (acc_scr[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_attention(q, k, v, causal=True, sm_scale=None, block_q=128,
                    block_k=128):
    """Blocked attention (q, k, v: [B, H, T, D]). Single dispatch point:
    on a real TPU backend this routes to the jax library's TPU flash kernel
    (fully-blocked Pallas backward, no [T, T] residuals — measured ~20%
    faster in-model with seq-wide blocks than the 128 defaults); everywhere
    else (CPU mesh, interpret mode) it runs the portable in-repo kernel
    below, whose backward recomputes attention through XLA."""
    # library path only for the self-attention shape it was profiled on;
    # cross-attention (Tk != Tq) runs the portable kernel, whose kv_len
    # masking handles ragged kv blocks
    if _device.on_tpu() and q.shape == k.shape:
        T = q.shape[2]
        blk = next((b for b in (512, 256, 128) if T % b == 0 and b <= T),
                   None)
        if blk is not None:
            bs = _TpuFlashBlockSizes(
                block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
                block_q_major_dkv=blk, block_k_major_dkv=blk,
                block_k_dkv=blk, block_q_dkv=blk,
                block_k_major_dq=blk, block_k_dq=blk, block_q_dq=blk)
            if sm_scale is None:
                sm_scale = q.shape[-1] ** -0.5
            return _tpu_flash(q, k, v, causal=causal, sm_scale=sm_scale,
                              block_sizes=bs)
    return flash_attention_portable(q, k, v, causal, sm_scale, block_q,
                                    block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_portable(q, k, v, causal=True, sm_scale=None,
                             block_q=128, block_k=128):
    """The in-repo blocked kernel, O(block) VMEM (q, k, v: [B, H, T, D])."""
    return _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k)


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k):
    B, H, T, D = q.shape
    Tk = k.shape[2]
    if sm_scale is None:
        sm_scale = D ** -0.5
    qp = _pad_to(q.reshape(B * H, T, D), 1, block_q)
    kp = _pad_to(k.reshape(B * H, Tk, D), 1, block_k)
    vp = _pad_to(v.reshape(B * H, Tk, D), 1, block_k)
    Tq_p, Tk_p = qp.shape[1], kp.shape[1]
    grid = (B * H, Tq_p // block_q, Tk_p // block_k)

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal, block_q=block_q,
        block_k=block_k, kv_len=Tk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq_p, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=_device.pallas_interpret(),
        name="flash_attention",   # the kernel's row in a device trace
    )(qp, kp, vp)
    return out[:, :T].reshape(B, H, T, D)


def _flash_fwd_rule(q, k, v, causal, sm_scale, block_q, block_k):
    out = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k)
    return out, (q, k, v)


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, res, g):
    """Backward by recompute (standard flash-attention formulation); the
    [T, T] intermediate is rematerialized and XLA-fused, trading FLOPs for
    the HBM the naive backward would burn."""
    q, k, v = res
    D = q.shape[-1]
    scale = sm_scale if sm_scale is not None else D ** -0.5

    def attn(q32, k32, v32):
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, k32) * scale
        if causal:
            Tq, Tk = s.shape[-2], s.shape[-1]
            mask = jnp.tril(jnp.ones((Tq, Tk), bool), Tk - Tq)
            s = jnp.where(mask, s, _NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v32)

    f32 = jnp.float32
    _, vjp = jax.vjp(attn, q.astype(f32), k.astype(f32), v.astype(f32))
    dq, dk, dv = vjp(g.astype(f32))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention_portable.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def attention_reference(q, k, v, causal=True, sm_scale=None):
    """The unfused lax reference for flash_attention (q, k, v:
    [B, H, T, D]) — the registry fallback and the numerics oracle the
    kernel tests pin against."""
    D = q.shape[-1]
    scale = sm_scale if sm_scale is not None else D ** -0.5
    f32 = jnp.float32
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(f32), k.astype(f32)) * scale
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Tq, Tk), bool), Tk - Tq)
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(f32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# paged attention: decode / speculative verify windows over KVBlockPool
# pages, block tables resolved INSIDE the kernel (scalar-prefetch index
# maps — the PagedAttention formulation)
# ---------------------------------------------------------------------------


def _paged_attn_kernel(tables_ref, span_ref, layer_ref, q_ref, k_ref, v_ref,
                       vis_ref, o_ref, m_scr, l_scr, acc_scr, *, sm_scale,
                       block_size, n_heads, tree):
    """Grid (B, Mb); j (the block-table slot) is innermost, carrying
    the online-softmax state of every head across one row's pages. The
    k/v BlockSpec index maps already resolved the layer (``layer_ref``,
    read by the index maps only) and table slot j to its PHYSICAL page
    (null pages land here too — harmless, their logical positions are
    masked or the whole block is skipped).

    A block is one whole page as the pool stores it, ``[block_size, H,
    Dh]``: Mosaic tiles the last two axes of a block in (8, 128) units,
    so a block that took one head out of the second-to-last axis
    (``(1, bs, 1, Dh)``) is refused, while a whole page equals the
    array in both. A head's ``[block_size, Dh]`` rows are read out of
    the page with a static index on the head axis (a strided load);
    the query window and the output keep the heads in the lane axis.

    ``span_ref`` (scalar prefetch, ``[2, B]``) holds each row's first
    and last window CACHE position. ``vis_ref`` decides in-window
    visibility. Linear window: the ``[C, 1]`` cache position of each
    window slot, causal. ``tree``: the ``[C, C]`` float ancestor matrix
    — window slot c sits at cache position pos0+c, and a key at logical
    position t is visible to slot c iff t < pos0 (committed prefix,
    strict — slot 0's own write is window-visible via anc[0, 0], never
    prefix-visible) or t-pos0 is an ancestor of c. The ancestor lookup
    runs as a one-hot matmul against the ancestor matrix — no in-kernel
    gathers. pos0 comes from SMEM because Mosaic cannot broadcast a
    loaded [1, 1] vector along sublanes and lanes at once."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    C = q_ref.shape[1]
    Dh = q_ref.shape[2] // n_heads

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # pages wholly past the row's LAST query position hold nothing any
    # window slot may attend to — skip their compute (their table
    # entries are the null page anyway)
    @pl.when(j * block_size <= span_ref[1, b])
    def _body():
        q = q_ref[0].astype(jnp.float32)                # [C, H*Dh]
        # logical positions covered by table slot j
        t_pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (C, block_size), 1)
        if tree:
            rel = t_pos - span_ref[0, b]                # row-constant
            # anc[c, rel] via one-hot matmul: onehot[r, t] = (rel_t == r)
            onehot = (jax.lax.broadcasted_iota(
                jnp.int32, (C, block_size), 0) == rel).astype(jnp.float32)
            win_vis = jax.lax.dot_general(
                vis_ref[:], onehot, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32) > 0.0
            mask = (rel < 0) | win_vis                  # [C, bs]
        else:
            mask = t_pos <= vis_ref[0]                  # causal in-window

        for h in range(n_heads):
            lanes = slice(h * Dh, (h + 1) * Dh)
            k = k_ref[0, 0, :, h, :].astype(jnp.float32)    # [bs, Dh]
            v = v_ref[0, 0, :, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(
                q[:, lanes], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale  # [C, bs]
            s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_scr[h][:, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_scr[h][:, :1] * alpha + p.sum(axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    @pl.when(j == nj - 1)
    def _finish():
        o_ref[0] = jnp.concatenate(
            [acc_scr[h] / jnp.maximum(l_scr[h][:, :1], 1e-30)
             for h in range(n_heads)], axis=-1).astype(o_ref.dtype)


def _paged_call(k_pool, v_pool, q, block_tables, positions, anc, layer,
                sm_scale):
    B, C, H, Dh = q.shape
    bs = k_pool.shape[2]
    Mb = block_tables.shape[1]
    HD = H * Dh
    if sm_scale is None:
        sm_scale = Dh ** -0.5
    tree = anc is not None

    def row(b, j, tables, span, layer):
        return (b, 0, 0)

    # the pool goes in whole: a block is one page of one layer, found
    # by the index map, so nothing pool-sized is sliced or copied for
    # the call
    def page(b, j, tables, span, layer):
        return (layer[0], tables[b, j], 0, 0, 0)

    pos = jnp.maximum(positions, 0).astype(jnp.int32)    # [B, C]
    if tree:
        vis = jnp.asarray(anc, jnp.float32)
        vis_spec = pl.BlockSpec((C, C), lambda b, j, *prefetch: (0, 0))
    else:
        vis = pos[:, :, None]                            # [B, C, 1]
        vis_spec = pl.BlockSpec((1, C, 1), row)
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, sm_scale=sm_scale,
                          block_size=bs, n_heads=H, tree=tree),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, Mb),
            in_specs=[pl.BlockSpec((1, C, HD), row),
                      pl.BlockSpec((1, 1, bs, H, Dh), page),
                      pl.BlockSpec((1, 1, bs, H, Dh), page),
                      vis_spec],
            out_specs=pl.BlockSpec((1, C, HD), row),
            scratch_shapes=[
                pltpu.VMEM((H, C, 128), jnp.float32),
                pltpu.VMEM((H, C, 128), jnp.float32),
                pltpu.VMEM((H, C, Dh), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, C, HD), jnp.float32),
        interpret=_device.pallas_interpret(),
        # its row in a device trace; unnamed, the TPU compiler called
        # the call after the jitted function around it (`step`)
        name="paged_attention_tree" if tree else "paged_attention",
    )(block_tables.astype(jnp.int32),
      jnp.stack([pos[:, 0], pos[:, C - 1]]),             # [2, B] span
      jnp.asarray(layer, jnp.int32).reshape(1),
      q.reshape(B, C, HD), k_pool, v_pool, vis)
    return out.reshape(B, C, H, Dh)


def paged_attention(k_pool, v_pool, q, block_tables, positions, *, layer,
                    sm_scale=None):
    """Attention over a paged KV cache, block tables resolved in-kernel.

    k_pool/v_pool: ``[n_layers, num_blocks+1, block_size, H, Dh]`` — the
    ``KVBlockPool`` device arrays WHOLE, as they are stored (page 0 of
    every layer is the null page). ``layer`` picks the layer inside the
    kernel's index map. Do not hand it ``k_pool[layer]``: a custom call
    cannot read through a slice, so XLA would copy the layer's pages
    out of the pool first — half of a decode step before PR 25
    (docs/KERNELS.md).
    q: ``[B, C, H, Dh]`` query window (C=1 for plain decode, C=k+1 for
    the speculative verify window). block_tables: ``[B, Mb]`` int32 —
    table slot j holds the physical page covering logical positions
    ``[j*bs, (j+1)*bs)``; unallocated slots hold the null page.
    positions: ``[B, C]`` int32 — window slot c attends to logical
    positions ``t <= positions[b, c]`` (the row's k/v for the whole
    window are written before the call, exactly like the XLA path).

    Returns the ``[B, C, H, Dh]`` fp32 context. Numerics: online softmax
    (flash formulation) — token-identical to the gathered reference, not
    bitwise (docs/KERNELS.md)."""
    return _paged_call(k_pool, v_pool, q, block_tables, positions, None,
                       layer, sm_scale)


def _gathered_context(pool, layer, block_tables):
    """One layer's pages gathered through the block tables, as the
    serving model's lax path does it: ``[B, Mb, bs, H, Dh]`` ->
    ``[B, T, H, Dh]``."""
    B, Mb = block_tables.shape
    with jax.named_scope("kv_read"):
        return pool[layer][block_tables].reshape(
            (B, Mb * pool.shape[2]) + pool.shape[3:])


def paged_attention_reference(k_pool, v_pool, q, block_tables,
                              positions, *, layer, sm_scale=None):
    """The unfused lax fallback: contiguous gather through the block
    table, then masked softmax attention — element-for-element the
    serving model's historical XLA decode-attention path."""
    B, C, H, Dh = q.shape
    max_ctx = block_tables.shape[1] * k_pool.shape[2]
    if sm_scale is None:
        sm_scale = Dh ** -0.5
    k_ctx = _gathered_context(k_pool, layer, block_tables)
    v_ctx = _gathered_context(v_pool, layer, block_tables)
    scores = jnp.einsum("bchd,bthd->bcht", q, k_ctx) * sm_scale
    t_ids = jnp.arange(max_ctx)[None, None, :]
    valid = t_ids <= positions[:, :, None]
    scores = jnp.where(valid[:, :, None, :], scores, -jnp.inf)
    w = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return jnp.einsum("bcht,bthd->bchd", w, v_ctx)


# ---------------------------------------------------------------------------
# paged chunk attention: the chunked-prefill window over KVBlockPool pages,
# cut into query tiles; the pool stays in HBM, a tile's pages come by DMA
# ---------------------------------------------------------------------------

CHUNK_PAGES_PER_STEP = 8


def _run_copies(tables_ref, row, run, n_pages, layer, pools, bufs, sems,
                half, *, pages, block_size, start, first_page=None,
                unroll=1):
    """Start, or wait for, the copies of run ``run`` of ``row``'s pages
    (table slots ``run * pages`` on, counted from ``first_page`` where a
    walk does not start at slot 0, up to the walk's ``n_pages``) from
    ``pools`` in HBM (one latent pool, or K and V) into half ``half`` of
    each pool's two VMEM buffers ``bufs``: one DMA a page and pool, a
    run's copies on one semaphore a pool (``sems[pool, half]``). The one
    page walk of every kernel that copies a row's own pages. The scalar
    core issues a descriptor a page in the kernel's one instruction
    stream; ``unroll`` pages a turn of the loop (the rest a page a turn)
    takes the loop's own cost off small pages."""
    P, bs = pages, block_size
    count = jnp.minimum(P, n_pages - run * P)

    def page(p, carry):
        blk = tables_ref[row, (run * P if first_page is None
                               else first_page + run * P) + p]
        dst = pl.ds(pl.multiple_of(p * bs, bs), bs)
        for which, (pool, buf) in enumerate(zip(pools, bufs)):
            copy = pltpu.make_async_copy(
                pool.at[layer, blk], buf.at[half, dst],
                sems.at[which, half])
            copy.start() if start else copy.wait()
        return carry

    def group(i, carry):
        for j in range(unroll):
            page(i * unroll + j, carry)
        return carry
    whole = count // unroll
    jax.lax.fori_loop(0, whole, group, 0)
    jax.lax.fori_loop(whole * unroll, count, page, 0)


def _pages_in(run_bytes, pool, table_len):
    """The pages of ``pool`` (its shape and dtype: ``[layers, pages,
    block_size, ...]``) that make a run of ``run_bytes``: at least one,
    and no more than a block-table line holds."""
    page_bytes = math.prod(pool.shape[2:]) * jnp.dtype(pool.dtype).itemsize
    return int(max(1, min(run_bytes // page_bytes, int(table_len))))


def _row_pipe(copies, attend, row, n_runs, next_row, n_rows, hand_ref):
    """One LIVE row's walk through a page pipe that does not run empty
    between a call's first live row and its last: the row's runs in
    turn, the next in flight (``copies(row, run, half, start)`` starts
    or waits for a run's copies into a buffer half) while this one is
    attended (``attend(run, half)``), and the row's LAST run starts the
    first run of the next live row, ``next_row`` (``n_rows`` where none
    follows; rows that are not live may lie between), into the other
    half. ``hand_ref`` (SMEM ``[2]``) hands over which half that was
    and that it was done, so only the first live row of a call opens
    its own pipe and waits for it with nothing to attend; the kernel
    clears ``hand_ref[1]`` in its first grid step. A walk is at least
    one run, so every live row but the last hands over."""
    # the live row before, if there was one, has started this row's
    # first run and left word of the buffer half
    opened = hand_ref[1] == 1
    half0 = jnp.where(opened, hand_ref[0], 0)
    hand_ref[1] = 0
    follows = next_row < n_rows
    nxt = jnp.minimum(next_row, n_rows - 1)
    pl.when(jnp.logical_not(opened))(lambda: copies(row, 0, half0, True))

    def one_run(run, carry):
        half = (half0 + run) % 2

        @pl.when(run + 1 < n_runs)
        def _next():
            copies(row, run + 1, 1 - half, True)

        @pl.when((run + 1 == n_runs) & follows)
        def _next_row():
            hand_ref[0] = 1 - half
            hand_ref[1] = 1
            copies(nxt, 0, 1 - half, True)

        copies(row, run, half, False)
        attend(run, half)
        return carry

    jax.lax.fori_loop(0, n_runs, one_run, 0)


def _next_live(live):
    """For each row the next row after it with ``live`` set, or the row
    count where none follows."""
    n = live.shape[0]
    at = jnp.where(live, jnp.arange(n, dtype=jnp.int32), n)
    after = jax.lax.cummin(at, reverse=True)
    return jnp.concatenate([after[1:], jnp.full((1,), n, jnp.int32)])


def _chunk_attn_kernel(tables_ref, pos_ref, len_ref, layer_ref, _blk_ref,
                       q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, m_scr,
                       l_scr, acc_scr, *, sm_scale, block_size, pages,
                       n_heads):
    """Grid (tiles,): one query tile a grid step. Both pools stay in
    HBM; the pages of the tile's row, up to the page of the tile's LAST
    token and no further, are copied into one of two VMEM buffers in
    runs of ``pages`` (one DMA a page and pool, a run's copies on one
    semaphore a pool, the next run in flight while this one is
    attended). A run is one ``[pages * block_size, H, Dh]`` key block; a
    head's rows are read out of it with a static index on the head axis,
    as ``_paged_attn_kernel`` reads them out of a page. The online
    softmax of every head is carried over the tile's runs.

    Tile slot c holds the token at logical position ``pos + c`` and sees
    ``t <= pos + c``. Every live tile computes all ``Cq`` query rows:
    a tile of one token is right but costs a whole tile's arithmetic,
    and the chunk step sends those to ``paged_decode_attention``
    instead. Slots at or past the tile's length come out zero. An empty
    tile computes nothing and MOVES nothing: its query and output
    blocks are a live tile's (``_chunk_call``), so it must leave
    ``o_ref`` alone, and its own slots of the output are never
    written. Lines of a buffer past
    the tile's last page keep an earlier tile's values: finite (the
    buffers are zeroed once) and masked by position. The operands of
    both products are rounded to bfloat16, which is what a
    default-precision fp32 dot does on the chip; the softmax statistics
    and both accumulations are fp32."""
    t = pl.program_id(0)
    bs, P = block_size, pages
    span = P * bs
    Cq = q_ref.shape[1]
    Dh = q_ref.shape[2] // n_heads
    n_tok = len_ref[t]
    pos0 = pos_ref[t]
    n_pages = (pos0 + jnp.maximum(n_tok, 1) - 1) // bs + 1
    n_runs = (n_pages + P - 1) // P
    layer = layer_ref[0]

    @pl.when(t == 0)
    def _zero():
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def copies(run, half, start):
        _run_copies(tables_ref, t, run, n_pages, layer, (k_hbm, v_hbm),
                    (kbuf, vbuf), sems, half, pages=P, block_size=bs,
                    start=start)

    def attend(run, half):
        t_pos = run * span + jax.lax.broadcasted_iota(
            jnp.int32, (Cq, span), 1)
        mask = t_pos <= pos0 + jax.lax.broadcasted_iota(
            jnp.int32, (Cq, span), 0)
        for h in range(n_heads):
            q = q_ref[0, :, h * Dh:(h + 1) * Dh]            # [Cq, Dh] bf16
            k = kbuf[half, :, h, :].astype(jnp.bfloat16)    # [span, Dh]
            v = vbuf[half, :, h, :].astype(jnp.bfloat16)
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_scr[h, :, :1]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_scr[h, :, :1] * alpha + p.sum(axis=-1, keepdims=True)
            acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
                p.astype(jnp.bfloat16), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])

    def finish():
        live = jax.lax.broadcasted_iota(jnp.int32, (Cq, Dh), 0) < n_tok
        for h in range(n_heads):
            o_ref[0, :, h * Dh:(h + 1) * Dh] = jnp.where(
                live, acc_scr[h] / jnp.maximum(l_scr[h, :, :1], 1e-30), 0.0)

    @pl.when(n_tok > 0)
    def _tile():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        copies(0, 0, True)

        def one_run(run, carry):
            half = run % 2

            @pl.when(run + 1 < n_runs)
            def _next():
                copies(run + 1, 1 - half, True)

            copies(run, half, False)
            attend(run, half)
            return carry

        jax.lax.fori_loop(0, n_runs, one_run, 0)
        finish()


def paged_chunk_attention(k_pool, v_pool, q, block_tables, positions,
                          lengths, *, layer, sm_scale=None,
                          pages_per_step=CHUNK_PAGES_PER_STEP):
    """The chunked-prefill window's attention over the paged KV cache,
    the window cut into query tiles.

    k_pool/v_pool: ``[n_layers, num_blocks+1, block_size, H, Dh]``, the
    ``KVBlockPool`` arrays WHOLE (never ``k_pool[layer]``; see
    :func:`paged_attention`); they are left in HBM and ``layer`` and the
    block tables pick the pages the kernel copies. q: ``[N, Cq, H, Dh]``
    query tiles: tile n holds ``lengths[n]`` consecutive tokens of ONE
    sequence, slot c at logical position ``positions[n] + c``, and
    ``block_tables[n]`` (``[N, Mb]``) is that sequence's block-table
    line. A decode row is a tile of one token; a prefilling row's chunk
    is ``ceil(tokens / Cq)`` tiles. The window's K and V are written
    before the call, so slot c sees ``t <= positions[n] + c``: the
    committed prefix and the window's earlier tokens.

    Returns the ``[N, Cq, H, Dh]`` fp32 context, zero at slots at or
    past a LIVE tile's length. A tile of length 0 is skipped: nothing is
    computed, copied in or written for it, and its slots of the result
    hold whatever the buffer held, so the caller reads none of them (the
    chunk step sends its one-token tiles to ``paged_decode_attention``
    and marks them 0 here: 3 MB a skipped tile and layer that no longer
    move). Operands of both products rounded to bfloat16
    (a default-precision fp32 dot on the chip), online softmax in fp32:
    token-identical to the gathered reference, not bitwise."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    # the layer goes in as a traced scalar, under one jitted function:
    # a step calls this once a layer, and tracing and lowering the
    # kernel's body (every head unrolled) for each call cost 10 s of a
    # 24-layer step's first call, compile cache or not (a cached
    # program is found by its lowered text)
    return _chunk_call(k_pool, v_pool, q, block_tables, positions,
                       lengths, jnp.asarray(layer, jnp.int32),
                       sm_scale=float(sm_scale),
                       pages=int(min(pages_per_step,
                                     block_tables.shape[1])),
                       interpret=_device.pallas_interpret())


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "pages", "interpret"))
def _chunk_call(k_pool, v_pool, q, block_tables, positions, lengths, layer,
                *, sm_scale, pages, interpret):
    N, Cq, H, Dh = q.shape
    bs = k_pool.shape[2]

    def tile(n, _tables, _pos, _len, _layer, blk):
        return (blk[n], 0, 0)

    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    out = pl.pallas_call(
        functools.partial(_chunk_attn_kernel, sm_scale=sm_scale,
                          block_size=bs, pages=pages, n_heads=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(N,),
            in_specs=[pl.BlockSpec((1, Cq, H * Dh), tile), hbm, hbm],
            out_specs=pl.BlockSpec((1, Cq, H * Dh), tile),
            scratch_shapes=[
                pltpu.VMEM((2, pages * bs, H, Dh), k_pool.dtype),
                pltpu.VMEM((2, pages * bs, H, Dh), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((H, Cq, 128), jnp.float32),
                pltpu.VMEM((H, Cq, 128), jnp.float32),
                pltpu.VMEM((H, Cq, Dh), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((N, Cq, H * Dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="paged_chunk_attention",
    )(block_tables.astype(jnp.int32),
      jnp.maximum(positions, 0).astype(jnp.int32),
      lengths.astype(jnp.int32), layer.reshape(1), _tile_blocks(lengths),
      q.reshape(N, Cq, H * Dh).astype(jnp.bfloat16), k_pool, v_pool)
    return out.reshape(N, Cq, H, Dh)


def _tile_blocks(lengths):
    """The query and output block of each grid step of
    ``_chunk_attn_kernel``: a live tile's own; a skipped tile's is the
    last live tile's before it (the first live tile's where none came
    before), so the block index does not change over a skipped tile and
    Pallas copies nothing in or out for it."""
    n = jnp.arange(lengths.shape[0], dtype=jnp.int32)
    live = lengths > 0
    last = jax.lax.cummax(jnp.where(live, n, -1))
    return jnp.where(last < 0, jnp.argmax(live).astype(jnp.int32), last)


def paged_chunk_attention_reference(k_pool, v_pool, q, block_tables,
                                    positions, lengths, *, layer,
                                    sm_scale=None, pages_per_step=None):
    """The lax fallback over the same tiles: each tile's block-table
    line gathered, masked softmax (:func:`paged_attention_reference`
    with slot c at ``positions[n] + c``), zero past a tile's length."""
    Cq = q.shape[1]
    slots = jnp.arange(Cq, dtype=jnp.int32)[None, :]
    out = paged_attention_reference(
        k_pool, v_pool, q, block_tables,
        jnp.maximum(positions, 0)[:, None] + slots, layer=layer,
        sm_scale=sm_scale)
    return jnp.where((slots < lengths[:, None])[:, :, None, None], out, 0.0)


# ---------------------------------------------------------------------------
# paged decode attention: one token a row over KVBlockPool pages, one grid
# step a ROW; the pool stays in HBM, the row's own pages come by DMA and
# all its heads go through the MXU together
# ---------------------------------------------------------------------------

DECODE_PAGES_PER_STEP = 8


def _decode_attn_kernel(tables_ref, pos_ref, act_ref, layer_ref, q_ref,
                        k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, half_ref,
                        m_scr, l_scr, acc_scr, *, sm_scale, block_size,
                        pages):
    """Grid (B,): one row a grid step, whatever the table's length. Both
    pools stay in HBM; the row's pages ``0 .. pos // block_size`` are
    copied into one of two VMEM buffers in runs of ``pages``, as
    ``_chunk_attn_kernel`` copies a tile's (one DMA a page and pool, the
    next run in flight while this one is attended). A row's last run
    also starts the NEXT live row's first one, so a row does not open on
    an empty pipe (0.3 ms of a 12.8 ms step at 16 rows); ``half_ref``
    (SMEM) hands over which buffer it went to.

    A row has one query token and ``H`` heads, and ``H`` query rows are
    the sublane tile the MXU wants anyway: the run's keys are read as
    they lie, ``[tokens * H, Dh]`` (token-major, no strided head reads),
    every head's query meets every line in ONE product ``[H, tokens *
    H]``, and the mask keeps the entries whose line belongs to the
    query's own head (``col % H == row``) at a visible position. The
    same mask makes ``p @ v`` over all lines the per-head context. An
    inactive row computes nothing and comes out zero. Arithmetic as
    ``_chunk_attn_kernel``: operands of both products rounded to
    bfloat16, softmax statistics and accumulations fp32."""
    b = pl.program_id(0)
    last_row = pl.num_programs(0) - 1
    bs, P = block_size, pages
    H, Dh = q_ref.shape[1], q_ref.shape[2]
    layer = layer_ref[0]

    def pages_of(row):
        return pos_ref[row] // bs + 1

    n_pages = pages_of(b)
    n_runs = (n_pages + P - 1) // P

    @pl.when(b == 0)
    def _zero():
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def copies(row, run, half, start):
        _run_copies(tables_ref, row, run, pages_of(row), layer,
                    (k_hbm, v_hbm), (kbuf, vbuf), sems, half, pages=P,
                    block_size=bs, start=start)

    def attend(run, half):
        n = P * bs * H
        col = jax.lax.broadcasted_iota(jnp.int32, (H, n), 1)
        own = col % H == jax.lax.broadcasted_iota(jnp.int32, (H, n), 0)
        k = kbuf[half].reshape(n, Dh).astype(jnp.bfloat16)
        v = vbuf[half].reshape(n, Dh).astype(jnp.bfloat16)
        s = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale   # [H, n]
        s = jnp.where(own & (run * P * bs + col // H <= pos_ref[b]), s,
                      _NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, :1] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(jnp.bfloat16), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(act_ref[b] > 0)
    def _row():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        # the row before, if it was live, has started this row's first
        # run and left word of the buffer
        opened = (b > 0) & (act_ref[jnp.maximum(b - 1, 0)] > 0)
        half0 = jnp.where(opened, half_ref[0], 0)
        nxt = jnp.minimum(b + 1, last_row)
        follows = (b < last_row) & (act_ref[nxt] > 0)
        pl.when(jnp.logical_not(opened))(lambda: copies(b, 0, half0, True))

        def one_run(run, carry):
            half = (half0 + run) % 2

            @pl.when(run + 1 < n_runs)
            def _next():
                copies(b, run + 1, 1 - half, True)

            @pl.when((run + 1 == n_runs) & follows)
            def _next_row():
                half_ref[0] = 1 - half
                copies(nxt, 0, 1 - half, True)

            copies(b, run, half, False)
            attend(run, half)
            return carry

        jax.lax.fori_loop(0, n_runs, one_run, 0)
        o_ref[0] = acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)


def paged_decode_attention(k_pool, v_pool, q, block_tables, positions, *,
                           layer, sm_scale=None, active=None,
                           pages_per_step=DECODE_PAGES_PER_STEP):
    """One-token decode attention over the paged KV cache; the contract
    of :func:`paged_attention` at ``C == 1`` (the pools whole, ``layer``
    picked inside, q ``[B, 1, H, Dh]``, positions ``[B, 1]``), plus
    ``active`` (``[B]`` bool, all rows if None): an inactive row is
    skipped and comes out zero.

    Where the geometry allows (:func:`_chunk_qualify`: heads of whole
    lane tiles, as the served configurations have them) the kernel takes
    ONE GRID STEP A ROW and copies that row's pages, ``0 .. position //
    block_size``, from the pool in HBM itself: a step costs what its
    rows hold. ``paged_attention``'s BlockSpec grid takes ``B x Mb``
    grid steps whatever they hold (2,048 a layer at 16 rows of 128
    blocks: 7-12 ms of a decode step); it stays the kernel of narrower
    heads, which Mosaic cannot slice out of HBM, and of the verify
    windows. Operands of both products rounded to bfloat16 (a
    default-precision fp32 dot on the chip), online softmax in fp32:
    token-identical to the gathered reference, not bitwise."""
    B, C, H, Dh = q.shape
    bs = k_pool.shape[2]
    if C != 1 or not _chunk_qualify(head_dim=Dh, block_size=bs)[0]:
        return paged_attention(k_pool, v_pool, q, block_tables, positions,
                               layer=layer, sm_scale=sm_scale)
    if sm_scale is None:
        sm_scale = Dh ** -0.5
    if active is None:
        active = jnp.ones((B,), jnp.int32)
    # under one jitted function with the layer a traced scalar, as
    # `_chunk_call`: a step's 24 calls are traced and lowered once
    return _decode_call(k_pool, v_pool, q[:, 0], block_tables,
                        positions[:, 0], active,
                        jnp.asarray(layer, jnp.int32),
                        sm_scale=float(sm_scale),
                        pages=int(min(pages_per_step,
                                      block_tables.shape[1])),
                        interpret=_device.pallas_interpret())[:, None]


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "pages", "interpret"))
def _decode_call(k_pool, v_pool, q, block_tables, positions, active, layer,
                 *, sm_scale, pages, interpret):
    B, H, Dh = q.shape
    bs = k_pool.shape[2]

    def row(b, *_):
        return (b, 0, 0)

    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    return pl.pallas_call(
        functools.partial(_decode_attn_kernel, sm_scale=sm_scale,
                          block_size=bs, pages=pages),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, H, Dh), row), hbm, hbm],
            out_specs=pl.BlockSpec((1, H, Dh), row),
            scratch_shapes=[
                pltpu.VMEM((2, pages * bs, H, Dh), k_pool.dtype),
                pltpu.VMEM((2, pages * bs, H, Dh), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.VMEM((H, Dh), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="paged_decode_attention",
    )(block_tables.astype(jnp.int32),
      jnp.maximum(positions, 0).astype(jnp.int32),
      active.astype(jnp.int32), layer.reshape(1),
      q.astype(jnp.bfloat16), k_pool, v_pool)


# ---------------------------------------------------------------------------
# tree-mask spec window: the paged verify window generalized to a token
# TREE — visibility inside the window follows the ancestor matrix, not
# the linear causal diagonal (committed prefix stays fully visible)
# ---------------------------------------------------------------------------


def paged_attention_tree(k_pool, v_pool, q, block_tables, positions,
                         anc, *, layer, sm_scale=None):
    """Tree-mask verify window over the paged KV cache, one kernel.

    Same contract as :func:`paged_attention` (the whole stored pool and
    a ``layer``) except the window is a speculation TREE: positions:
    ``[B, C]`` int32, the CACHE position of
    each window slot (``positions[b, c] = pos0_b + c`` — level-order slot
    c writes cache position pos0+c regardless of its tree depth). anc:
    ``[C, C]`` — ``anc[c, t]`` truthy iff window slot t is c or an
    ancestor of c (passed as float so the kernel can resolve it as a
    one-hot matmul). A key at logical position t is visible to slot c
    iff ``t < pos0`` (committed prefix, STRICT) or ``anc[c, t-pos0]``.

    With the linear-chain ancestor matrix (lower-triangular ones) this
    is numerically identical to the linear spec window. Returns the
    ``[B, C, H, Dh]`` fp32 context; online-softmax numerics, token-
    identical (not bitwise) to the gathered reference."""
    return _paged_call(k_pool, v_pool, q, block_tables, positions, anc,
                       layer, sm_scale)


def paged_attention_tree_reference(k_pool, v_pool, q, block_tables,
                                   positions, anc, *, layer,
                                   sm_scale=None):
    """The unfused lax fallback: contiguous gather through the block
    table, tree-masked softmax — element-for-element the serving model's
    XLA tree-window attention branch."""
    B, C, H, Dh = q.shape
    max_ctx = block_tables.shape[1] * k_pool.shape[2]
    if sm_scale is None:
        sm_scale = Dh ** -0.5
    k_ctx = _gathered_context(k_pool, layer, block_tables)
    v_ctx = _gathered_context(v_pool, layer, block_tables)
    scores = jnp.einsum("bchd,bthd->bcht", q, k_ctx) * sm_scale
    anc_b = jnp.asarray(anc) > 0
    pos0 = positions[:, 0]                               # [B]
    t_ids = jnp.arange(max_ctx)[None, None, :]           # [1, 1, T]
    rel = t_ids - pos0[:, None, None]                    # [B, 1, T]
    in_win = (rel >= 0) & (rel < C)
    rel_c = jnp.clip(rel, 0, C - 1)
    anc_t = anc_b[jnp.arange(C)[None, :, None], rel_c]   # [B, C, T]
    valid = (rel < 0) | (in_win & anc_t)
    scores = jnp.where(valid[:, :, None, :], scores, -jnp.inf)
    w = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    return jnp.einsum("bcht,bthd->bchd", w, v_ctx)


# ---------------------------------------------------------------------------
# fused int8 matmul: in-kernel activation quantize, int8×int8→int32 MXU
# dot, per-output-channel dequantize on the last K block
# ---------------------------------------------------------------------------


def _int8_mm_kernel(x_ref, w_ref, s_ref, o_ref, acc_scr, *, act_scale):
    kk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kk == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # the quantize op's exact grid: round-half-even, clip, int8 (zero
    # padding quantizes to zero and contributes nothing to the dot)
    qa = jnp.clip(jnp.round(x_ref[:] * act_scale), -128, 127) \
        .astype(jnp.int8)
    acc_scr[:] += jax.lax.dot_general(
        qa, w_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(kk == nk - 1)
    def _finish():
        o_ref[:] = acc_scr[:].astype(jnp.float32) * s_ref[:]


def int8_matmul(x, w_int8, dq_scale, act_scale, block_m=32, block_k=128,
                block_n=128):
    """Fused full-int8 matmul: ``dequant(quant(x) @ w_int8)`` in one
    kernel. x: ``[M, K]`` fp32 activation; w_int8: ``[K, N]`` int8
    weight; dq_scale: ``[N]`` fp32 combined per-output-channel
    dequantize scale (``(w_scales/127) * (s_act/127)``); act_scale: the
    activation quantize scale (``127/s_act``). Returns ``[M, N]`` fp32.

    int32 accumulation is exact over any K split, so the result matches
    the unfused quantize→dot→dequantize_linear path bitwise up to the
    final fp32 scale multiply (docs/KERNELS.md numerics policy)."""
    M, K = x.shape
    N = w_int8.shape[1]

    xp = _pad_to(_pad_to(x, 0, block_m), 1, block_k)
    wp = _pad_to(_pad_to(w_int8, 0, block_k), 1, block_n)
    sp = _pad_to(jnp.asarray(dq_scale, jnp.float32).reshape(1, N), 1,
                 block_n)
    Mp, Kp = xp.shape
    Np = wp.shape[1]
    grid = (Mp // block_m, Np // block_n, Kp // block_k)

    out = pl.pallas_call(
        functools.partial(_int8_mm_kernel, act_scale=act_scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        interpret=_device.pallas_interpret(),
        name="int8_matmul",
    )(xp, wp, sp)
    return out[:M, :N]


def int8_matmul_reference(x, w_int8, dq_scale, act_scale):
    """The unfused lax fallback — bitwise the quantize →
    int8-dot(int32) → dequantize_linear op chain the quant_rewrite pass
    emits when the fused kernel is off."""
    qa = jnp.clip(jnp.round(x * act_scale), -128, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(qa, w_int8, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * jnp.asarray(dq_scale, jnp.float32)


# ---------------------------------------------------------------------------
# gmm: grouped matmul over experts, rows sorted by expert, tile-aligned
# ---------------------------------------------------------------------------

GMM_BLOCK_M = 16     # one bf16 sublane tile of rows


def _gmm_kernel(tile_expert_ref, n_used_ref, lhs_ref, rhs_ref, o_ref):
    """Grid (M // block_m,): tile i is rows of expert
    ``tile_expert[i]``, whose whole ``[K, N]`` matrix is the weight
    block (the index map found it; consecutive tiles of one expert
    keep the block, so it is read once). Tiles at or past ``n_used``
    hold no row: their table entry repeats the last used expert, so
    no weight is fetched for them, and their output rows are zero."""
    used = pl.program_id(0) < n_used_ref[0]

    @pl.when(used)
    def _tile():
        o_ref[:] = jax.lax.dot_general(
            lhs_ref[:], rhs_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(used))
    def _empty():
        o_ref[:] = jnp.zeros_like(o_ref)


def gmm(lhs, rhs, tile_expert, n_used, *, block_m=GMM_BLOCK_M):
    """Grouped matmul ``out[r] = lhs[r] @ rhs[expert_of_row(r)]``.

    lhs: ``[M, K]`` rows sorted by expert in a TILE-ALIGNED layout: M
    is a multiple of ``block_m`` and every tile of ``block_m`` rows
    belongs to one expert (a group's tail tile is padded with zero
    rows). rhs: ``[E, K, N]``, the held experts' matrices.
    tile_expert: ``[M // block_m]`` int32, the expert of each tile;
    entries at or past ``n_used`` repeat the last used expert.
    n_used: int32 scalar (or ``[1]``), how many tiles hold rows.

    Returns ``[M, N]`` float32; rows of tiles at or past ``n_used`` are
    zero. bf16 operands, fp32 accumulation."""
    M, K = lhs.shape
    E, K2, N = rhs.shape
    if K != K2 or M % block_m:
        raise ValueError("gmm: lhs %r does not tile against rhs %r at "
                         "block_m %d" % (lhs.shape, rhs.shape, block_m))
    return pl.pallas_call(
        _gmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(M // block_m,),
            in_specs=[
                pl.BlockSpec((block_m, K), lambda i, te, nu: (i, 0)),
                pl.BlockSpec((1, K, N), lambda i, te, nu: (te[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((block_m, N), lambda i, te, nu: (i, 0))),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_device.pallas_interpret(),
        name="gmm",
    )(tile_expert.astype(jnp.int32),
      jnp.asarray(n_used, jnp.int32).reshape(1), lhs, rhs)


def gmm_reference(lhs, rhs, tile_expert, n_used, *, block_m=GMM_BLOCK_M):
    """The lax fallback: ``jax.lax.ragged_dot`` over the same
    tile-aligned layout, each expert's group being its tiles. Rows
    past the used tiles are made zero, as the kernel leaves them (the
    chip's ragged_dot leaves rows past the last group unwritten)."""
    n_tiles = lhs.shape[0] // block_m
    n_used = jnp.asarray(n_used, jnp.int32).reshape(())
    used = jnp.arange(n_tiles) < n_used
    sizes = jnp.zeros((rhs.shape[0],), jnp.int32).at[
        tile_expert.astype(jnp.int32)].add(
            jnp.where(used, block_m, 0).astype(jnp.int32))
    # the precision stated: the operands are what they are (bf16 on the
    # chip), and under a caller's `default_matmul_precision("highest")`
    # the TPU's ragged_dot kernel refuses bf16 operands ("Bad lhs type")
    out = jax.lax.ragged_dot(lhs, rhs, sizes,
                             precision=jax.lax.Precision.DEFAULT,
                             preferred_element_type=jnp.float32)
    return jnp.where((jnp.arange(lhs.shape[0]) < n_used * block_m)[:, None],
                     out, 0.0)


# ---------------------------------------------------------------------------
# latent paged attention: MLA's absorbed form over a paged latent cache
# ---------------------------------------------------------------------------

LATENT_RUN_BYTES = 5 << 19        # a run of the attention kernel: 2.5 MiB
LATENT_COPIES_UNROLL = 8          # its page loop: descriptors a turn
LATENT_PRODUCT_SPANS = 4          # its products: the buffer, or a halving

def _latent_write_kernel(tables_ref, pos_ref, len_ref, layer_ref, rows_ref,
                         page_ref, o_ref, *, block_size, window):
    """Grid (B, pages a window can touch): the page (found by the index
    map, the null page where the window does not reach) is read, the
    window's rows that fall into it are put in place by selects on the
    page's row index, and the page is written back over itself."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    pos0 = pos_ref[b]
    first = (pos0 // block_size + j) * block_size     # page's first position
    shape = page_ref.shape[2:]
    t = first + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    rows = rows_ref[0].astype(jnp.float32)             # [C, W]
    page = page_ref[0, 0].astype(jnp.float32)
    for c in range(window):
        hit = (t == pos0 + c) & (c < len_ref[b])
        page = jnp.where(hit, jnp.broadcast_to(rows[c:c + 1, :], shape),
                         page)
    o_ref[0, 0] = page.astype(o_ref.dtype)


def latent_write(pool, rows, block_tables, positions, lengths, *, layer):
    """Write a window's cache rows into the paged latent pool in place.

    pool: ``[n_layers, num_blocks+1, block_size, width]`` WHOLE (aliased
    to the result); rows: ``[B, C, width]``, row b's window slot c goes
    to logical position ``positions[b] + c`` for ``c < lengths[b]`` (a
    row with length 0 writes nothing). The pool's dtype may pack two
    tokens into one sublane (bfloat16), where XLA's own scatter changes
    the whole pool's layout to update a row and copies it back for the
    attention kernel: twice the pool in HBM (docs/KERNELS.md). This
    kernel reads and rewrites whole pages instead."""
    B, C, W = rows.shape
    bs = pool.shape[2]
    Mb = block_tables.shape[1]
    n_pages = 1 if C == 1 else -(-(C - 1) // bs) + 1
    pos = jnp.maximum(positions, 0).astype(jnp.int32)
    lens = lengths.astype(jnp.int32)

    def page(b, j, tables, pos, lens, layer):
        slot = pos[b] // bs + j
        reached = (lens[b] > 0) & (slot * bs < pos[b] + lens[b]) \
            & (slot < Mb)
        return (layer[0],
                jnp.where(reached, tables[b, jnp.minimum(slot, Mb - 1)], 0),
                0, 0)

    return pl.pallas_call(
        functools.partial(_latent_write_kernel, block_size=bs, window=C),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B, n_pages),
            in_specs=[pl.BlockSpec((1, C, W), lambda b, j, *_: (b, 0, 0)),
                      pl.BlockSpec((1, 1, bs, W), page)],
            out_specs=pl.BlockSpec((1, 1, bs, W), page)),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        # operand 5 (after the four prefetched scalars and the rows) is
        # the pool: the result is the same buffer
        input_output_aliases={5: 0},
        interpret=_device.pallas_interpret(),
        name="latent_write",
    )(block_tables.astype(jnp.int32), pos, lens,
      jnp.asarray(layer, jnp.int32).reshape(1), rows.astype(pool.dtype),
      pool)


def latent_write_reference(pool, rows, block_tables, positions, lengths,
                           *, layer):
    """The lax fallback: one scatter; a slot past its row's length is
    dropped (an index past the pool)."""
    B, C, W = rows.shape
    bs = pool.shape[2]
    Mb = block_tables.shape[1]
    slots = jnp.arange(C, dtype=jnp.int32)[None, :]
    pos2d = jnp.maximum(positions, 0)[:, None] + slots
    valid = (slots < lengths[:, None]) & (pos2d < Mb * bs)
    blk = jnp.where(valid, jnp.take_along_axis(
        block_tables, jnp.clip(pos2d // bs, 0, Mb - 1), axis=1),
        pool.shape[1])
    return pool.at[layer, blk, pos2d % bs].set(rows.astype(pool.dtype),
                                               mode="drop")



def latent_pages_per_run(pool, table_len):
    """The pages of one run of ``latent_paged_attention``'s page pipe
    over ``pool`` (anything with the shape and dtype of a ``[layers,
    pages, block_size, width]`` latent pool): as many as make
    :data:`LATENT_RUN_BYTES` (a buffer half), at least one and no more
    than a block-table line holds. A run is sized by what it MOVES, as
    :func:`gqa_pages_per_run` sizes the grouped-query kernel's: it has
    a fixed cost (a turn of the loop, the waits, a rescale of the
    online softmax and two products), which 32 pages of 20 KB carried
    for a quarter of the bytes that 32 pages of 80 KB do. The kernel's
    call and the step log (``engine._decode_pipe_walked``) both ask
    here."""
    return _pages_in(LATENT_RUN_BYTES, pool, table_len)


def _run_wait(count, bufs, sems, half, *, pages, block_size):
    """Wait for the ``count`` page copies a pool that
    :func:`_run_copies` started into half ``half``, by SIZE: a DMA
    semaphore counts what has arrived, so one wait on a descriptor of
    ``k`` pages takes ``k`` page copies off it, and ``count`` (at most
    ``pages``) is waited for by its bits: at most ``log2(pages) + 1``
    waits a pool where a wait a page is ``count``."""
    k = 1 << (int(pages).bit_length() - 1)
    while k:
        def wait(k=k):
            for which, buf in enumerate(bufs):
                whole = buf.at[half, pl.ds(0, k * block_size)]
                pltpu.make_async_copy(whole, whole,
                                      sems.at[which, half]).wait()
        pl.when((count & k) != 0)(wait)
        k //= 2


def _latent_attend(q_ref, buf, half, run, pos0, nq, m_scr, l_scr, acc_scr,
                   *, n_heads, v_width, span):
    """The first ``span`` tokens of the run in buffer ``half`` (one
    ``[span, width]`` key block whose first ``v_width`` lanes are the
    value) against the row's first ``nq`` query rows: the online
    softmax's update of rows ``:nq`` of the statistics and the
    accumulator."""
    k = buf[half, :span]
    t0 = run * buf.shape[1]
    s = jax.lax.dot_general(
        q_ref[0, :nq, :], k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                 # [nq, span]
    t_pos = t0 + jax.lax.broadcasted_iota(jnp.int32, (nq, span), 1)
    slot = jax.lax.broadcasted_iota(jnp.int32, (nq, span), 0) // n_heads
    s = jnp.where(t_pos <= pos0 + slot, s, _NEG_INF)
    m_prev = m_scr[:nq, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_scr[:nq, :1] * alpha + p.sum(axis=-1, keepdims=True)
    acc_scr[:nq, :] = acc_scr[:nq, :] * alpha + jax.lax.dot_general(
        p.astype(k.dtype), k[:, :v_width], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[:nq, :] = jnp.broadcast_to(m_new, (nq, m_scr.shape[1]))
    l_scr[:nq, :] = jnp.broadcast_to(l_new, (nq, l_scr.shape[1]))


def _latent_attn_kernel(tables_ref, pos_ref, len_ref, layer_ref, next_ref,
                        q_ref, pool_ref, o_ref, buf, sems, hand_ref, m_scr,
                        l_scr, acc_scr, *, block_size, pages, window,
                        n_heads, v_width):
    """Grid (B,): one row a grid step. The pool stays in HBM; a LIVE
    row's own pages (``len_ref[b] > 0``), and no others, are copied into
    one of two VMEM buffers in runs of ``pages`` (one DMA a page, all of
    a run on one semaphore), each run being one ``[pages * block_size,
    width]`` key block whose first ``v_width`` lanes are the value. The
    page pipe is :func:`_row_pipe`'s: the next run in flight while this
    one is attended, and a row's LAST run starts the first run of the
    next live row, ``next_ref[b]`` (:func:`_next_live`), so only the
    first live row of a call opens on an empty pipe. The online-softmax
    state is carried over the row's runs. A row that is not live starts
    no copy, computes nothing and comes out zero.

    Query rows are ``[window * n_heads, width]``, slot-major: row r is
    head ``r % n_heads`` of window slot ``r // n_heads``, which sees
    logical positions ``t <= pos + slot``. A row whose window holds one
    token (every decode row of a mixed step) computes its first
    ``n_heads`` query rows only; it and the window rows go through the
    same grid and the same hand-over. Lines of a buffer past the row's
    last page keep an earlier row's values: finite (the buffers are
    zeroed once) and masked by position."""
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    bs, P = block_size, pages
    n_tok = len_ref[b]
    pos0 = pos_ref[b]
    layer = layer_ref[0]

    def pages_of(row):
        return (pos_ref[row] + len_ref[row] - 1) // bs + 1

    n_runs = (pages_of(b) + P - 1) // P

    @pl.when(b == 0)
    def _first():
        buf[...] = jnp.zeros_like(buf)
        hand_ref[1] = 0

    def held(row, run):
        """The pages run ``run`` of ``row`` holds."""
        return jnp.minimum(P, pages_of(row) - run * P)

    def copies(row, run, half, start):
        if start:
            _run_copies(tables_ref, row, run, pages_of(row), layer,
                        (pool_ref,), (buf,), sems, half, pages=P,
                        block_size=bs, start=True,
                        unroll=LATENT_COPIES_UNROLL)
        else:
            _run_wait(held(row, run), (buf,), sems, half, pages=P,
                      block_size=bs)

    # the products span the smallest of a few halvings of the buffer
    # that holds the run: a row's last run is rarely full
    spans = [P * bs]
    while len(spans) < LATENT_PRODUCT_SPANS and spans[-1] % (2 * bs) == 0:
        spans.append(spans[-1] // 2)

    def attend(run, half):
        tokens = held(b, run) * bs

        def rows(nq):
            for i, span in enumerate(spans):
                fits = tokens <= span
                if i + 1 < len(spans):
                    fits = fits & (tokens > spans[i + 1])
                pl.when(fits)(functools.partial(
                    _latent_attend, q_ref, buf, half, run, pos0, nq, m_scr,
                    l_scr, acc_scr, n_heads=n_heads, v_width=v_width,
                    span=span))
        if window == 1:
            rows(n_heads)
        else:
            pl.when(n_tok == 1)(lambda: rows(n_heads))
            pl.when(n_tok > 1)(lambda: rows(window * n_heads))

    @pl.when(n_tok <= 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_tok > 0)
    def _row():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        _row_pipe(copies, attend, b, n_runs, next_ref[b], n_rows, hand_ref)
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[:, :1], 1e-30)
                    ).astype(o_ref.dtype)


def latent_paged_attention(pool, q, block_tables, positions, lengths, *,
                           layer, v_width, pages_per_step=None):
    """Absorbed-form latent attention over a paged latent cache.

    pool: ``[n_layers, num_blocks+1, block_size, width]``, the latent
    ``KVBlockPool`` array WHOLE (one row a token a layer: the
    normalised latent, then the rotated shared key); it is left in HBM
    and ``layer`` and the block table pick the pages the kernel copies,
    in runs of :func:`latent_pages_per_run` pages where
    ``pages_per_step`` is not given.
    q: ``[B, C, H, width]`` queries in the cache's own space
    (``q_nope @ W_UK`` beside the rotated ``q_pe``), ALREADY scaled.
    positions: ``[B]`` int32, each row's first window position;
    lengths: ``[B]`` int32, tokens in its window (window slot c sees
    ``t <= positions[b] + c``; the window's own rows are written before
    the call). A row of length 0 is not live: none of its pages is
    read and it comes out zero. Slots at or past a live row's length
    are meaningless; for a row of one token they are not computed and
    come out zero.

    Returns the ``[B, C, H, v_width]`` fp32 context in latent space
    (``@ W_UV`` follows). The query is rounded to the pool's dtype for
    the MXU; softmax and both accumulations are fp32."""
    if pages_per_step is None:
        pages_per_step = latent_pages_per_run(pool, block_tables.shape[1])
    # under one jitted function with the layer a traced scalar, as
    # `_chunk_call`: a step calls this once a latent layer, and the
    # kernel's body (a product a span, a wait a bit) is traced and
    # lowered once, not once a layer
    return _latent_call(pool, q, block_tables, positions, lengths,
                        jnp.asarray(layer, jnp.int32), v_width=int(v_width),
                        pages=int(min(pages_per_step,
                                      block_tables.shape[1])),
                        interpret=_device.pallas_interpret())


@functools.partial(jax.jit,
                   static_argnames=("v_width", "pages", "interpret"))
def _latent_call(pool, q, block_tables, positions, lengths, layer, *,
                 v_width, pages, interpret):
    B, C, H, W = q.shape
    bs, P = pool.shape[2], pages
    lengths = lengths.astype(jnp.int32)

    def row(b, *_):
        return (b, 0, 0)

    out = pl.pallas_call(
        functools.partial(_latent_attn_kernel, block_size=bs, pages=P,
                          window=C, n_heads=H, v_width=v_width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, C * H, W), row),
                      pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)],
            out_specs=pl.BlockSpec((1, C * H, v_width), row),
            scratch_shapes=[
                pltpu.VMEM((2, P * bs, W), pool.dtype),
                pltpu.SemaphoreType.DMA((1, 2)),
                pltpu.SMEM((2,), jnp.int32),
                pltpu.VMEM((C * H, 128), jnp.float32),
                pltpu.VMEM((C * H, 128), jnp.float32),
                pltpu.VMEM((C * H, v_width), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((B, C * H, v_width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="latent_paged_attention",
    )(block_tables.astype(jnp.int32),
      jnp.maximum(positions, 0).astype(jnp.int32), lengths,
      layer.reshape(1), _next_live(lengths > 0),
      q.reshape(B, C * H, W).astype(pool.dtype), pool)
    return out.reshape(B, C, H, v_width)


def latent_paged_attention_reference(pool, q, block_tables, positions,
                                     lengths, *, layer, v_width,
                                     pages_per_step=None):
    """The lax fallback: the layer's pages gathered through the block
    table, then the same absorbed attention, masked softmax in fp32."""
    B, C, H, W = q.shape
    # the kernel's roundings (query and weights to the pool's dtype),
    # the products themselves in float32
    ctx = _gathered_context(pool, layer, block_tables) \
        .astype(jnp.float32)                               # [B, T, W]
    T = ctx.shape[1]
    scores = jnp.einsum("bchw,btw->bcht",
                        q.astype(pool.dtype).astype(jnp.float32), ctx)
    q_pos = (jnp.maximum(positions, 0)[:, None]
             + jnp.arange(C, dtype=jnp.int32)[None, :])    # [B, C]
    valid = jnp.arange(T)[None, None, :] <= q_pos[:, :, None]
    scores = jnp.where(valid[:, :, None, :], scores, -jnp.inf)
    w = jnp.exp(scores - jnp.max(scores, axis=-1, keepdims=True))
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    out = jnp.einsum("bcht,btv->bchv",
                     w.astype(pool.dtype).astype(jnp.float32),
                     ctx[..., :v_width])
    # as the kernel: a one-token row computes its first slot only, and
    # a row that is not live (no token) nothing
    slots = jnp.arange(C, dtype=jnp.int32)[None, :]
    skipped = (lengths <= 1)[:, None] & (slots >= lengths[:, None])
    return jnp.where(skipped[:, :, None, None], 0.0, out)


# ---------------------------------------------------------------------------
# KDA: the delta rule with a per-channel decay (linear attention), whose
# state a batch row carries beside its pages (kv_cache.RowState)
# ---------------------------------------------------------------------------
#
#   S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,  o_t = S_t^T q_t
#
# a head's S is ``[dk, dv]`` float32; the steps keep every row's, every
# layer's and every head's in ONE array ``[B, L, H, dk, dv]`` that both
# kernels take whole and update in place (the layer a traced scalar).

KDA_TILE = 64        # tokens a grid step of kda_chunk carries the state over
KDA_SUB = 16         # tokens whose decays are factored against one reference
_HIGHEST = jax.lax.Precision.HIGHEST
# the most half a sub-chunk's log-decay can be: KDA_SUB / 2 tokens at the
# gate's lower bound of -5 a token
_KDA_HALF_SPAN = 40.0


def _kda_decode_kernel(src_ref, act_ref, layer_ref, n_act_ref, cols_ref,
                       rows_ref, s_ref, so_ref, o_ref, *, n_heads):
    """Grid (B,): one batch row a grid step, its layer's ``[H, dk, dv]``
    states one block (found by the index map; an INACTIVE row's index
    repeats its active neighbour's, so nothing is fetched or written
    back for it). ``cols [dk, 3H..]`` holds the row's decay, key and
    query a head as COLUMNS (``dk`` on sublanes, as the state's rows
    are), ``rows [2H, dv]`` its value and beta as lane rows."""
    b = pl.program_id(0)
    H = n_heads

    @pl.when(act_ref[b] > 0)
    def _row():
        cols = cols_ref[0]
        for h in range(H):
            s = s_ref[0, 0, h].astype(jnp.float32)         # [dk, dv]
            kc = cols[:, H + h:H + h + 1]
            s = cols[:, h:h + 1] * s                       # the decay
            u = rows_ref[0, H + h:H + h + 1, :] * (
                rows_ref[0, h:h + 1, :]
                - jnp.sum(kc * s, axis=0, keepdims=True))  # [1, dv]
            s = s + kc * u                                 # the delta rule
            so_ref[0, 0, h] = s.astype(so_ref.dtype)
            s = so_ref[0, 0, h].astype(jnp.float32)        # as stored
            o_ref[0, h:h + 1, :] = jnp.sum(
                cols[:, 2 * H + h:2 * H + h + 1] * s, axis=0, keepdims=True)

    @pl.when(act_ref[b] == 0)
    def _idle():
        o_ref[...] = jnp.zeros_like(o_ref)

    # no row active: the one block every step names is handed back as read
    @pl.when((b == 0) & (n_act_ref[0] == 0))
    def _untouched():
        so_ref[...] = s_ref[...]


def kda_decode(state, q, k, v, alpha, beta, active, *, layer):
    """One token a row through the delta rule, the rows' states updated
    IN PLACE (``state`` is aliased to the first result).

    state: ``[B, L, H, dk, dv]`` float32 WHOLE; ``layer`` (an int or a
    traced scalar) picks ``state[:, layer]``. q, k, alpha: ``[B, H,
    dk]`` (the query scaled, ``alpha = exp(g)`` the decay a channel, 0
    where a row starts its sequence: its stored state is then ignored),
    v: ``[B, H, dv]``, beta: ``[B, H]``, active: ``[B]`` bool. A row that
    is not active is skipped: its state is neither read nor written and
    its output is zero.

    Returns ``(state', o [B, H, dv] float32)``. Memory-bound: every
    active row's ``H * dk * dv`` floats are read and written once."""
    B, L, H, dk, dv = state.shape
    f32 = jnp.float32
    lanes = -(-3 * H // 128) * 128
    cols = jnp.concatenate([alpha.astype(f32), k.astype(f32),
                            q.astype(f32)], axis=1)        # [B, 3H, dk]
    cols = jnp.pad(jnp.swapaxes(cols, 1, 2),
                   ((0, 0), (0, 0), (0, lanes - 3 * H)))   # [B, dk, lanes]
    rows = jnp.concatenate(
        [v.astype(f32), jnp.broadcast_to(beta.astype(f32)[:, :, None],
                                         (B, H, dv))], axis=1)
    on = active.astype(jnp.int32)
    idx = jnp.arange(B, dtype=jnp.int32)
    before = jax.lax.cummax(jnp.where(active, idx, -1))
    src = jnp.where(before >= 0, before, jnp.argmax(active).astype(jnp.int32))

    def of_row(b, src, on, layer, n):
        return (src[b], layer[0], 0, 0, 0)

    blk = pl.BlockSpec((1, 1, H, dk, dv), of_row)
    new_state, o = pl.pallas_call(
        functools.partial(_kda_decode_kernel, n_heads=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            in_specs=[pl.BlockSpec((1, dk, lanes), lambda b, *_: (b, 0, 0)),
                      pl.BlockSpec((1, 2 * H, dv), lambda b, *_: (b, 0, 0)),
                      blk],
            out_specs=[blk,
                       pl.BlockSpec((1, H, dv), lambda b, *_: (b, 0, 0))]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, H, dv), f32)],
        # operand 6 (after the four prefetched scalars, the columns and
        # the rows) is the state: the first result is the same buffer
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=_device.pallas_interpret(),
        name="kda_decode",
    )(src, on, jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.sum(on).reshape(1), cols, rows, state)
    return new_state, o


def kda_decode_reference(state, q, k, v, alpha, beta, active, *, layer):
    """The lax fallback: the same step on ``state[:, layer]`` whole."""
    f32 = jnp.float32
    s0 = jnp.take(state, layer, axis=1).astype(f32)        # [B, H, dk, dv]
    s = alpha.astype(f32)[..., None] * s0
    u = beta.astype(f32)[..., None] * (v.astype(f32) - jnp.einsum(
        "bhk,bhkv->bhv", k.astype(f32), s, precision=_HIGHEST))
    s = s + k.astype(f32)[..., None] * u[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", q.astype(f32), s, precision=_HIGHEST)
    on = active[:, None, None]
    s = jnp.where(on[..., None], s, s0).astype(state.dtype)
    new_state = jax.lax.dynamic_update_index_in_dim(
        state, s[:, None], jnp.asarray(layer, jnp.int32), axis=1)
    return new_state, jnp.where(on, o, 0.0)


def _kda_chunk_kernel(start_ref, len_ref, row_ref, load_ref, store_ref,
                      layer_ref, q_hbm, k_hbm, kb_hbm, vb_hbm, g_hbm, s_hbm,
                      so_hbm, o_hbm, qbuf, kbuf, kbbuf, vbbuf, gbuf, obuf,
                      sbuf, sems, *stage, tile, sub, n_heads, group):
    """Grid (tiles,), in order: a tile is up to ``tile`` consecutive
    tokens of ONE batch row, a row's tiles following one another. The
    row's ``[H, dk, dv]`` states live in ``sbuf`` from its first tile
    (copied from HBM, or zeroed where the row starts its sequence) to
    its last (copied back). The tokens' operands lie in HBM head-group
    major, ``[H / group, tokens * group, d]``, so that a tile's window of
    any start is a whole number of (8, 128) tiles; a head's tokens are
    every ``group``-th line of the window.

    Inside a tile the recurrence is solved in closed form (the WY / UT
    transform): with ``G`` the running sum of the log-decays, ``A[t, s]
    = sum_c kb_t k_s exp(G_t - G_s)`` (s < t) and ``B`` the same of q (s
    <= t), ``U = (I + A)^-1 (vb - (kb exp G) S0)``, ``O = (q exp G) S0 +
    B U``, ``S' = Diag(exp G_end) S0 + (k exp(G_end - G))^T U``. The
    decays never meet as ``exp(G_t) * exp(-G_s)``: a row block of ``sub``
    tokens is taken against the sum at ITS MIDDLE token, so each factor
    is ``exp`` of at most ``sub / 2`` tokens' decay either way (e^40 and
    e^-40 at the gate's bound of -5: neither an overflow nor a flushed
    denormal, and their product, of entries above the diagonal that the
    mask drops, still a float32) and of anything further down."""
    n = pl.program_id(0)
    C, H, hg = tile, n_heads, group
    n_tok = len_ref[n]
    f32 = jnp.float32

    @pl.when(n_tok > 0)
    def _tile():
        layer, row = layer_ref[0], row_ref[n]
        first = pl.multiple_of(start_ref[n] * hg, hg)
        window = pl.ds(first, C * hg)
        copies = [pltpu.make_async_copy(hbm.at[:, window], buf, sems.at[i])
                  for i, (hbm, buf) in enumerate(
                      ((q_hbm, qbuf), (k_hbm, kbuf), (kb_hbm, kbbuf),
                       (vb_hbm, vbbuf), (g_hbm, gbuf)))]
        for c in copies:
            c.start()
        # a state stored narrower than float32 passes through a buffer
        # of its own type (a DMA converts nothing)
        held = stage[0] if stage else sbuf
        state_in = pltpu.make_async_copy(s_hbm.at[row, layer], held,
                                         sems.at[5])

        @pl.when(load_ref[n] == 1)
        def _load():
            state_in.start()
            state_in.wait()
            if stage:
                sbuf[...] = held[...].astype(f32)

        @pl.when(load_ref[n] == 2)
        def _fresh():
            sbuf[...] = jnp.zeros_like(sbuf)

        for c in copies:
            c.wait()

        t_col = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
        live = t_col < n_tok
        tt = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
        ss = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
        tri = (ss <= tt).astype(f32)
        eye = (ss == tt).astype(f32)
        same = (ss // sub) == (tt // sub)
        dk = sbuf.shape[1]
        on_diag = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
                   == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1))

        def mm(a, b, dims=(((1,), (0,)), ((), ()))):
            return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                                       preferred_element_type=f32)

        def nt(a, b):                       # a @ b^T
            return mm(a, b, (((1,), (1,)), ((), ())))

        def head(gi, hh):
            lines = pl.ds(hh, C, stride=hg)
            q = jnp.where(live, qbuf[gi, lines, :], 0.0)
            k = jnp.where(live, kbuf[gi, lines, :], 0.0)
            kb = jnp.where(live, kbbuf[gi, lines, :], 0.0)
            vb = jnp.where(live, vbbuf[gi, lines, :], 0.0)
            g = jnp.where(live, gbuf[gi, lines, :], 0.0)
            h = gi * hg + hh
            s0 = sbuf[h]                                   # [dk, dv]
            G = mm(tri, g)                                 # running sums
            a_rows, b_rows = [], []
            for i in range(C // sub):
                r0 = i * sub
                mid = r0 + sub // 2
                ref = G[mid - 1:mid, :]
                down = jnp.exp(G[r0:r0 + sub] - ref)
                up = k * jnp.exp(jnp.minimum(ref - G, _KDA_HALF_SPAN))
                ab = nt(jnp.concatenate(
                    [kb[r0:r0 + sub] * down, q[r0:r0 + sub] * down]), up)
                a_rows.append(ab[:sub])
                b_rows.append(ab[sub:])
            A = jnp.where(ss < tt, jnp.concatenate(a_rows), 0.0)
            Bm = jnp.where(ss <= tt, jnp.concatenate(b_rows), 0.0)
            eg = jnp.exp(G)
            ks = mm(jnp.concatenate([kb * eg, q * eg]), s0)
            W, O = vb - ks[:C], ks[C:]
            # (I + A)^-1: the diagonal blocks by the nilpotent product
            # (N^sub = 0), the blocks below them by (I + M)^-1 with
            # M^(C / sub) = 0
            N = -jnp.where(same, A, 0.0)
            T = eye + N
            power = N
            for _ in range(max(sub - 1, 1).bit_length() - 1):
                power = mm(power, power)
                T = T + mm(T, power)
            X = mm(T, W)
            M = mm(T, jnp.where(same, 0.0, A))
            U = X
            power, sign = M, -1.0
            for _ in range(C // sub - 1):
                U = U + sign * mm(power, X)
                power, sign = mm(power, M), -sign
            O = O + mm(Bm, U)
            g_end = G[C - 1:C, :]
            khat = k * jnp.exp(g_end - G)
            # Diag(exp G_end) S0 as a product: the decay is a lane row
            # and the state's rows are the channels
            decay = jnp.where(on_diag, jnp.exp(g_end), 0.0)
            sbuf[h] = mm(decay, s0) + mm(khat, U, (((0,), (0,)), ((), ())))
            obuf[gi, lines, :] = O

        def groups(gi, carry):
            for hh in range(hg):
                head(gi, hh)
            return carry

        jax.lax.fori_loop(0, H // hg, groups, 0)
        out = pltpu.make_async_copy(obuf, o_hbm.at[:, window], sems.at[6])
        out.start()
        state_out = pltpu.make_async_copy(held, so_hbm.at[row, layer],
                                          sems.at[5])

        @pl.when(store_ref[n] == 1)
        def _store():
            if stage:
                held[...] = sbuf[...].astype(held.dtype)
            state_out.start()
            state_out.wait()

        out.wait()


def kda_chunk(state, q, k, v, g, beta, tile_start, tile_len, tile_row,
              tile_load, tile_store, *, layer, tile=KDA_TILE, sub=KDA_SUB):
    """A step's prefill tokens through the delta rule in TILES, each
    row's state read from ``state`` at its first tile, carried over its
    tiles and written back IN PLACE after its last (``state`` is aliased
    to the first result).

    state: ``[B, L, H, dk, dv]`` float32 WHOLE; ``layer`` picks
    ``state[:, layer]``. q, k, g: ``[T, H, dk]`` token rows (the query
    scaled, ``g`` the LOG of the decay a channel, <= 0), v: ``[T, H,
    dv]``, beta: ``[T, H]``; a batch row's tokens are consecutive rows.
    Tile ``n`` is the ``tile_len[n] <= tile`` token rows from
    ``tile_start[n]`` of batch row ``tile_row[n]`` (0: no tile, or one
    the caller computes otherwise: nothing is read or written);
    ``tile_load[n]``: 1 the tile starts from the row's stored state, 2
    from zero (the row starts its sequence), 0 from where the tile
    before left it (the same row's); ``tile_store[n]``: 1 the row's
    state is written back after it. Tiles come in token order, a row's
    one after another.

    Returns ``(state', o [T, H, dv] float32)``; token rows of no tile
    come out unspecified."""
    B, L, H, dk, dv = state.shape
    T = q.shape[0]
    hg = 8 if H % 8 == 0 else 1
    f32 = jnp.float32
    n_tiles = tile_start.shape[0]

    def lines(a):
        """``[T, H, d]`` -> ``[H / hg, (T + tile) * hg, d]``."""
        d = a.shape[-1]
        a = jnp.pad(a.astype(f32), ((0, tile), (0, 0), (0, 0)))
        return a.reshape(T + tile, H // hg, hg, d).transpose(1, 0, 2, 3) \
            .reshape(H // hg, (T + tile) * hg, d)

    b32 = beta.astype(f32)[..., None]
    operands = [lines(q), lines(k), lines(k.astype(f32) * b32),
                lines(v.astype(f32) * b32), lines(g)]
    anywhere = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    token_buf = pltpu.VMEM((H // hg, tile * hg, dk), f32)
    value_buf = pltpu.VMEM((H // hg, tile * hg, dv), f32)
    new_state, o = pl.pallas_call(
        functools.partial(_kda_chunk_kernel, tile=tile, sub=sub, n_heads=H,
                          group=hg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(n_tiles,),
            in_specs=[anywhere] * 6,
            out_specs=[anywhere, anywhere],
            scratch_shapes=[token_buf, token_buf, token_buf, value_buf,
                            token_buf, value_buf,
                            pltpu.VMEM((H, dk, dv), f32),
                            pltpu.SemaphoreType.DMA((7,))]
            + ([] if state.dtype == f32
               else [pltpu.VMEM((H, dk, dv), state.dtype)])),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((H // hg, (T + tile) * hg, dv),
                                        f32)],
        # operand 11 (after the six prefetched scalars and the five token
        # operands) is the state: the first result is the same buffer
        input_output_aliases={11: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=96 * 1024 * 1024),
        interpret=_device.pallas_interpret(),
        name="kda_chunk",
    )(tile_start.astype(jnp.int32), tile_len.astype(jnp.int32),
      tile_row.astype(jnp.int32), tile_load.astype(jnp.int32),
      tile_store.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *operands, state)
    o = o.reshape(H // hg, T + tile, hg, dv).transpose(1, 0, 2, 3)
    return new_state, o.reshape(T + tile, H, dv)[:T]


def kda_chunk_reference(state, q, k, v, g, beta, tile_start, tile_len,
                        tile_row, tile_load, tile_store, *, layer,
                        tile=KDA_TILE, sub=KDA_SUB):
    """The lax fallback: the recurrence itself, a token at a time, over
    the same tiles in the same order (nothing of the closed form)."""
    f32 = jnp.float32
    B, L, H, dk, dv = state.shape
    T = q.shape[0]
    layer = jnp.asarray(layer, jnp.int32)

    def token(carry, t):
        s, o, start, n_tok = carry
        at = jnp.minimum(start + t, T - 1)
        live = t < n_tok
        kt, qt = k[at].astype(f32), q[at].astype(f32)
        s1 = jnp.exp(g[at].astype(f32))[..., None] * s
        u = beta[at].astype(f32)[:, None] * (v[at].astype(f32) - jnp.einsum(
            "hk,hkv->hv", kt, s1, precision=_HIGHEST))
        s1 = s1 + kt[..., None] * u[:, None, :]
        ot = jnp.einsum("hk,hkv->hv", qt, s1, precision=_HIGHEST)
        o = jnp.where(live, o.at[at].set(ot), o)
        return (jnp.where(live, s1, s), o, start, n_tok), None

    def one_tile(n, carry):
        state, s, o = carry
        row = tile_row[n]
        stored = jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(state, row, 0, keepdims=False),
            layer, 0, keepdims=False).astype(f32)
        s = jnp.where(tile_load[n] == 1, stored,
                      jnp.where(tile_load[n] == 2, 0.0, s))
        (s, o, _, _), _ = jax.lax.scan(
            token, (s, o, tile_start[n], tile_len[n]), jnp.arange(tile))
        write = (tile_store[n] == 1) & (tile_len[n] > 0)
        state = jnp.where(write, jax.lax.dynamic_update_slice(
            state, s.astype(state.dtype)[None, None],
            (row, layer, 0, 0, 0)), state)
        return state, s, o

    state, _s, o = jax.lax.fori_loop(
        0, tile_start.shape[0], one_tile,
        (state, jnp.zeros((H, dk, dv), f32), jnp.zeros((T, H, dv), f32)))
    return state, o


# ---------------------------------------------------------------------------
# registry entries (ops/kernel_registry — docs/KERNELS.md qualification
# table; importing this module is what populates the registry)
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# grouped-query paged attention (a window of positions or all of them) and
# the in-place page write of a packed bf16 K/V pool
# ---------------------------------------------------------------------------

GQA_PAGES_PER_STEP = 4            # the chunk kernel's run
GQA_RUN_BYTES = 1 << 20           # the decode kernel's run, a pool
GQA_ALL_POSITIONS = 2 ** 30       # a `window` no context reaches


def gqa_pages_per_run(pool, table_len):
    """The pages of one run of ``gqa_paged_decode_attention`` over
    ``pool`` (anything with the shape and dtype of a ``[layers, pages,
    block_size, Hkv * Dh]`` pool): as many as make
    :data:`GQA_RUN_BYTES` (a buffer half), at least one and no more than
    a block-table line holds. A run is sized by what it MOVES: it has a
    fixed cost (a turn of the loop, a rescale of the online softmax, two
    small products a cache head), and eight pages of 32 KiB carried it
    for a quarter of the bytes that eight pages of 128 KiB do. The
    kernel and the step log (``engine._decode_pipe_walked``) both ask
    here."""
    return _pages_in(GQA_RUN_BYTES, pool, table_len)


def _gqa_head_run(q, kbuf, vbuf, half, g, mask, m_scr, l_scr, acc_scr, *,
                  sm_scale, head_dim):
    """Cache head ``g``'s keys and values of the run in buffer ``half``
    against its ``R`` stacked query rows ``q``: the online softmax's
    update of rows ``:R`` of the head's statistics and accumulator."""
    R, Dh = q.shape[0], head_dim
    k = kbuf[half, :, g * Dh:(g + 1) * Dh]         # [span, Dh]
    v = vbuf[half, :, g * Dh:(g + 1) * Dh]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sm_scale
    s = jnp.where(mask, s, _NEG_INF)
    m_prev = m_scr[g, :R, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_scr[g, :R, :1] * alpha \
        + p.sum(axis=-1, keepdims=True)
    acc_scr[g, :R, :] = acc_scr[g, :R, :] * alpha \
        + jax.lax.dot_general(
            p.astype(jnp.bfloat16), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    m_scr[g, :R, :] = jnp.broadcast_to(m_new, (R, m_scr.shape[2]))
    l_scr[g, :R, :] = jnp.broadcast_to(l_new, (R, l_scr.shape[2]))


def _gqa_attn_kernel(tables_ref, pos_ref, len_ref, scal_ref, q_ref, k_hbm,
                     v_hbm, o_ref, kbuf, vbuf, sems, m_scr, l_scr, acc_scr,
                     *, sm_scale, block_size, pages, n_kv, head_dim):
    """Grid (tiles,): one query tile a grid step, as
    ``_chunk_attn_kernel``, over a pool whose page is ``[block_size,
    Hkv * Dh]`` (a cache head is a whole-lane-tile slice of the page's
    lanes). A tile arrives as the step has it, ``[Cq, H * Dh]``;
    for each cache head the ``H // Hkv`` query heads of its group are
    stacked into ONE operand of ``group * nq`` rows (head-major: row
    ``r`` is the tile's token ``r % nq``) against that head's keys, so a
    key tile is loaded into the MXU once a group and not once a query
    head. ``nq`` is the tile's ``Cq`` slots, or one sublane tile of 16
    where the tile holds one token (the steps send those to
    ``_gqa_decode_kernel``).

    ``scal_ref`` holds the layer (its index in this pool's arrays) and
    the WINDOW: a query at position ``p`` sees ``p - window < t <= p``.
    The page walk starts at the first page the tile's earliest query
    still sees (``max(pos0 - window + 1, 0) // block_size``: table
    entries before it may be released, and are never read) and ends at
    the page of the tile's last token. With ``window`` past every
    context (:data:`GQA_ALL_POSITIONS`) it is plain causal attention
    from page 0. Arithmetic as ``_chunk_attn_kernel``: operands of both
    products bfloat16, softmax statistics and accumulations fp32; a
    masked entry contributes exactly zero, so a run that lies wholly
    outside a query's window leaves that query's statistics alone."""
    t = pl.program_id(0)
    bs, P, Dh = block_size, pages, head_dim
    span = P * bs
    Cq = q_ref.shape[1]
    G = q_ref.shape[2] // (n_kv * Dh)
    one = min(Cq, 16)                  # a bf16 sublane tile of query rows
    n_tok = len_ref[t]
    pos0 = pos_ref[t]
    layer, window = scal_ref[0], scal_ref[1]
    first_page = jnp.maximum(pos0 - window + 1, 0) // bs
    n_pages = (pos0 + jnp.maximum(n_tok, 1) - 1) // bs + 1 - first_page
    n_runs = (n_pages + P - 1) // P

    @pl.when(t == 0)
    def _zero():
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)

    def copies(run, half, start):
        _run_copies(tables_ref, t, run, n_pages, layer, (k_hbm, v_hbm),
                    (kbuf, vbuf), sems, half, pages=P, block_size=bs,
                    start=start, first_page=first_page)

    def attend(run, half, nq):
        R = G * nq
        t_pos = (first_page + run * P) * bs + jax.lax.broadcasted_iota(
            jnp.int32, (R, span), 1)
        # nq is a power of two: row r is token r % nq
        q_pos = pos0 + (jax.lax.broadcasted_iota(
            jnp.int32, (R, span), 0) & (nq - 1))
        mask = (t_pos <= q_pos) & (t_pos > q_pos - window)
        for g in range(n_kv):
            q = jnp.concatenate(
                [q_ref[0, :nq, (g * G + j) * Dh:(g * G + j + 1) * Dh]
                 for j in range(G)], axis=0)               # [R, Dh] bf16
            _gqa_head_run(q, kbuf, vbuf, half, g, mask, m_scr, l_scr,
                          acc_scr, sm_scale=sm_scale, head_dim=Dh)

    def finish(nq):
        R = G * nq
        live = (jax.lax.broadcasted_iota(jnp.int32, (R, Dh), 0)
                & (nq - 1)) < n_tok
        for g in range(n_kv):
            out = jnp.where(live, acc_scr[g, :R, :]
                            / jnp.maximum(l_scr[g, :R, :1], 1e-30), 0.0)
            for j in range(G):
                o_ref[0, :nq, (g * G + j) * Dh:(g * G + j + 1) * Dh] = \
                    out[j * nq:(j + 1) * nq, :]

    def by_size(fn):
        """`fn(nq)` for the tile's size: one token, or a window."""
        if one == Cq:
            fn(Cq)
        else:
            pl.when(n_tok == 1)(lambda: fn(one))
            pl.when(n_tok > 1)(lambda: fn(Cq))

    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_tok > 0)
    def _tile():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        copies(0, 0, True)

        def one_run(run, carry):
            half = run % 2

            @pl.when(run + 1 < n_runs)
            def _next():
                copies(run + 1, 1 - half, True)

            copies(run, half, False)
            by_size(lambda nq: attend(run, half, nq))
            return carry

        jax.lax.fori_loop(0, n_runs, one_run, 0)
        by_size(finish)


def _gqa_decode_kernel(tables_ref, pos_ref, act_ref, scal_ref, next_ref,
                       q_ref, k_hbm, v_hbm, o_ref, kbuf, vbuf, sems,
                       hand_ref, m_scr, l_scr, acc_scr, *, sm_scale,
                       block_size, pages, n_kv, head_dim):
    """Grid (rows,): one ONE-TOKEN row a grid step over the pools of
    ``_gqa_attn_kernel``, the row's query heads arriving grouped by
    cache head, ``[Hkv, R, Dh]`` (the group's heads are the ``R`` rows
    of their cache head, all at the row's one position): operands of
    ``R`` rows, which is what keeps a decode row's arithmetic under its
    page reads. Window, walk, mask and arithmetic as there.

    What differs is the PAGE PIPE, which never runs empty between the
    first live row and the last (:func:`_row_pipe`): a run is ``pages``
    pages (:func:`gqa_pages_per_run`: sized by its bytes), the next in
    flight while this one is attended, and a row's LAST run starts the
    first run of the next live row, ``next_ref[t]``
    (:func:`_next_live` of the active rows), ``hand_ref`` (SMEM)
    handing the buffer half over."""
    t = pl.program_id(0)
    n_rows = pl.num_programs(0)
    bs, P, Dh = block_size, pages, head_dim
    R = q_ref.shape[2]
    layer, window = scal_ref[0], scal_ref[1]

    def walk(row):
        """`row`'s first live page and how many it walks."""
        first = jnp.maximum(pos_ref[row] - window + 1, 0) // bs
        return first, pos_ref[row] // bs + 1 - first

    pos0 = pos_ref[t]
    first_page, n_pages = walk(t)
    n_runs = (n_pages + P - 1) // P

    @pl.when(t == 0)
    def _first():
        kbuf[...] = jnp.zeros_like(kbuf)
        vbuf[...] = jnp.zeros_like(vbuf)
        hand_ref[1] = 0

    def copies(row, run, half, start):
        first, n = walk(row)
        _run_copies(tables_ref, row, run, n, layer, (k_hbm, v_hbm),
                    (kbuf, vbuf), sems, half, pages=P, block_size=bs,
                    start=start, first_page=first)

    def attend(run, half):
        t_pos = (first_page + run * P) * bs + jax.lax.broadcasted_iota(
            jnp.int32, (R, P * bs), 1)
        mask = (t_pos <= pos0) & (t_pos > pos0 - window)
        for g in range(n_kv):
            _gqa_head_run(q_ref[0, g], kbuf, vbuf, half, g, mask, m_scr,
                          l_scr, acc_scr, sm_scale=sm_scale, head_dim=Dh)

    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(act_ref[t] > 0)
    def _row():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        _row_pipe(copies, attend, t, n_runs, next_ref[t], n_rows, hand_ref)
        for g in range(n_kv):
            o_ref[0, g] = acc_scr[g] / jnp.maximum(l_scr[g, :, :1], 1e-30)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "pages", "n_kv", "name", "interpret"))
def _gqa_call(k_pool, v_pool, q, block_tables, positions, lengths, layer,
              window, *, sm_scale, pages, n_kv, name, interpret):
    """q ``[N, Cq, H * Dh]`` (``Cq`` a power of two: the chunk kernel),
    or ``[N, Hkv, R, Dh]`` (one token a tile, its query heads grouped by
    cache head: the decode kernel) -> the same shape, float32. Layer and
    window are traced scalars: one lowering serves every layer of a
    pool, whichever positions they keep."""
    N = q.shape[0]
    bs = k_pool.shape[2]
    Dh = k_pool.shape[3] // n_kv
    decode = q.ndim == 4
    if decode:
        rows = q.shape[2]
    else:
        Cq, HD = q.shape[1:]
        rows = HD // (n_kv * Dh) * Cq   # a cache head's stacked query rows
        if Cq & (Cq - 1):
            raise ValueError("a query tile holds a power of two of "
                             "slots, got %d" % Cq)
    block = (1,) + q.shape[1:]
    lengths = lengths.astype(jnp.int32)
    scalars = [block_tables.astype(jnp.int32),
               jnp.maximum(positions, 0).astype(jnp.int32), lengths,
               jnp.stack([layer, window]).astype(jnp.int32)]
    buffers = [pltpu.VMEM((2, pages * bs, n_kv * Dh), k_pool.dtype),
               pltpu.VMEM((2, pages * bs, n_kv * Dh), v_pool.dtype),
               pltpu.SemaphoreType.DMA((2, 2))]
    if decode:
        scalars.append(_next_live(lengths > 0))
        buffers.append(pltpu.SMEM((2,), jnp.int32))

    def tile(n, *_):
        return (n,) + (0,) * (q.ndim - 1)

    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)
    return pl.pallas_call(
        functools.partial(_gqa_decode_kernel if decode
                          else _gqa_attn_kernel, sm_scale=sm_scale,
                          block_size=bs, pages=pages, n_kv=n_kv,
                          head_dim=Dh),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(N,),
            in_specs=[pl.BlockSpec(block, tile), hbm, hbm],
            out_specs=pl.BlockSpec(block, tile),
            scratch_shapes=buffers + [
                pltpu.VMEM((n_kv, rows, 128), jnp.float32),
                pltpu.VMEM((n_kv, rows, 128), jnp.float32),
                pltpu.VMEM((n_kv, rows, Dh), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=96 * 1024 * 1024),
        interpret=interpret,
        name=name,
    )(*scalars, q.astype(jnp.bfloat16), k_pool, v_pool)


def _gqa_window(window):
    return jnp.asarray(GQA_ALL_POSITIONS if window is None else window,
                       jnp.int32)


def gqa_paged_chunk_attention(k_pool, v_pool, q, block_tables, positions,
                              lengths, *, layer, window=None,
                              sm_scale=None,
                              pages_per_step=GQA_PAGES_PER_STEP):
    """The chunk window's attention of a GROUPED-QUERY block over a
    paged pool, the window cut into query tiles
    (:func:`paged_chunk_attention`'s contract) with two differences.
    The pools are ``[n_layers, num_blocks+1, block_size, Hkv * Dh]``
    (bfloat16: a page is a packed 2-D tile whose lanes hold the cache
    heads side by side), fewer cache heads than the ``H`` query heads of
    q ``[N, Cq, H, Dh]`` (``Cq`` a power of two): query head ``n`` reads
    cache head ``n // (H // Hkv)``. And a position sees the last
    ``window`` positions only, its own among them (``None``: all): the
    walk starts at the first page the tile's first query sees, so
    block-table entries before it may point anywhere. ``layer`` and
    ``window`` may be traced. Returns ``[N, Cq, H, Dh]`` float32, zero at
    slots past a tile's length."""
    N, Cq, H, Dh = q.shape
    if sm_scale is None:
        sm_scale = Dh ** -0.5
    out = _gqa_call(k_pool, v_pool, q.reshape(N, Cq, H * Dh), block_tables,
                    positions, lengths, jnp.asarray(layer, jnp.int32),
                    _gqa_window(window), sm_scale=float(sm_scale),
                    pages=int(min(pages_per_step, block_tables.shape[1])),
                    n_kv=k_pool.shape[3] // Dh,
                    name="gqa_paged_chunk_attention",
                    interpret=_device.pallas_interpret())
    return out.reshape(N, Cq, H, Dh)


def gqa_paged_decode_attention(k_pool, v_pool, q, block_tables, positions,
                               *, layer, window=None, active=None,
                               sm_scale=None, pages_per_step=None):
    """One-token decode attention of a grouped-query block: q ``[B, H,
    Dh]``, positions ``[B]``, over the pools of
    :func:`gqa_paged_chunk_attention`. One grid step a row; the row's
    live pages (from the first its position still sees, on a window
    layer) come by DMA in runs of :func:`gqa_pages_per_run` pages (by
    the pool's own page: 8 of 128 KiB, 32 of 32 KiB), a row's last run
    starting the next active row's first (``_gqa_decode_kernel``), and
    the query heads of a group, padded to one sublane tile of 16 rows,
    are one operand against their cache head's keys. An inactive row is
    skipped and comes out zero. Returns ``[B, H, Dh]`` float32."""
    B, H, Dh = q.shape
    n_kv = k_pool.shape[3] // Dh
    G = H // n_kv
    rows = -(-G // 16) * 16
    if sm_scale is None:
        sm_scale = Dh ** -0.5
    if active is None:
        active = jnp.ones((B,), jnp.int32)
    if pages_per_step is None:
        pages_per_step = gqa_pages_per_run(k_pool, block_tables.shape[1])
    qg = jnp.pad(q.reshape(B, n_kv, G, Dh),
                 ((0, 0), (0, 0), (0, rows - G), (0, 0)))
    out = _gqa_call(k_pool, v_pool, qg, block_tables, positions,
                    active.astype(jnp.int32),
                    jnp.asarray(layer, jnp.int32), _gqa_window(window),
                    sm_scale=float(sm_scale),
                    pages=int(min(pages_per_step, block_tables.shape[1])),
                    n_kv=n_kv, name="gqa_paged_decode_attention",
                    interpret=_device.pallas_interpret())
    return out[:, :, :G].reshape(B, H, Dh)


def gqa_paged_attention_reference(k_pool, v_pool, q, block_tables,
                                  positions, lengths, *, layer,
                                  window=None, sm_scale=None,
                                  pages_per_step=None):
    """The lax fallback of both grouped-query kernels over query tiles
    ``[N, Cq, H, Dh]``: the tile's block-table line gathered, cache
    heads repeated over their groups, softmax under the causal band.
    Released table entries gather the null page, which the band masks."""
    N, Cq, H, Dh = q.shape
    bs = k_pool.shape[2]
    n_kv = k_pool.shape[3] // Dh
    if sm_scale is None:
        sm_scale = Dh ** -0.5
    window = _gqa_window(window)

    def ctx(pool):
        g = _gathered_context(pool, layer, block_tables) \
            .astype(jnp.float32).reshape(N, -1, n_kv, Dh)
        return jnp.repeat(g, H // n_kv, axis=2)            # [N, T, H, Dh]

    k_ctx, v_ctx = ctx(k_pool), ctx(v_pool)
    slots = jnp.arange(Cq, dtype=jnp.int32)[None, :]
    q_pos = (jnp.maximum(positions, 0)[:, None] + slots)[:, :, None]
    t_ids = jnp.arange(block_tables.shape[1] * bs)[None, None, :]
    seen = (t_ids <= q_pos) & (t_ids > q_pos - window)     # [N, Cq, T]
    s = jnp.einsum("nchd,nthd->ncht", q.astype(jnp.float32), k_ctx) \
        * sm_scale
    s = jnp.where(seen[:, :, None, :], s, -jnp.inf)
    w = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    out = jnp.einsum("ncht,nthd->nchd", w, v_ctx)
    return jnp.where((slots < lengths[:, None])[:, :, None, None], out, 0.0)


def gqa_paged_decode_attention_reference(k_pool, v_pool, q, block_tables,
                                         positions, *, layer, window=None,
                                         active=None, sm_scale=None,
                                         pages_per_step=None):
    B = q.shape[0]
    lengths = (jnp.ones((B,), jnp.int32) if active is None
               else active.astype(jnp.int32))
    return gqa_paged_attention_reference(
        k_pool, v_pool, q[:, None], block_tables, positions, lengths,
        layer=layer, window=window, sm_scale=sm_scale)[:, 0]


def _kv_page_write_kernel(blk_ref, lo_ref, hi_ref, layer_ref, krows_ref,
                          vrows_ref, kpage_ref, vpage_ref, ko_ref, vo_ref):
    """Grid (units,): one page of K and one of V a grid step, found by
    the index map (the null page for an unused unit). The page is read,
    its rows ``lo <= r < hi`` take the unit's new rows, and it is
    written back over itself."""
    u = pl.program_id(0)
    shape = kpage_ref.shape[2:]
    r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    hit = (r >= lo_ref[u]) & (r < hi_ref[u])
    for rows_ref, page_ref, o_ref in ((krows_ref, kpage_ref, ko_ref),
                                      (vrows_ref, vpage_ref, vo_ref)):
        rows = jnp.broadcast_to(rows_ref[0].astype(jnp.float32), shape)
        o_ref[0, 0] = jnp.where(hit, rows, page_ref[0, 0].astype(
            jnp.float32)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _kv_page_write_call(k_pool, v_pool, k_rows, v_rows, page_ids, lo, hi,
                        layer, *, interpret):
    U, n_rows, W = k_rows.shape
    bs = k_pool.shape[2]

    def page(u, blk, lo, hi, layer):
        return (layer[0], blk[u], 0, 0)

    rows = pl.BlockSpec((1, n_rows, W), lambda u, *_: (u, 0, 0))
    pool = pl.BlockSpec((1, 1, bs, W), page)
    return pl.pallas_call(
        _kv_page_write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(U,),
            in_specs=[rows, rows, pool, pool],
            out_specs=[pool, pool]),
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        # operands 6 and 7 (after four prefetched scalars and the rows)
        # are the pools: each result is the same buffer
        input_output_aliases={6: 0, 7: 1},
        interpret=interpret,
        name="kv_page_write",
    )(page_ids.astype(jnp.int32), lo.astype(jnp.int32),
      hi.astype(jnp.int32), layer.reshape(1),
      k_rows.astype(k_pool.dtype), v_rows.astype(v_pool.dtype),
      k_pool, v_pool)


def kv_page_write(k_pool, v_pool, k_rows, v_rows, page_ids, lo, hi, *,
                  layer):
    """Write new K and V rows into a paged pool IN PLACE, whole pages at
    a time (the pools are aliased to the results).

    k_pool/v_pool: ``[n_layers, num_blocks+1, block_size, W]`` WHOLE.
    The write comes as UNITS, one page each: unit ``u`` rewrites rows
    ``lo[u] <= r < hi[u]`` of page ``page_ids[u]`` of ``layer`` with
    ``k_rows[u, r]`` / ``v_rows[u, r]`` (``[U, block_size, W]``; or
    ``[U, 1, W]``: the one new row of a decode step, which goes where
    ``lo`` says). No two units of a call may name the same page, the
    null page (``lo == hi``: an unused unit) excepted. As
    :func:`latent_write`: XLA's own scatter into a packed bfloat16 pool
    changes the pool's layout and copies it; this reads and rewrites
    the touched pages only (docs/KERNELS.md)."""
    return _kv_page_write_call(k_pool, v_pool, k_rows, v_rows, page_ids,
                               lo, hi, jnp.asarray(layer, jnp.int32),
                               interpret=_device.pallas_interpret())


def kv_page_write_reference(k_pool, v_pool, k_rows, v_rows, page_ids, lo,
                            hi, *, layer):
    """The lax fallback: one scatter a pool; rows outside ``[lo, hi)``
    are dropped (an index past the pool)."""
    U, n_rows, _W = k_rows.shape
    bs = k_pool.shape[2]
    r = jnp.arange(bs, dtype=jnp.int32)[None, :]
    hit = (r >= lo[:, None]) & (r < hi[:, None])
    blk = jnp.where(hit, page_ids[:, None], k_pool.shape[1])
    src = jnp.zeros((U, bs), jnp.int32) if n_rows == 1 else \
        jnp.broadcast_to(r, (U, bs))
    take = jnp.arange(U)[:, None]

    def put(pool, rows):
        return pool.at[layer, blk, jnp.broadcast_to(r, (U, bs))].set(
            rows[take, src].astype(pool.dtype), mode="drop")

    return put(k_pool, k_rows), put(v_pool, v_rows)



def _flash_qualify(T=None, Tk=None, head_dim=None, causal=False):
    """The compat_ops.py gate, promoted and FIXED: the historical check
    required q.shape == k.shape, silently dropping the tuned path for
    every cross-attention-shaped call — non-causal cross attention
    (Tq != Tk) tiles fine (the kernel masks by kv length). Causal still
    requires Tq == Tk: the blocked diagonal assumes aligned starts."""
    Tk = T if Tk is None else Tk
    if T is None or T % 128 or Tk % 128:
        return False, "seq len not a multiple of 128"
    if head_dim is None or head_dim < 64:
        return False, "head_dim < 64"
    if causal and Tk != T:
        return False, "causal cross-attention (Tq != Tk)"
    return True, None


def _paged_qualify(head_dim=None, block_size=None, window=None):
    """The BlockSpec kernels over the paged cache (``paged_attention``,
    the tree window), and ``paged_decode``: that one takes every head
    width this admits, because ``paged_decode_attention`` walks pages
    where ``_chunk_qualify`` holds and is ``paged_attention`` where it
    does not; a narrow head is a reason to pick the other kernel, not to
    leave for the lax path.

    One-row pages stay on the lax path. While a block was a page
    with the heads folded into the lanes, Mosaic refused them; the
    stored ``[bs, H, Dh]`` page of PR 25 compiles at ``block_size`` 1
    too, but has never run on the chip, and a grid step per cached
    token is no kernel to want. Every geometry tried compiles for the
    v5e topology (tests/test_kernels_lower_tpu.py and a sweep):
    head_dim 8..128, 1..32 heads, block_size 1..128, windows of 1..33,
    fp32 and bf16 pages — a block is a whole page and so equals the
    array in its last two axes."""
    if block_size is not None and block_size < 2:
        return False, "block_size < 2 (one-row pages stay on the lax path)"
    return True, None


def _chunk_qualify(head_dim=None, block_size=None, window=None):
    """The kernel copies pages as the pool stores them in HBM, rows of
    ``head_dim`` lanes, and Mosaic slices HBM in whole 128-lane tiles
    ("Slice shape along dimension 4 must be aligned to tiling (128)",
    the v5e compile at a head of 64)."""
    if head_dim is not None and head_dim % 128:
        return False, "head_dim not a multiple of 128 (pages are copied " \
                      "as stored, whole lane tiles)"
    return _paged_qualify(head_dim, block_size, window)


def _int8_qualify(x=None, w=None, *args, **kwargs):
    xs = getattr(x, "shape", None)
    ws = getattr(w, "shape", None)
    if xs is None or ws is None or len(xs) != 2 or len(ws) != 2:
        return False, "operands are not 2-D"
    return True, None


def _gmm_qualify(rows=None, k=None, n=None, block_m=GMM_BLOCK_M):
    """The weight block is an expert's whole ``[K, N]`` matrix, held
    twice in VMEM (double buffering) beside the row and output tiles."""
    if rows is not None and rows % block_m:
        return False, "rows not a multiple of block_m"
    if k is not None and n is not None and 2 * k * n * 2 > 40 * 2 ** 20:
        return False, "an expert's matrix does not fit VMEM twice"
    return True, None


def _latent_qualify(width=None, v_width=None, block_size=None,
                    window=None):
    if block_size is not None and block_size % 8:
        return False, "block_size not a multiple of 8 (pages are stacked)"
    if v_width is not None and v_width % 128:
        return False, "v_width not a multiple of 128 (a lane slice)"
    return True, None


def _gqa_qualify(head_dim=None, block_size=None, window=None):
    """Pages are packed bfloat16 tiles ``[block_size, Hkv * head_dim]``:
    whole sublane tiles of 16 rows, a cache head a whole lane tile."""
    if head_dim is not None and head_dim % 128:
        return False, "head_dim not a multiple of 128 (a cache head is " \
                      "a lane slice of the page)"
    if block_size is not None and block_size % 16:
        return False, "block_size not a multiple of 16 (packed bf16 pages)"
    return True, None


def _kda_qualify(head_dim=None, n_heads=None):
    """A head's state is ``[dk, dv]`` float32 tiles: whole lane tiles of
    values, whole sublane tiles of channels; the chunk kernel reads a
    head's tokens as every eighth line of a group of eight heads."""
    if head_dim is not None and head_dim % 128:
        return False, "head_dim not a multiple of 128 (a state's rows " \
                      "are lane tiles)"
    if n_heads is not None and n_heads % 8:
        return False, "n_heads not a multiple of 8 (a token's heads " \
                      "are stored in groups of eight lines)"
    return True, None


def _register_all():
    from .kernel_registry import register_kernel

    register_kernel(
        "flash_attention", flash_attention, attention_reference,
        qualify=_flash_qualify, default_on=None,
        doc="blocked online-softmax attention ([B,H,T,D]); default: on "
            "everywhere (interpret off-TPU, its historical dispatch)")
    register_kernel(
        "paged_decode", paged_decode_attention, paged_attention_reference,
        qualify=_paged_qualify, default_on=_device.on_tpu,
        doc="one-token decode attention over KVBlockPool pages: at "
            "heads of whole 128-lane tiles one grid step a row, the "
            "row's own pages copied from the pool in HBM by manual DMA "
            "and all heads in one product; at narrower heads the "
            "BlockSpec grid over every table slot (paged_attention); "
            "default: TPU only")
    register_kernel(
        "spec_window", paged_attention, paged_attention_reference,
        qualify=_paged_qualify, default_on=_device.on_tpu,
        doc="speculative verify-window (k+1 query positions) over the "
            "paged cache in one kernel; default: TPU only")
    register_kernel(
        "spec_window_tree", paged_attention_tree,
        paged_attention_tree_reference,
        qualify=_paged_qualify, default_on=_device.on_tpu,
        doc="tree-mask verify window (width x depth token tree, one "
            "kernel) over the paged cache — in-window visibility by "
            "ancestor matrix via one-hot matmul; default: TPU only")
    register_kernel(
        "chunk_window", paged_chunk_attention,
        paged_chunk_attention_reference,
        qualify=_chunk_qualify, default_on=_device.on_tpu,
        doc="the chunked-prefill window's attention over query tiles "
            "(a decode row is a tile of one token), a tile's own pages "
            "copied from the pool in HBM by manual DMA; default: TPU "
            "only")
    register_kernel(
        "gmm", gmm, gmm_reference,
        qualify=_gmm_qualify, default_on=_device.on_tpu,
        doc="grouped expert matmul over tile-aligned rows sorted by "
            "expert, the weight block found by a scalar-prefetched "
            "table; default: TPU only")
    register_kernel(
        "latent_decode", latent_paged_attention,
        latent_paged_attention_reference,
        qualify=_latent_qualify, default_on=_device.on_tpu,
        doc="one-token absorbed MLA attention over the paged latent "
            "cache, all heads on one shared row a token; default: TPU "
            "only")
    register_kernel(
        "latent_window", latent_paged_attention,
        latent_paged_attention_reference,
        qualify=_latent_qualify, default_on=_device.on_tpu,
        doc="the same kernel over a chunk window of query slots "
            "(one-token rows compute one slot); default: TPU only")
    register_kernel(
        "latent_write", latent_write, latent_write_reference,
        qualify=_latent_qualify, default_on=_device.on_tpu,
        doc="a window's rows written into the paged latent pool page by "
            "page, in place (XLA's scatter into a packed bf16 pool "
            "copies the pool); default: TPU only")
    register_kernel(
        "gqa_decode", gqa_paged_decode_attention,
        gqa_paged_decode_attention_reference,
        qualify=_gqa_qualify, default_on=_device.on_tpu,
        doc="one-token grouped-query attention over packed bf16 pages, "
            "a group's query heads one operand against its cache head, "
            "the walk from the row's first live page on a window layer; "
            "default: TPU only")
    register_kernel(
        "gqa_chunk", gqa_paged_chunk_attention,
        gqa_paged_attention_reference,
        qualify=_gqa_qualify, default_on=_device.on_tpu,
        doc="the chunk window's grouped-query attention over query "
            "tiles under the causal band of a window layer (or plain "
            "causal); default: TPU only")
    register_kernel(
        "kv_page_write", kv_page_write, kv_page_write_reference,
        qualify=_gqa_qualify, default_on=_device.on_tpu,
        doc="new K and V rows written into a packed bf16 paged pool a "
            "whole page at a time, in place; default: TPU only")
    register_kernel(
        "kda_decode", kda_decode, kda_decode_reference,
        qualify=_kda_qualify, default_on=_device.on_tpu,
        doc="one token a row through the delta rule with a per-channel "
            "decay: the row's [H, dk, dv] float32 states of one layer "
            "read, decayed, updated and read out on the VPU and written "
            "back in place, an inactive row skipped; default: TPU only")
    register_kernel(
        "kda_chunk", kda_chunk, kda_chunk_reference,
        qualify=_kda_qualify, default_on=_device.on_tpu,
        doc="a step's prefill tokens through the delta rule in tiles of "
            "64 (the WY form, decays factored over sub-chunks of 16), a "
            "row's state carried in VMEM over its tiles and written back "
            "in place; default: TPU only")
    register_kernel(
        "int8_matmul", int8_matmul, int8_matmul_reference,
        qualify=_int8_qualify, default_on=_device.on_tpu,
        doc="fused quantize + int8 dot (int32 acc) + per-channel "
            "dequantize for full-int8 programs; default: TPU only")


_register_all()
