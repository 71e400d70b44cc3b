"""Remaining Appendix-A operator registrations (SURVEY Appendix A — the
reference ops without a dedicated home module: fused/fusion variants,
pserver sharding helpers, SSD mining, SPP/unpool, and misc losses).

Ops the reference registers but which this architecture deliberately
handles OUTSIDE the kernel registry are NOT here: feed/fetch/save/load/
save_combine/load_combine (executor + io.py), while/conditional_block/
recurrent and the tensor-array/LoD-structure ops (layers/control_flow.py
lowers them to lax control flow + Python tensor arrays), delete_var/
get_places (scope/platform). See PARITY.md §2.2.
"""

import jax
import jax.numpy as jnp

from .registry import register, get, simple_op


# ---- simple math / losses -------------------------------------------------

@simple_op("minus", in_slots=("X", "Y"))
def _minus(ctx, x, y, **attrs):
    return x - y


@register("fill", differentiable=False)
def _fill(ctx, ins, attrs):
    """fill_op.cc: materialize a constant tensor from attr data."""
    import numpy as np

    from .registry import np_dtype

    shape = tuple(attrs.get("shape", []))
    dt = np_dtype(attrs.get("dtype", "float32"))
    # convert in numpy at the TARGET dtype — a float32 intermediate would
    # corrupt int64 values above 2^24
    return {"Out": [jnp.asarray(
        np.asarray(attrs.get("value", [0.0]), dt).reshape(shape))]}


@register("fill_zeros_like2", differentiable=False)
def _fill_zeros_like2(ctx, ins, attrs):
    return {"Out": [jnp.zeros_like(ins["X"][0])]}


@register("modified_huber_loss", nondiff_inputs=("Y",))
def _modified_huber_loss(ctx, ins, attrs):
    """modified_huber_loss_op.cc: y in {0,1} -> {-1,1}; quadratic inside
    the margin, linear outside."""
    x = ins["X"][0]
    y = 2.0 * ins["Y"][0].astype(jnp.float32) - 1.0
    yf = y * x
    loss = jnp.where(yf >= -1.0,
                     jnp.square(jnp.maximum(0.0, 1.0 - yf)),
                     -4.0 * yf)
    return {"Out": [loss], "IntermediateVal": [yf]}


@simple_op("conv_shift", in_slots=("X", "Y"))
def _conv_shift(ctx, x, y, **attrs):
    """Circular correlation (conv_shift_op.cc): X [B, W], Y [B, N] with N
    odd; out[b, i] = sum_j Y[b, j] * X[b, (i + j - N//2) mod W]."""
    W = x.shape[1]
    N = y.shape[1]
    shifts = jnp.stack([jnp.roll(x, (N // 2) - j, axis=1)
                        for j in range(N)], axis=1)  # [B, N, W]
    return jnp.einsum("bn,bnw->bw", y, shifts)


# ---- pooling family -------------------------------------------------------

@register("spp")
def _spp(ctx, ins, attrs):
    """Spatial pyramid pooling (spp_op.cc): pyramid_height levels of
    bin-pooled features, flattened and concatenated."""
    x = ins["X"][0]
    levels = attrs.get("pyramid_height", 2)
    ptype = attrs.get("pooling_type", "max")
    n, c, h, w = x.shape
    outs = []
    for lvl in range(levels):
        bins = 2 ** lvl
        pooled = jnp.zeros((n, c, bins, bins), x.dtype)
        for i in range(bins):
            for j in range(bins):
                hs, he = (h * i) // bins, max((h * (i + 1) + bins - 1) // bins,
                                              (h * i) // bins + 1)
                ws, we = (w * j) // bins, max((w * (j + 1) + bins - 1) // bins,
                                              (w * j) // bins + 1)
                block = x[:, :, hs:he, ws:we]
                red = (block.max(axis=(2, 3)) if ptype == "max"
                       else block.mean(axis=(2, 3)))
                pooled = pooled.at[:, :, i, j].set(red)
        outs.append(pooled.reshape(n, -1))
    return {"Out": [jnp.concatenate(outs, axis=1)]}


@register("max_pool3d_with_index")
def _max_pool3d_with_index(ctx, ins, attrs):
    from .conv import _pool_max_with_index

    out, mask = _pool_max_with_index(ins["X"][0], attrs, 3)
    return {"Out": [out], "Mask": [mask]}


@register("unpool", nondiff_inputs=("Indices",))
def _unpool(ctx, ins, attrs):
    """Max-unpooling (unpool_op.cc): scatter pooled values back to the
    positions recorded in Indices (flat h*w offsets per channel). Output
    size follows the reference formula (in-1)*stride + ksize - 2*pad."""
    x = ins["X"][0]
    idx = ins["Indices"][0].astype(jnp.int32)
    n, c, h, w = x.shape
    ksize = list(attrs.get("ksize", [2, 2]))
    strides = list(attrs.get("strides", ksize))
    pads = list(attrs.get("paddings", [0, 0]))
    oh = (h - 1) * strides[0] + ksize[0] - 2 * pads[0]
    ow = (w - 1) * strides[1] + ksize[1] - 2 * pads[1]
    flat_out = jnp.zeros((n, c, oh * ow), x.dtype)
    flat_x = x.reshape(n, c, h * w)
    flat_idx = idx.reshape(n, c, h * w)
    bi = jnp.arange(n)[:, None, None]
    ci = jnp.arange(c)[None, :, None]
    flat_out = flat_out.at[bi, ci, flat_idx].set(flat_x)
    return {"Out": [flat_out.reshape(n, c, oh, ow)]}


# ---- metrics / mining -----------------------------------------------------

@register("positive_negative_pair", differentiable=False)
def _positive_negative_pair(ctx, ins, attrs):
    """positive_negative_pair_op.cc: per-query counts of correctly ordered
    (positive), wrongly ordered (negative), and tied prediction pairs."""
    score = ins["Score"][0].reshape(-1)
    label = ins["Label"][0].reshape(-1)
    qid = ins["QueryID"][0].reshape(-1)
    same_q = qid[:, None] == qid[None, :]
    upper = jnp.triu(jnp.ones_like(same_q, dtype=bool), 1)
    valid = same_q & upper & (label[:, None] != label[None, :])
    s_diff = score[:, None] - score[None, :]
    l_diff = (label[:, None] - label[None, :]).astype(jnp.float32)
    pos = jnp.sum((valid & (s_diff * l_diff > 0)).astype(jnp.float32))
    neg = jnp.sum((valid & (s_diff * l_diff < 0)).astype(jnp.float32))
    neu = jnp.sum((valid & (s_diff == 0)).astype(jnp.float32))
    acc = ins.get("AccumulatePositivePair")
    if acc:
        pos = pos + ins["AccumulatePositivePair"][0].reshape(())
        neg = neg + ins["AccumulateNegativePair"][0].reshape(())
        neu = neu + ins["AccumulateNeutralPair"][0].reshape(())
    return {"PositivePair": [pos.reshape((1,))],
            "NegativePair": [neg.reshape((1,))],
            "NeutralPair": [neu.reshape((1,))]}


@register("mine_hard_examples", differentiable=False)
def _mine_hard_examples(ctx, ins, attrs):
    """SSD hard-negative mining (mine_hard_examples_op.cc): per image keep
    the neg_pos_ratio * num_pos highest-loss negatives. Padded-dense: the
    output is an updated MatchIndices where un-selected negatives stay -1
    and selected hard negatives are marked -2 (NegIndices mask rides along
    as a dense 0/1 tensor instead of a LoD list)."""
    cls_loss = ins["ClsLoss"][0]
    match_indices = ins["MatchIndices"][0]
    loss = cls_loss.reshape(match_indices.shape)
    if ins.get("LocLoss"):
        loss = loss + ins["LocLoss"][0].reshape(match_indices.shape)
    ratio = attrs.get("neg_pos_ratio", 3.0)
    is_neg = match_indices < 0
    num_pos = jnp.sum(~is_neg, axis=1, keepdims=True)
    num_neg = jnp.minimum((num_pos * ratio).astype(jnp.int32),
                          jnp.sum(is_neg, axis=1, keepdims=True))
    neg_loss = jnp.where(is_neg, loss, -jnp.inf)
    order = jnp.argsort(-neg_loss, axis=1)
    rank = jnp.argsort(order, axis=1)
    selected = is_neg & (rank < num_neg)
    updated = jnp.where(selected, -2, match_indices)
    return {"NegIndices": [selected.astype(jnp.int32)],
            "UpdatedMatchIndices": [updated]}


@register("sample_logits", nondiff_inputs=("Labels", "CustomizedSamples"))
def _sample_logits(ctx, ins, attrs):
    """sample_logits_op.cc: gather the label logits plus num_samples
    uniformly sampled negative-class logits (sampled-softmax front half)."""
    logits = ins["Logits"][0]
    labels = ins["Labels"][0].astype(jnp.int32)
    b, n_classes = logits.shape
    num_samples = attrs.get("num_samples", 16)
    if ins.get("CustomizedSamples"):
        samples = ins["CustomizedSamples"][0].astype(jnp.int32)
    else:
        key = ctx.rng(attrs)
        neg = jax.random.randint(key, (b, num_samples), 0, n_classes)
        samples = jnp.concatenate([labels.reshape(b, -1), neg], axis=1)
    sampled = jnp.take_along_axis(logits, samples, axis=1)
    n_true = labels.reshape(b, -1).shape[1]
    sampled_labels = jnp.arange(n_true, dtype=jnp.int32)[None, :].repeat(
        b, axis=0)
    return {"SampledLogits": [sampled], "Samples": [samples],
            "SampledLabels": [sampled_labels],
            "Probabilities": [jnp.full(samples.shape,
                                       1.0 / n_classes, jnp.float32)],
            "LogitsDim": [jnp.asarray(logits.shape, jnp.int32)],
            "LabelsDim": [jnp.asarray(labels.shape, jnp.int32)]}


# ---- pserver sharding helpers --------------------------------------------

@register("split_ids", differentiable=False)
def _split_ids(ctx, ins, attrs):
    """split_ids_op.cc: route ids to N shards by id %% N (padded-dense:
    each shard output keeps its ids, others set to -1)."""
    ids = ins["Ids"][0]
    n = attrs.get("num_shards", 1)
    outs = [jnp.where(ids % n == s, ids, -1) for s in range(n)]
    return {"Out": outs}


@register("merge_ids", differentiable=False)
def _merge_ids(ctx, ins, attrs):
    """merge_ids_op.cc capability: gather per-shard rows back into the
    original id order. Rows[i] holds the embedding rows for ids routed to
    shard i (id %% n == i), in that shard's id order."""
    ids = ins["Ids"][0].reshape(-1)
    rows = ins["X"]
    n = len(rows)
    dim = rows[0].shape[-1]
    out = jnp.zeros((ids.shape[0], dim), rows[0].dtype)
    for s in range(n):
        mask = ids % n == s
        # position of each id within its shard = cumulative count - 1
        pos = jnp.cumsum(mask) - 1
        gathered = rows[s][jnp.clip(pos, 0, rows[s].shape[0] - 1)]
        out = jnp.where(mask[:, None], gathered, out)
    return {"Out": [out]}


@register("split_selected_rows", differentiable=False)
def _split_selected_rows(ctx, ins, attrs):
    """split_selected_rows_op.cc: slice a dense (row-major) tensor into
    height_sections row blocks."""
    x = ins["X"][0]
    sections = attrs.get("height_sections", [x.shape[0]])
    outs, start = [], 0
    for h in sections:
        outs.append(x[start:start + h])
        start += h
    return {"Out": outs}


@register("lookup_sparse_table", nondiff_inputs=("Ids",))
def _lookup_sparse_table(ctx, ins, attrs):
    """lookup_sparse_table_op.cc: same lowering as lookup_table (the
    auto-growth sparse-table behavior belongs to the host embedding store
    — parallel/host_embedding.py)."""
    return get("lookup_table").impl(ctx, {"W": ins["W"], "Ids": ins["Ids"]},
                                    attrs)


# ---- fused / fusion variants ---------------------------------------------

@register("fused_embedding_seq_pool", nondiff_inputs=("Ids",))
def _fused_embedding_seq_pool(ctx, ins, attrs):
    """fused_embedding_seq_pool_op.cc: lookup + sum-pool over time in one
    op (Ids [B, T] padded; pad entries use padding_idx semantics)."""
    table = ins["W"][0]
    ids = ins["Ids"][0].astype(jnp.int32)
    if ids.ndim == 3:
        ids = ids[..., 0]
    emb = table[jnp.clip(ids, 0, table.shape[0] - 1)]
    padding_idx = attrs.get("padding_idx", -1)
    if padding_idx is not None and padding_idx >= 0:
        emb = jnp.where((ids == padding_idx)[..., None], 0.0, emb)
    return {"Out": [jnp.sum(emb, axis=1)]}


@register("fused_elemwise_activation")
def _fused_elemwise_activation(ctx, ins, attrs):
    """fused_elemwise_activation_op.cc: functor_list[0] is the OUTER
    functor — ["binary", "unary"] computes Binary(X, Unary(Y)),
    ["unary", "binary"] computes Unary(Binary(X, Y)). IntermediateOut is
    the inner functor's result."""
    functors = [f.split(",")[0] for f in attrs.get("functor_list", [])]
    x, y = ins["X"][0], ins["Y"][0]
    binary = {"elementwise_add": jnp.add, "elementwise_sub": jnp.subtract,
              "elementwise_mul": jnp.multiply}
    unary = {"relu": jax.nn.relu, "scale": lambda v: v * attrs.get(
        "scale", 1.0), "tanh": jnp.tanh, "sigmoid": jax.nn.sigmoid}
    if len(functors) != 2:
        raise ValueError("fused_elemwise_activation needs functor_list of "
                         "two entries, got %r" % (functors,))
    f0, f1 = functors
    if f0 in binary:
        inner = unary[f1](y)
        out = binary[f0](x, inner)
    else:
        inner = binary[f1](x, y)
        out = unary[f0](inner)
    return {"Out": [out], "IntermediateOut": [inner]}


def _project_then(op_name, extra_out_slots):
    """fusion_gru/fusion_lstm = X @ WeightX (+bias) then the plain RNN
    kernel (fusion_*_op.cc fuse the input GEMM into the recurrence)."""

    def impl(ctx, ins, attrs):
        x = ins["X"][0]
        wx = ins["WeightX"][0]
        projected = jnp.einsum("btm,mk->btk", x, wx)
        inner_ins = {"Input": [projected], "Weight": ins["WeightH"]}
        if ins.get("Bias"):
            inner_ins["Bias"] = ins["Bias"]
        if ins.get("H0"):
            inner_ins["H0"] = ins["H0"]
        if ins.get("C0"):
            inner_ins["C0"] = ins["C0"]
        out = get(op_name).impl(ctx, inner_ins, attrs)
        res = {"Hidden": out["Hidden"], "XX": [projected]}
        for slot, src in extra_out_slots.items():
            res[slot] = out[src]
        return res

    return impl


register("fusion_gru")(_project_then("gru", {}))
register("fusion_lstm")(_project_then("lstm", {"Cell": "Cell"}))


@register("lstmp")
def _lstmp(ctx, ins, attrs):
    """Projection LSTM (lstmp_op.cc): standard LSTM whose output is
    projected through ProjWeight each step; recurrence runs on the
    projection."""
    x = ins["Input"][0]
    w = ins["Weight"][0]          # [P, 4D]
    w_proj = ins["ProjWeight"][0]  # [D, P]
    d = w_proj.shape[0]
    p = w_proj.shape[1]
    b = x.shape[0]
    bias = ins["Bias"][0] if ins.get("Bias") else None
    h0 = ins["H0"][0] if ins.get("H0") else jnp.zeros((b, p), x.dtype)
    c0 = ins["C0"][0] if ins.get("C0") else jnp.zeros((b, d), x.dtype)
    xt_seq = jnp.swapaxes(x, 0, 1)

    def step(carry, xt):
        h_prev, c_prev = carry
        g = xt + h_prev @ w
        if bias is not None:
            g = g + bias
        i, f, cand, o = jnp.split(g, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c_prev + jax.nn.sigmoid(i) * jnp.tanh(cand)
        h = (jax.nn.sigmoid(o) * jnp.tanh(c)) @ w_proj
        return (h, c), (h, c)

    (_hl, _cl), (hs, cs) = jax.lax.scan(step, (h0, c0), xt_seq)
    return {"Projection": [jnp.swapaxes(hs, 0, 1)],
            "Cell": [jnp.swapaxes(cs, 0, 1)],
            "BatchGate": [jnp.swapaxes(hs, 0, 1)],
            "BatchCellPreAct": [jnp.swapaxes(cs, 0, 1)],
            "BatchHidden": [jnp.swapaxes(hs, 0, 1)]}


@register("cudnn_lstm")
def _cudnn_lstm(ctx, ins, attrs):
    """cudnn_lstm_op.cu.cc capability: the fused long-sequence LSTM is the
    same lax.scan kernel — XLA fuses the steps (no cuDNN analog needed)."""
    return get("lstm").impl(ctx, ins, attrs)


@register("attention_lstm")
def _attention_lstm(ctx, ins, attrs):
    """attention_lstm_op.cc: per step, softmax attention over the source
    sequence conditioned on the previous cell state, then one LSTM step on
    the attended vector."""
    x = ins["X"][0]                   # [B, T, M]
    att_w = ins["AttentionWeight"][0]  # [M+D, 1]
    lstm_w = ins["LSTMWeight"][0]      # [M+D, 4D]
    lstm_b = ins["LSTMBias"][0]        # [1, 4D]
    b_sz, t_len, m = x.shape
    d = lstm_w.shape[1] // 4
    h0 = ins["H0"][0] if ins.get("H0") else jnp.zeros((b_sz, d), x.dtype)
    c0 = ins["C0"][0] if ins.get("C0") else jnp.zeros((b_sz, d), x.dtype)

    def step(carry, _):
        h_prev, c_prev = carry
        ctx_in = jnp.concatenate(
            [x, jnp.repeat(c_prev[:, None, :], t_len, axis=1)], axis=-1)
        scores = jnp.einsum("btk,ko->bto", ctx_in, att_w)[..., 0]
        alpha = jax.nn.softmax(scores, axis=1)
        attended = jnp.einsum("bt,btm->bm", alpha, x)
        g = jnp.concatenate([attended, h_prev], axis=-1) @ lstm_w + lstm_b
        i, f, cand, o = jnp.split(g, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c_prev + jax.nn.sigmoid(i) * jnp.tanh(cand)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), h

    (h_last, c_last), hs = jax.lax.scan(step, (h0, c0), None, length=t_len)
    return {"Hidden": [jnp.swapaxes(hs, 0, 1)], "Cell": [c_last],
            "AttentionedX": [x], "AttentionFCOut": [h_last],
            "LSTMX": [h_last], "LSTMOUT": [h_last]}


# ---- gradient compression / buffer fusion --------------------------------

@register("dgc", differentiable=False, stateful=True)
def _dgc(ctx, ins, attrs):
    """dgc_op.cc: momentum-corrected top-k sparsification. U carries the
    momentum-accumulated residual, V the unsent mass; the dense masked
    gradient goes out for the (sparse) allreduce."""
    from ..parallel.dgc import topk_sparsify

    grad = ins["Grad"][0]
    u = ins["U"][0]
    v = ins["V"][0]
    m = attrs.get("m", 0.9)
    ratio = 1.0 - attrs.get("sparsity", [0.999])[-1]
    k = max(1, int(grad.size * ratio))
    u_out = m * u + grad
    v_out = v + u_out
    vals, idx, residual = topk_sparsify(v_out, k)
    dense = v_out - residual          # the sent (top-k) mass
    sent = dense != 0
    # the encode buffer is float32: indices ride BITCAST (a numeric cast
    # would corrupt indices above 2^24), values numerically cast
    idx_bits = jax.lax.bitcast_convert_type(idx.astype(jnp.int32),
                                            jnp.float32)
    return {"U_out": [jnp.where(sent, 0.0, u_out)],
            "V_out": [residual],
            "EncodeGrad": [jnp.concatenate(
                [idx_bits, vals.astype(jnp.float32)])],
            "Grad_out": [dense],
            "GatherBuff": [dense]}


@register("dgc_clip_by_norm", differentiable=False)
def _dgc_clip_by_norm(ctx, ins, attrs):
    """dgc_clip_by_norm_op.cc: clip_by_norm gated on the rampup window."""
    step = ins["current_step"][0].reshape(()) if ins.get(
        "current_step") else jnp.asarray(0.0)
    rampup = attrs.get("rampup_begin_step", 0.0)
    clipped = get("clip_by_norm").impl(ctx, {"X": ins["X"]}, attrs)["Out"][0]
    out = jnp.where(step >= rampup, clipped, ins["X"][0])
    return {"Out": [out]}


@register("alloc_continuous_space", differentiable=False)
def _alloc_continuous_space(ctx, ins, attrs):
    """alloc_continuous_space_op.cc: fuse a list of tensors into one flat
    buffer (gradient-bucketing ancestor). Outputs the per-input views plus
    the fused flat buffer; XLA's buffer assignment owns actual placement."""
    xs = ins["Input"]
    flat = jnp.concatenate([x.reshape(-1) for x in xs])
    if attrs.get("set_constant", False):
        flat = jnp.full_like(flat, attrs.get("constant", 0.0))
        outs, start = [], 0
        for x in xs:
            outs.append(flat[start:start + x.size].reshape(x.shape))
            start += x.size
    else:
        outs = list(xs)
    return {"Output": outs, "FusedOutput": [flat]}


def _flash_on_mesh(q, k, v, causal, scale, mesh):
    """The flash kernel on q, k, v: [B, H, T, Dh] inside a GSPMD-sharded
    step. A compiled Pallas (Mosaic) kernel cannot be partitioned by
    GSPMD ("Mosaic kernels cannot be automatically partitioned"), so on a
    multi-device step mesh the call runs under a shard_map that makes
    every mesh axis manual: batch split over dp and heads over tp where
    they divide (attention is independent per batch row and head), the
    operand replicated over an axis that does not divide or does not
    apply. Inside a region that is already (partly) manual — a pipeline
    stage, the SPMD trainer — the call is made directly, as before."""
    from .pallas_kernels import flash_attention

    if mesh is None or mesh.size == 1 \
            or jax.sharding.get_abstract_mesh().manual_axes:
        return flash_attention(q, k, v, causal, scale)
    from jax.sharding import PartitionSpec as P

    sizes = dict(mesh.shape)
    spec = P(*[axis if sizes.get(axis, 1) > 1 and dim % sizes[axis] == 0
               else None
               for axis, dim in (("dp", q.shape[0]), ("tp", q.shape[1]))],
             None, None)
    return jax.shard_map(
        lambda q, k, v: flash_attention(q, k, v, causal, scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)(q, k, v)


@register("flash_attention")
def _flash_attention_op(ctx, ins, attrs):
    """Fused attention exposed as a graph op. Q/K/V layout is [B, H, T, Dh]
    (attr layout="bhtd", default) or [B, T, H, Dh] ("bthd" — transpose-free
    from a reshape of [B, T, D], XLA folds the layout into the dots).

    Dispatches to the tuned TPU flash kernel whenever the shape tiles
    (in-model profile on v5e at B128/H8/T512/D64: flash fwd ~1.8 ms vs the
    XLA-fused softmax path's ~1 GB materialized score/prob buffers); the
    XLA path covers shapes the blocked kernels can't tile.
    Differentiable through the kernels' own VJPs."""
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    if attrs.get("__amp_bf16__") and q.dtype == jnp.float32:
        # AMP white-list marking: bf16 QKV matmuls (softmax stays fp32
        # inside the kernels); output stays bf16 like every white-list op
        q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    out_dtype = q.dtype
    causal = attrs.get("causal", False)
    scale = attrs.get("sm_scale", None)
    layout = attrs.get("layout", "bhtd")
    t_axis = 2 if layout == "bhtd" else 1
    Dh = q.shape[-1]
    T = q.shape[t_axis]

    # Sequence parallelism through the descriptor path (SURVEY §5.7, the
    # scale-sequence-length axis): when the step mesh carries an "sp" axis
    # (BuildStrategy.sequence_parallel_degree), self-attention runs as
    # RING attention — K/V blocks rotate over the sp ranks via ppermute
    # while each rank accumulates its Q-shard online-softmax, so the full
    # [T, T] score matrix never exists on any chip. The shard_map is
    # manual over sp only; dp/tp stay GSPMD-auto, and its seq-sharded
    # out_specs seed sharding propagation through the residual stream.
    mesh = getattr(ctx, "mesh", None)
    sp = dict(mesh.shape).get("sp", 1) if mesh is not None else 1
    if sp > 1:
        if T % sp == 0 and q.shape == k.shape \
                and not getattr(ctx, "no_pair_collectives", False):
            from ..parallel.ring_attention import ring_attention_sharded

            qb, kb, vb = ((jnp.swapaxes(t, 1, 2) for t in (q, k, v))
                          if layout == "bthd" else (q, k, v))
            out = ring_attention_sharded(qb, kb, vb, mesh, causal=causal,
                                         sm_scale=scale,
                                         partial_manual=True)
            if layout == "bthd":
                out = jnp.swapaxes(out, 1, 2)
            return {"Out": [out.astype(out_dtype)]}
        if T % sp == 0 and q.shape == k.shape:
            # inside a pipeline stage branch: the ring's ppermute would
            # deadlock (pair collectives rendezvous across all devices),
            # so use the ALL-GATHER sequence-parallel formulation — Q and
            # the output stay seq-sharded over sp (scores O(T^2/sp) per
            # chip), K/V gather to replicated (group-safe) — expressed
            # purely through GSPMD constraints around the shared XLA
            # attention math, no manual collectives
            from jax.sharding import NamedSharding as _NS
            from jax.sharding import PartitionSpec as _P

            from ..parallel.mesh import current_abstract_mesh

            cmesh = current_abstract_mesh(mesh)
            U = _P.UNCONSTRAINED
            seq_spec = (_P(U, "sp", U, U) if layout == "bthd"
                        else _P(U, U, "sp", U))
            repl_spec = (_P(U, None, U, U) if layout == "bthd"
                         else _P(U, U, None, U))
            q = jax.lax.with_sharding_constraint(q, _NS(cmesh, seq_spec))
            k = jax.lax.with_sharding_constraint(k, _NS(cmesh, repl_spec))
            v = jax.lax.with_sharding_constraint(v, _NS(cmesh, repl_spec))
            out = _xla_softmax_attention(q, k, v, layout, causal, scale, Dh)
            out = jax.lax.with_sharding_constraint(out, _NS(cmesh, seq_spec))
            return {"Out": [out.astype(out_dtype)]}
        import warnings

        form = ("all-gather sequence parallelism (pipeline-stage form)"
                if getattr(ctx, "no_pair_collectives", False)
                else "ring attention")
        warnings.warn(
            "sequence_parallel_degree=%d is set but %s cannot engage for "
            "this op (seq %d %% sp != 0, or cross-attention q/k shapes "
            "differ): falling back to per-chip full attention — the sp "
            "mesh ranks replicate this work and the [T, T] scores "
            "materialize per chip" % (sp, form, T),
            RuntimeWarning)

    # registry-dispatched: the tuned kernel when the shape qualifies
    # (the old ad-hoc gate here required q.shape == k.shape, silently
    # dropping the tuned path for cross-attention — the registry's
    # qualification allows non-causal Tq != Tk and logs any
    # disqualification once), lax softmax attention otherwise
    from .kernel_registry import choose as _choose_kernel

    if _choose_kernel("flash_attention", T=T, Tk=k.shape[t_axis],
                      head_dim=Dh, causal=causal):
        if layout == "bthd":
            q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
        out = _flash_on_mesh(q, k, v, causal, scale, mesh)
        if layout == "bthd":
            out = jnp.swapaxes(out, 1, 2)
    else:
        out = _xla_softmax_attention(q, k, v, layout, causal, scale, Dh)
    return {"Out": [out.astype(out_dtype)]}


@register("fused_multihead_attention")
def _fused_multihead_attention(ctx, ins, attrs):
    """The whole self-attention sublayer as ONE op: per-head q/k/v
    projections, (flash) attention, and the output projection. TPU-native
    analogue of the reference's fused attention inference kernels
    (multihead_matmul_op.cu, fused/multihead_matmul_fuse_pass semantics)
    — but used in TRAINING too, because on TPU the fusion is a layout
    property, not just an op-count one: the projections are einsums
    `btd,dhx->bthx` whose output keeps heads as real dot dimensions, so
    the [B,H,T,Dh] operand order the flash kernel needs folds into the
    dot's output layout. The unfused fc+split formulation flattens the
    projection to a 2D dot, the head permutation cannot be a bitcast of
    any 2D layout, and every q/k/v materializes an HBM copy — measured
    ~34 ms/step (10% of device time) at flagship scale.

    Inputs: X [B,T,D]; WQ/WK/WV [D,H,Dh]; WO [H,Dh,D]; optional BQ/BK/BV
    [H,Dh] and BO [D]. Attrs: causal, sm_scale (default Dh^-0.5).
    Output: [B,T,D]. Attention itself (ring-sp dispatch, Pallas/XLA
    fallback) is delegated to the flash_attention op in bthd layout."""
    x = ins["X"][0]
    wq, wk, wv = ins["WQ"][0], ins["WK"][0], ins["WV"][0]
    wo = ins["WO"][0]
    if attrs.get("__amp_bf16__") and x.dtype == jnp.float32:
        x = x.astype(jnp.bfloat16)
    cdt = x.dtype
    Dh = wq.shape[-1]

    def proj(w, b):
        y = jnp.einsum("btd,dhx->bthx", x, w.astype(cdt))
        if b is not None:
            y = y + b.astype(cdt)
        return y

    q = proj(wq, (ins.get("BQ") or [None])[0])
    k = proj(wk, (ins.get("BK") or [None])[0])
    v = proj(wv, (ins.get("BV") or [None])[0])
    ctx_out = get("flash_attention").impl(ctx, {"Q": [q], "K": [k],
                                               "V": [v]}, {
        "causal": bool(attrs.get("causal", False)),
        "sm_scale": attrs.get("sm_scale") or Dh ** -0.5,
        "layout": "bthd"})["Out"][0]
    out = jnp.einsum("bthx,hxd->btd", ctx_out, wo.astype(cdt))
    bo = (ins.get("BO") or [None])[0]
    if bo is not None:
        out = out + bo.astype(cdt)
    return {"Out": [out]}


def _xla_softmax_attention(q, k, v, layout, causal, scale, Dh):
    """XLA-fused softmax attention with the head layout folded into the
    dots — shared by the non-Pallas fallback and the pipeline-safe
    all-gather sequence-parallel path."""
    s = scale if scale is not None else Dh ** -0.5
    qs, ks, vs = (("bhqd", "bhkd", "bhkd") if layout == "bhtd"
                  else ("bqhd", "bkhd", "bkhd"))
    logits = jnp.einsum("%s,%s->bhqk" % (qs, ks), q, k,
                        preferred_element_type=jnp.float32) * s
    if causal:
        Tq, Tk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((Tq, Tk), bool), Tk - Tq)
        logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out_spec = "bhqd" if layout == "bhtd" else "bqhd"
    return jnp.einsum("bhqk,%s->%s" % (vs, out_spec), p, v)
