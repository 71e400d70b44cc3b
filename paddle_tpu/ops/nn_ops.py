"""NN layer ops: softmax, dropout, embedding, norms, fc (parity:
operators/{softmax_op,dropout_op,lookup_table_op,layer_norm_op,batch_norm_op,
group_norm_op,data_norm_op,lrn_op,maxout_op}.cc).

TPU notes: softmax/layer_norm are left to XLA fusion (bandwidth-bound chains
fuse into one pass); batch_norm keeps functional moving-stat updates (the
executor writes MeanOut/VarianceOut back to the persistable store);
lookup_table is a dense take() whose VJP is a scatter-add — the SelectedRows
sparse-grad path of the reference maps to sorted segment-sum under XLA.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.lax import optimization_barrier

from .registry import register, simple_op, np_dtype


@register("softmax")
def _softmax(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    return {"Out": [jax.nn.softmax(x, axis=axis)]}


@register("log_softmax")
def _log_softmax(ctx, ins, attrs):
    x = ins["X"][0]
    return {"Out": [jax.nn.log_softmax(x, axis=attrs.get("axis", -1))]}


@register("dropout", stateful=True)
def _dropout(ctx, ins, attrs):
    x = ins["X"][0]
    p = attrs.get("dropout_prob", 0.5)
    is_test = attrs.get("is_test", False) or ctx.is_test
    impl_type = attrs.get("dropout_implementation", "downgrade_in_infer")
    if is_test:
        if impl_type == "upscale_in_train":
            return {"Out": [x], "Mask": [jnp.ones_like(x)]}
        return {"Out": [x * (1.0 - p)], "Mask": [jnp.ones_like(x)]}
    key = ctx.rng(attrs)
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    mask = keep.astype(x.dtype)
    if impl_type == "upscale_in_train":
        out = jnp.where(keep, x / (1.0 - p), 0.0).astype(x.dtype)
    else:
        out = x * mask
    return {"Out": [out], "Mask": [mask]}


@register("lookup_table", nondiff_inputs=("Ids",))
def _lookup_table(ctx, ins, attrs):
    w, ids = ins["W"][0], ins["Ids"][0]
    # Fluid ids have trailing [..., 1] dim
    if ids.shape and ids.shape[-1] == 1:
        ids = ids.reshape(ids.shape[:-1])
    padding_idx = attrs.get("padding_idx", -1)
    out = jnp.take(w, ids, axis=0)
    if padding_idx is not None and padding_idx >= 0:
        mask = (ids != padding_idx)[..., None].astype(out.dtype)
        out = out * mask
    return {"Out": [out]}


@register("lookup_table_v2", nondiff_inputs=("Ids",))
def _lookup_table_v2(ctx, ins, attrs):
    w, ids = ins["W"][0], ins["Ids"][0]
    out = jnp.take(w, ids, axis=0)
    padding_idx = attrs.get("padding_idx", -1)
    if padding_idx is not None and padding_idx >= 0:
        mask = (ids != padding_idx)[..., None].astype(out.dtype)
        out = out * mask
    return {"Out": [out]}


@register("layer_norm")
def _layer_norm(ctx, ins, attrs):
    x = ins["X"][0]
    begin = attrs.get("begin_norm_axis", 1)
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(begin, x.ndim))
    # keep the stats reduces OUT of the producer's fusion: without this
    # barrier XLA fuses the mean/var epilogue into a preceding matmul
    # fusion, which measurably serializes the dot (flagship FFN pair:
    # 4.06 ms fused-with-stats vs ~1.8 ms behind a barrier — a 2.2x
    # slowdown on the hottest fusions in the step)
    x = optimization_barrier(x)
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.mean((xf - mean) ** 2, axis=axes, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    feat_shape = x.shape[begin:]
    if ins.get("Scale"):
        y = y * ins["Scale"][0].reshape(feat_shape).astype(jnp.float32)
    if ins.get("Bias"):
        y = y + ins["Bias"][0].reshape(feat_shape).astype(jnp.float32)
    return {
        "Y": [y.astype(x.dtype)],
        "Mean": [mean.reshape((-1,))],
        "Variance": [var.reshape((-1,))],
    }


@register("batch_norm", stateful=True)
def _batch_norm(ctx, ins, attrs):
    x = ins["X"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean_in, var_in = ins["Mean"][0], ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False) or ctx.is_test
    data_layout = attrs.get("data_layout", "NCHW")
    use_global = attrs.get("use_global_stats", False) or is_test
    ch_axis = 1 if data_layout == "NCHW" else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    bshape = tuple(x.shape[ch_axis] if i == ch_axis else 1 for i in range(x.ndim))
    xf = x.astype(jnp.float32)
    if use_global:
        mean, var = mean_in, var_in
        mean_out, var_out = mean_in, var_in
        saved_mean = mean
        saved_var = var
    else:
        mean = jnp.mean(xf, axis=axes)
        var = jnp.mean(xf * xf, axis=axes) - mean * mean
        if ctx.data_axis is not None:
            # sync_batch_norm parity (operators/sync_batch_norm_op.cu):
            # cross-replica stats ride an ICI psum instead of NCCL
            mean = jax.lax.pmean(mean, ctx.data_axis)
            var = jax.lax.pmean(var, ctx.data_axis)
        mean_out = mean_in * momentum + mean * (1.0 - momentum)
        var_out = var_in * momentum + var * (1.0 - momentum)
        saved_mean = mean
        saved_var = var
    y = (xf - mean.reshape(bshape)) * jax.lax.rsqrt(var.reshape(bshape) + eps)
    y = y * scale.reshape(bshape) + bias.reshape(bshape)
    return {
        "Y": [y.astype(x.dtype)],
        "MeanOut": [mean_out],
        "VarianceOut": [var_out],
        "SavedMean": [saved_mean],
        "SavedVariance": [saved_var],
    }


@register("group_norm")
def _group_norm(ctx, ins, attrs):
    x = ins["X"][0]  # NCHW
    groups = attrs.get("groups", 1)
    eps = attrs.get("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    spatial = x.shape[2:]
    xg = x.reshape((n, groups, c // groups) + spatial).astype(jnp.float32)
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.mean((xg - mean) ** 2, axis=axes, keepdims=True)
    y = ((xg - mean) * jax.lax.rsqrt(var + eps)).reshape(x.shape)
    bshape = (1, c) + (1,) * len(spatial)
    if ins.get("Scale"):
        y = y * ins["Scale"][0].reshape(bshape)
    if ins.get("Bias"):
        y = y + ins["Bias"][0].reshape(bshape)
    return {
        "Y": [y.astype(x.dtype)],
        "Mean": [mean.reshape((n, groups))],
        "Variance": [var.reshape((n, groups))],
    }


@register("data_norm")
def _data_norm(ctx, ins, attrs):
    x = ins["X"][0]
    bsize = ins["BatchSize"][0]
    bsum = ins["BatchSum"][0]
    bsqs = ins["BatchSquareSum"][0]
    eps = attrs.get("epsilon", 1e-4)
    mean = bsum / bsize
    scale = jax.lax.rsqrt(bsqs / bsize - mean * mean + eps)
    y = (x - mean) * scale
    return {"Y": [y], "Means": [mean], "Scales": [scale]}


@register("lrn")
def _lrn(ctx, ins, attrs):
    x = ins["X"][0]  # NCHW
    n = attrs.get("n", 5)
    k = attrs.get("k", 2.0)
    alpha = attrs.get("alpha", 1e-4)
    beta = attrs.get("beta", 0.75)
    sq = x * x
    half = n // 2
    pad = jnp.pad(sq, [(0, 0), (half, half), (0, 0), (0, 0)])
    acc = jnp.zeros_like(x)
    for i in range(n):
        acc = acc + pad[:, i : i + x.shape[1]]
    mid = (k + alpha * acc) ** beta
    return {"Out": [x / mid], "MidOut": [mid]}


@register("l2_normalize")
def _l2_normalize(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-12)
    norm = jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=True) + eps)
    return {"Out": [x / norm], "Norm": [norm]}


@register("norm")
def _norm(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=True) + eps)
    return {"Out": [x / norm], "Norm": [norm]}


@register("maxout")
def _maxout(ctx, ins, attrs):
    x = ins["X"][0]  # NCHW
    groups = attrs["groups"]
    n, c, h, w = x.shape
    out = x.reshape((n, c // groups, groups, h, w)).max(axis=2)
    return {"Out": [out]}


@register("pixel_shuffle")
def _pixel_shuffle(ctx, ins, attrs):
    x = ins["X"][0]
    r = attrs.get("upscale_factor", 1)
    n, c, h, w = x.shape
    out = x.reshape((n, c // (r * r), r, r, h, w))
    out = out.transpose((0, 1, 4, 2, 5, 3)).reshape((n, c // (r * r), h * r, w * r))
    return {"Out": [out]}


@register("space_to_depth")
def _space_to_depth(ctx, ins, attrs):
    x = ins["X"][0]
    b = attrs["blocksize"]
    n, c, h, w = x.shape
    out = x.reshape((n, c, h // b, b, w // b, b))
    out = out.transpose((0, 3, 5, 1, 2, 4)).reshape((n, c * b * b, h // b, w // b))
    return {"Out": [out]}


@register("shuffle_channel")
def _shuffle_channel(ctx, ins, attrs):
    x = ins["X"][0]
    g = attrs.get("group", 1)
    n, c, h, w = x.shape
    out = x.reshape((n, g, c // g, h, w)).transpose((0, 2, 1, 3, 4)).reshape(x.shape)
    return {"Out": [out]}


@register("temporal_shift")
def _temporal_shift(ctx, ins, attrs):
    x = ins["X"][0]
    seg_num = attrs["seg_num"]
    ratio = attrs.get("shift_ratio", 0.25)
    nt, c, h, w = x.shape
    n = nt // seg_num
    xr = x.reshape((n, seg_num, c, h, w))
    c1 = int(c * ratio)
    c2 = int(c * 2 * ratio)
    pre = jnp.pad(xr[:, :-1, :c1], [(0, 0), (1, 0), (0, 0), (0, 0), (0, 0)])
    post = jnp.pad(xr[:, 1:, c1:c2], [(0, 0), (0, 1), (0, 0), (0, 0), (0, 0)])
    rest = xr[:, :, c2:]
    out = jnp.concatenate([pre, post, rest], axis=2).reshape(x.shape)
    return {"Out": [out]}


@register("add_position_encoding")
def _add_position_encoding(ctx, ins, attrs):
    x = ins["X"][0]  # [B, T, D]
    alpha = attrs.get("alpha", 1.0)
    beta = attrs.get("beta", 1.0)
    b, t, d = x.shape
    pos = np.arange(t)[:, None]
    i = np.arange(d // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * i / d)
    pe = np.concatenate([np.sin(angle), np.cos(angle)], axis=1)
    return {"Out": [alpha * x + beta * jnp.asarray(pe, x.dtype)[None]]}


@register("grid_sampler")
def _grid_sampler(ctx, ins, attrs):
    x, grid = ins["X"][0], ins["Grid"][0]
    n, c, h, w = x.shape
    gx = (grid[..., 0] + 1.0) * (w - 1) / 2.0
    gy = (grid[..., 1] + 1.0) * (h - 1) / 2.0
    x0 = jnp.floor(gx)
    y0 = jnp.floor(gy)
    x1, y1 = x0 + 1, y0 + 1

    def sample(yy, xx):
        yy = jnp.clip(yy, 0, h - 1).astype(jnp.int32)
        xx = jnp.clip(xx, 0, w - 1).astype(jnp.int32)
        bidx = jnp.arange(n)[:, None, None]
        return x[bidx, :, yy, xx]  # [n, gh, gw, c]

    wa = ((x1 - gx) * (y1 - gy))[..., None]
    wb = ((x1 - gx) * (gy - y0))[..., None]
    wc = ((gx - x0) * (y1 - gy))[..., None]
    wd = ((gx - x0) * (gy - y0))[..., None]
    out = (sample(y0, x0) * wa + sample(y1, x0) * wb + sample(y0, x1) * wc
           + sample(y1, x1) * wd)
    return {"Output": [out.transpose((0, 3, 1, 2))]}


@register("affine_channel")
def _affine_channel(ctx, ins, attrs):
    x = ins["X"][0]
    layout = attrs.get("data_layout", "NCHW")
    ch_axis = 1 if layout == "NCHW" else x.ndim - 1
    bshape = tuple(x.shape[ch_axis] if i == ch_axis else 1 for i in range(x.ndim))
    return {"Out": [x * ins["Scale"][0].reshape(bshape)
                    + ins["Bias"][0].reshape(bshape)]}


@register("affine_grid")
def _affine_grid(ctx, ins, attrs):
    theta = ins["Theta"][0]  # [N, 2, 3]
    h, w = attrs["output_shape"][-2:]
    ys = jnp.linspace(-1.0, 1.0, h)
    xs = jnp.linspace(-1.0, 1.0, w)
    gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
    ones = jnp.ones_like(gx)
    base = jnp.stack([gx, gy, ones], axis=-1)  # [h, w, 3]
    grid = jnp.einsum("hwk,nak->nhwa", base, theta)
    return {"Output": [grid]}
