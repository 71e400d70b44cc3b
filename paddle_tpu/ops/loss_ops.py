"""Loss ops (parity: SURVEY Appendix A "Losses" — operators/{cross_entropy_op,
softmax_with_cross_entropy_op,sigmoid_cross_entropy_with_logits_op,huber_loss,
hinge_loss,log_loss,rank_loss,margin_rank_loss,smooth_l1_loss,kldiv_loss,
bpr_loss,npair_loss,...}.cc).
"""

import functools

import jax
import jax.numpy as jnp
from jax.lax import optimization_barrier

from .registry import register


def _take_label_prob(x, label):
    """Pick prob of the label class: x [N, C], label [N, 1] int or [N, C] soft."""
    if jnp.issubdtype(label.dtype, jnp.integer):
        lab = label.reshape((-1,))
        return jnp.take_along_axis(x, lab[:, None], axis=1)
    return None


@register("cross_entropy", nondiff_inputs=("Label",))
def _cross_entropy(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    soft = attrs.get("soft_label", False)
    ignore_index = attrs.get("ignore_index", -100)
    eps = 1e-12
    if soft:
        loss = -jnp.sum(label * jnp.log(jnp.maximum(x, eps)), axis=-1, keepdims=True)
    else:
        p = _take_label_prob(x, label)
        loss = -jnp.log(jnp.maximum(p, eps))
        lab = label.reshape((-1, 1))
        loss = jnp.where(lab == ignore_index, 0.0, loss)
    return {"Y": [loss]}


@register("cross_entropy2", nondiff_inputs=("Label",))
def _cross_entropy2(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    p = _take_label_prob(x, label)
    loss = -jnp.log(jnp.maximum(p, 1e-12))
    return {"Y": [loss], "MatchX": [p], "XShape": [jnp.zeros((0,) + x.shape, x.dtype)]}


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _hard_label_ce(logits, lab, ignore_index):
    """Mean-free per-position CE with a memory-lean vjp: residuals are the
    LOGITS themselves (bf16 under AMP), not the fp32 log-softmax — for an
    LM head that is the difference between pinning 8G and 4G in HBM.
    Backward recomputes softmax from logits (elementwise + one reduction:
    the cheap kind of remat, matching what XLA's own rematerializer picks
    for the native-path head)."""
    loss, _ = _hard_label_ce_fwd(logits, lab, ignore_index)
    return loss


def _hard_label_ce_fwd(logits, lab, ignore_index):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, lab[..., None].astype(jnp.int32),
                                 axis=-1)
    loss = jnp.where(lab[..., None] == ignore_index, 0.0, -picked)
    return loss, (logits, lab)


def _hard_label_ce_bwd(ignore_index, res, g):
    logits, lab = res
    # barrier: without it XLA CSEs this upcast with the forward's and
    # keeps the full fp32 logits alive from forward to backward — the
    # exact buffer this custom vjp exists to avoid
    logits = optimization_barrier(logits)
    xf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(xf, axis=-1, keepdims=True)
    # dlogits in the LOGITS dtype end to end: softmax values are in [0, 1]
    # where bf16 carries ~3 digits, and keeping the whole chain low
    # precision lets XLA emit one fused elementwise pass (bf16 in, bf16
    # out) instead of materializing a full-vocab fp32 intermediate
    sm = jnp.exp(xf - lse).astype(logits.dtype)
    onehot = jax.nn.one_hot(lab, logits.shape[-1], dtype=logits.dtype)
    gv = jnp.where(lab[..., None] != ignore_index, g, 0.0)
    dlogits = (sm - onehot) * gv.astype(logits.dtype)
    return dlogits, None


_hard_label_ce.defvjp(_hard_label_ce_fwd, _hard_label_ce_bwd)


@register("softmax_with_cross_entropy", nondiff_inputs=("Label",))
def _softmax_with_cross_entropy(ctx, ins, attrs):
    logits, label = ins["Logits"][0], ins["Label"][0]
    soft = attrs.get("soft_label", False)
    ignore_index = attrs.get("ignore_index", -100)
    axis = attrs.get("axis", -1)
    need_softmax = attrs.get("__need_softmax__", True)
    if not soft and axis in (-1, logits.ndim - 1):
        lab = label
        if lab.shape and lab.shape[-1] == 1:
            lab = lab.reshape(lab.shape[:-1])
        loss = _hard_label_ce(logits, lab, ignore_index)
        # Loss stays fp32 even for bf16 logits (black-list AMP
        # semantics): downstream sums over ~1e5 per-token losses would
        # lose ~3 digits in bf16
        if not need_softmax:
            # skipping the discarded side output saves a full fp32
            # [.., vocab] HBM round-trip per step on LM heads
            return {"Loss": [loss]}
        softmax = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        return {"Softmax": [softmax.astype(logits.dtype)], "Loss": [loss]}
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=axis)
    if soft:
        loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
        if not need_softmax:
            return {"Loss": [loss]}
    else:
        lab = label
        ax = axis % logits.ndim
        # hard label carries its singleton class dim at `axis` (reference
        # layout, softmax_with_cross_entropy_op.cc) — move it last to align
        # with the moveaxis'd logp before take_along_axis
        if lab.ndim == logits.ndim and lab.shape[ax] == 1:
            lab = jnp.squeeze(jnp.moveaxis(lab, ax, -1), -1)
        picked = jnp.take_along_axis(
            jnp.moveaxis(logp, ax, -1),
            lab[..., None].astype(jnp.int32), axis=-1)
        loss = jnp.where(lab[..., None] == ignore_index, 0.0, -picked)
        loss = jnp.moveaxis(loss, -1, ax)
        if not need_softmax:
            return {"Loss": [loss]}
    softmax = jnp.exp(logp)
    return {"Softmax": [softmax.astype(logits.dtype)], "Loss": [loss]}


@register("sigmoid_cross_entropy_with_logits", nondiff_inputs=("Label",))
def _sigmoid_ce(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    ignore_index = attrs.get("ignore_index", -100)
    loss = jnp.maximum(x, 0.0) - x * label + jax.nn.softplus(-jnp.abs(x))
    loss = jnp.where(label == ignore_index, 0.0, loss)
    if attrs.get("normalize", False):
        n_valid = jnp.maximum(jnp.sum((label != ignore_index).astype(x.dtype)), 1.0)
        loss = loss * (loss.size / n_valid)
    return {"Out": [loss]}


@register("bpr_loss", nondiff_inputs=("Label",))
def _bpr_loss(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    n, c = x.shape
    pos = jnp.take_along_axis(x, label.reshape((-1, 1)).astype(jnp.int32), axis=1)
    diff = x - pos
    loss = jnp.mean(jax.nn.softplus(diff), axis=1, keepdims=True) * (c / (c - 1.0))
    return {"Y": [loss]}


@register("hinge_loss", nondiff_inputs=("Labels",))
def _hinge_loss(ctx, ins, attrs):
    logits, labels = ins["Logits"][0], ins["Labels"][0]
    return {"Loss": [jnp.maximum(1.0 - (2.0 * labels - 1.0) * logits, 0.0)]}


@register("huber_loss", nondiff_inputs=("Y",))
def _huber_loss(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    delta = attrs.get("delta", 1.0)
    r = y - x
    ab = jnp.abs(r)
    loss = jnp.where(ab <= delta, 0.5 * r * r, delta * (ab - 0.5 * delta))
    return {"Out": [loss], "Residual": [r]}


@register("log_loss", nondiff_inputs=("Labels",))
def _log_loss(ctx, ins, attrs):
    p, label = ins["Predicted"][0], ins["Labels"][0]
    eps = attrs.get("epsilon", 1e-4)
    loss = -label * jnp.log(p + eps) - (1.0 - label) * jnp.log(1.0 - p + eps)
    return {"Loss": [loss]}


@register("rank_loss", nondiff_inputs=("Label",))
def _rank_loss(ctx, ins, attrs):
    label = ins["Label"][0]
    left, right = ins["Left"][0], ins["Right"][0]
    d = left - right
    return {"Out": [jax.nn.softplus(d) - label * d]}


@register("margin_rank_loss", nondiff_inputs=("Label",))
def _margin_rank_loss(ctx, ins, attrs):
    label = ins["Label"][0]
    x1, x2 = ins["X1"][0], ins["X2"][0]
    margin = attrs.get("margin", 0.0)
    out = jnp.maximum(0.0, -label * (x1 - x2) + margin)
    return {"Out": [out], "Activated": [(out > 0).astype(x1.dtype)]}


@register("smooth_l1_loss", nondiff_inputs=("Y",))
def _smooth_l1_loss(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    d = x - y
    if ins.get("InsideWeight"):
        d = d * ins["InsideWeight"][0]
    ab = jnp.abs(d)
    val = jnp.where(ab < 1.0 / s2, 0.5 * s2 * d * d, ab - 0.5 / s2)
    if ins.get("OutsideWeight"):
        val = val * ins["OutsideWeight"][0]
    loss = jnp.sum(val, axis=tuple(range(1, val.ndim))).reshape((-1, 1))
    return {"Out": [loss], "Diff": [d]}


@register("kldiv_loss", nondiff_inputs=("Target",))
def _kldiv_loss(ctx, ins, attrs):
    x, target = ins["X"][0], ins["Target"][0]
    reduction = attrs.get("reduction", "mean")
    loss = jnp.where(target > 0, target * (jnp.log(jnp.maximum(target, 1e-12)) - x), 0.0)
    if reduction == "mean":
        out = jnp.mean(loss).reshape((1,))
    elif reduction == "sum":
        out = jnp.sum(loss).reshape((1,))
    elif reduction == "batchmean":
        out = (jnp.sum(loss) / x.shape[0]).reshape((1,))
    else:
        out = loss
    return {"Loss": [out]}


@register("mse_loss", nondiff_inputs=())
def _mse_loss(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    return {"Out": [(x - y) ** 2]}


@register("npair_loss", nondiff_inputs=("Labels",))
def _npair_loss(ctx, ins, attrs):
    anchor, positive = ins["Anchor"][0], ins["Positive"][0]
    labels = ins["Labels"][0].reshape((-1,))
    l2_reg = attrs.get("l2_reg", 0.002)
    sim = anchor @ positive.T
    eq = (labels[:, None] == labels[None, :]).astype(sim.dtype)
    tgt = eq / jnp.sum(eq, axis=1, keepdims=True)
    logp = jax.nn.log_softmax(sim, axis=1)
    ce = -jnp.sum(tgt * logp, axis=1).mean()
    reg = l2_reg * (jnp.mean(jnp.sum(anchor * anchor, 1))
                    + jnp.mean(jnp.sum(positive * positive, 1))) * 0.25
    return {"Out": [(ce + reg).reshape((1,))]}


@register("teacher_student_sigmoid_loss", nondiff_inputs=("Label",))
def _ts_sigmoid_loss(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    soft_max_up = attrs.get("soft_max_up_bound", 15.0)
    soft_max_lo = attrs.get("soft_max_lower_bound", -15.0)
    z = jnp.clip(x, soft_max_lo, soft_max_up)
    teacher = jnp.where(label > 0.0, label, 0.0)
    student = (label > -1.0).astype(x.dtype)
    loss = jax.nn.softplus(z) - z * student + jax.nn.softplus(z) - z * teacher
    return {"Y": [loss]}


@register("dice_loss_helper")
def _dice_loss_helper(ctx, ins, attrs):
    # dice loss is composed in layers; helper kept for completeness
    x, label = ins["X"][0], ins["Label"][0]
    eps = attrs.get("epsilon", 1e-5)
    inter = jnp.sum(x * label, axis=tuple(range(1, x.ndim)))
    union = jnp.sum(x + label, axis=tuple(range(1, x.ndim)))
    return {"Out": [1.0 - (2.0 * inter + eps) / (union + eps)]}
