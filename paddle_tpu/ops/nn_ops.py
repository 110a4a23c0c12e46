"""Neural-net op kernels: conv, pool, normalization, losses, embedding.

Reference parity: paddle/fluid/operators/{conv_op,pool_op,batch_norm_op,
layer_norm_op,group_norm_op,instance_norm_op,softmax_op,cross_entropy_op,
softmax_with_cross_entropy_op,dropout_op,lookup_table_op,...}. The reference
dispatches to cuDNN; here the kernels are lax convolution/reduce-window
primitives that XLA maps onto the MXU directly.
"""
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register_op
from . import head_loss
from ..framework.dtypes import to_jax_dtype


def _x(ins, slot="X"):
    return ins[slot][0]


def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v), int(v))


# ---------------------------------------------------------------------------
# convolution
# ---------------------------------------------------------------------------

@register_op("conv2d")
def _conv2d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dil = _pair(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1) or 1
    out = lax.conv_general_dilated(
        x, w, window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dil, feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        preferred_element_type=jnp.float32
        if x.dtype == jnp.bfloat16 else None)
    out = out.astype(x.dtype)
    return {"Output": out}


@register_op("depthwise_conv2d")
def _depthwise_conv2d(ctx, ins, attrs):
    return _conv2d(ctx, ins, attrs)


def _conv_transpose_nd(x, w, strides, pads, dil, groups, dn, out_sp=None):
    """Fluid's conv_transpose IS the input-gradient of the forward conv
    (ref conv_transpose_op.h computes it with col2im); building it as the
    actual vjp of lax.conv_general_dilated is exact for every
    stride/padding/dilation/groups combination and stays differentiable
    (vjp-of-vjp). Filter layout: (in_c, out_c/g, *k). out_sp overrides the
    derived spatial output size (ref output_size attr) — any size whose
    forward conv maps back to x's extent is valid."""
    k_sp = w.shape[2:]
    if out_sp is None:
        out_sp = tuple(
            (x.shape[2 + i] - 1) * strides[i] - 2 * pads[i] +
            dil[i] * (k_sp[i] - 1) + 1 for i in range(len(k_sp)))
    out_shape = (x.shape[0], w.shape[1] * groups) + out_sp

    def fwd(y):
        return lax.conv_general_dilated(
            y, w, window_strides=strides,
            padding=[(p, p) for p in pads], rhs_dilation=dil,
            feature_group_count=groups, dimension_numbers=dn)

    _, vjp = jax.vjp(fwd, jnp.zeros(out_shape, x.dtype))
    return vjp(x)[0]


@register_op("conv2d_transpose")
def _conv2d_transpose(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dil = _pair(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1) or 1
    out_sp = attrs.get("output_size") or None
    out = _conv_transpose_nd(x, w, strides, pads, dil, groups,
                             ("NCHW", "OIHW", "NCHW"),
                             out_sp=None if out_sp is None
                             else tuple(out_sp))
    return {"Output": out}


@register_op("conv3d")
def _conv3d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = tuple(attrs.get("strides", [1, 1, 1]))
    pads = tuple(attrs.get("paddings", [0, 0, 0]))
    dil = tuple(attrs.get("dilations", [1, 1, 1]))
    groups = attrs.get("groups", 1) or 1
    out = lax.conv_general_dilated(
        x, w, window_strides=strides,
        padding=[(p, p) for p in pads], rhs_dilation=dil,
        feature_group_count=groups,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))
    return {"Output": out}


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------

@register_op("pool2d")
def _pool2d(ctx, ins, attrs):
    x = _x(ins)
    ptype = attrs.get("pooling_type", "max")
    if attrs.get("global_pooling", False) or (
            attrs.get("adaptive", False) and
            tuple(attrs.get("ksize", [1, 1])) == (1, 1)):
        fn = jnp.max if ptype == "max" else jnp.mean
        return {"Out": fn(x, axis=(2, 3), keepdims=True)}
    ks = _pair(attrs.get("ksize", [2, 2]))
    strides = _pair(attrs.get("strides", ks))
    pads = _pair(attrs.get("paddings", [0, 0]))
    if attrs.get("adaptive", False):
        oh, ow = _pair(attrs["ksize"])
        h, w = x.shape[2], x.shape[3]
        if h % oh or w % ow:
            raise NotImplementedError(
                "adaptive pool2d needs input divisible by output size "
                "(got %sx%s -> %sx%s)" % (h, w, oh, ow))
        x5 = x.reshape(x.shape[0], x.shape[1], oh, h // oh, ow, w // ow)
        fn = jnp.max if ptype == "max" else jnp.mean
        return {"Out": fn(x5, axis=(3, 5))}
    window = (1, 1) + ks
    strides4 = (1, 1) + strides
    padding = ((0, 0), (0, 0), (pads[0], pads[0]), (pads[1], pads[1]))
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) \
            else jnp.iinfo(x.dtype).min
        out = lax.reduce_window(x, init, lax.max, window, strides4, padding)
    else:
        s = lax.reduce_window(x, 0.0, lax.add, window, strides4, padding)
        if attrs.get("exclusive", True):
            ones = jnp.ones_like(x)
            cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides4,
                                    padding)
            out = s / cnt
        else:
            out = s / (ks[0] * ks[1])
    return {"Out": out}


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

@register_op("batch_norm", nondiff=("Mean", "Variance"))
def _batch_norm(ctx, ins, attrs):
    x = _x(ins)
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean, var = ins["Mean"][0], ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    layout = attrs.get("data_layout", "NCHW")
    is_test = attrs.get("is_test", False)
    c_axis = 1 if layout == "NCHW" else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != c_axis)
    bshape = [1] * x.ndim
    bshape[c_axis] = x.shape[c_axis]

    if is_test or attrs.get("use_global_stats", False):
        use_mean, use_var = mean, var
        mean_out, var_out = mean, var
        saved_mean, saved_var = mean, var
    else:
        xf = x.astype(jnp.float32)
        use_mean = jnp.mean(xf, axis=axes)
        use_var = jnp.var(xf, axis=axes)
        mean_out = mean * momentum + use_mean * (1 - momentum)
        var_out = var * momentum + use_var * (1 - momentum)
        saved_mean, saved_var = use_mean, use_var
    inv = lax.rsqrt(use_var.astype(jnp.float32) + eps)
    y = (x.astype(jnp.float32) - use_mean.reshape(bshape)) * \
        (inv * scale.astype(jnp.float32)).reshape(bshape) + \
        bias.astype(jnp.float32).reshape(bshape)
    return {"Y": y.astype(x.dtype),
            "MeanOut": lax.stop_gradient(mean_out),
            "VarianceOut": lax.stop_gradient(var_out),
            "SavedMean": lax.stop_gradient(saved_mean),
            "SavedVariance": lax.stop_gradient(saved_var)}


@register_op("layer_norm")
def _layer_norm(ctx, ins, attrs):
    x = _x(ins)
    eps = attrs.get("epsilon", 1e-5)
    begin = attrs.get("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    norm_shape = x.shape[begin:]
    if ins.get("Scale"):
        y = y * ins["Scale"][0].reshape(norm_shape).astype(jnp.float32)
    if ins.get("Bias"):
        y = y + ins["Bias"][0].reshape(norm_shape).astype(jnp.float32)
    return {"Y": y.astype(x.dtype),
            "Mean": mean.reshape(x.shape[:begin]),
            "Variance": var.reshape(x.shape[:begin])}


@register_op("group_norm")
def _group_norm(ctx, ins, attrs):
    x = _x(ins)  # NCHW
    g = attrs.get("groups", 1)
    eps = attrs.get("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape((n, g, c // g) + x.shape[2:])
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    y = ((xg - mean) * lax.rsqrt(var + eps)).reshape(x.shape)
    bshape = [1, c] + [1] * (x.ndim - 2)
    if ins.get("Scale"):
        y = y * ins["Scale"][0].reshape(bshape)
    if ins.get("Bias"):
        y = y + ins["Bias"][0].reshape(bshape)
    return {"Y": y, "Mean": mean.reshape(n, g), "Variance": var.reshape(n, g)}


@register_op("instance_norm")
def _instance_norm(ctx, ins, attrs):
    x = _x(ins)
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    y = (x - mean) * lax.rsqrt(var + eps)
    c = x.shape[1]
    bshape = [1, c] + [1] * (x.ndim - 2)
    if ins.get("Scale"):
        y = y * ins["Scale"][0].reshape(bshape)
    if ins.get("Bias"):
        y = y + ins["Bias"][0].reshape(bshape)
    return {"Y": y, "SavedMean": mean, "SavedVariance": var}


@register_op("l2_normalize")
def _l2_normalize(ctx, ins, attrs):
    x = _x(ins)
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True))
    return {"Out": x / jnp.maximum(norm, eps), "Norm": norm}


# ---------------------------------------------------------------------------
# softmax & losses
# ---------------------------------------------------------------------------

@register_op("softmax")
def _softmax(ctx, ins, attrs):
    return {"Out": jax.nn.softmax(_x(ins), axis=attrs.get("axis", -1))}


@register_op("log_softmax")
def _log_softmax(ctx, ins, attrs):
    return {"Out": jax.nn.log_softmax(_x(ins), axis=attrs.get("axis", -1))}


@register_op("cross_entropy", nondiff=("Label",))
def _cross_entropy(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(jnp.maximum(x, 1e-20)), axis=-1,
                        keepdims=True)
    else:
        lbl = label.reshape(label.shape[:-1]) if label.shape[-1] == 1 \
            else label
        picked = jnp.take_along_axis(
            x, lbl[..., None].astype(jnp.int32), axis=-1)
        loss = -jnp.log(jnp.maximum(picked, 1e-20))
        ignore = attrs.get("ignore_index", -100)
        loss = jnp.where(lbl[..., None] == ignore, 0.0, loss)
    return {"Y": loss}


@register_op("softmax_with_cross_entropy", nondiff=("Label",))
def _softmax_with_cross_entropy(ctx, ins, attrs):
    logits, label = ins["Logits"][0], ins["Label"][0]
    axis = attrs.get("axis", -1)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=axis)
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
    else:
        lbl = label
        squeeze = lbl.ndim == logits.ndim and lbl.shape[axis] == 1
        if squeeze:
            lbl = jnp.squeeze(lbl, axis=axis)
        picked = jnp.take_along_axis(
            logp, lbl[..., None].astype(jnp.int32), axis=axis)
        loss = -picked
        ignore = attrs.get("ignore_index", -100)
        loss = jnp.where(lbl[..., None] == ignore, 0.0, loss)
    return {"Softmax": jnp.exp(logp).astype(logits.dtype),
            "Loss": loss.astype(logits.dtype)}


def _record_head_plan(hidden, weight, form, cast_bf16):
    """One `head.plan` record a lowering of the head, while obs is on:
    which form engaged, and in how many blocks of how many rows the
    ``[tokens, vocab]`` logits are held."""
    from ..framework import obs
    if obs.enabled():
        now, t = obs.now(), hidden.shape[0]
        rows = head_loss.block_rows(t) if form == "weighted" else t
        obs.record("head.plan", now, now, rows=t, vocab=weight.shape[0],
                   block_rows=rows, blocks=t // rows, form=form,
                   operand_dtype="bfloat16" if cast_bf16
                   else str(hidden.dtype))


@register_op("fused_mlm_head_loss", nondiff=("Label", "TokenWeight"))
def _fused_mlm_head_loss(ctx, ins, attrs):
    """LM/MLM head + softmax CE in one op: ``Hidden (T, D) @ Weight^T
    (+ Bias)`` -> ``Loss``, in one of two forms, chosen by what the
    program passes.

    Per-token form (no ``TokenWeight``): Loss is (T, 1), by the matmul
    + softmax_with_cross_entropy chain the op replaced in models/bert
    (same math); it holds the ``[tokens, vocab]`` logits from its forward
    to its backward.

    Weighted form (``TokenWeight (T, 1)``, no gradient): Loss is the
    scalar ``Σ_t w_t · ce_t``, shape [1]. Its cotangent is a scalar, so
    ops/head_loss.py forms the gradients block by block over the token
    axis in the forward pass and no ``[tokens, vocab]`` array outlives a
    block (models/gpt, models/phi4flash).

    Weight is the (V, D) tied embedding table (``transpose_y=True``
    matmul layout); attr ``cast_bf16`` runs the projection in bf16 with
    f32 accumulation (the _mlm_decode trick)."""
    hidden, weight = ins["Hidden"][0], ins["Weight"][0]
    label = ins["Label"][0]
    bias = ins["Bias"][0] if ins.get("Bias") else None
    lbl = label.reshape(label.shape[:-1]) if label.ndim > 1 and \
        label.shape[-1] == 1 else label
    token_weight = ins["TokenWeight"][0] if ins.get("TokenWeight") else None
    cast_bf16 = attrs.get("cast_bf16", False)
    h, w = hidden, weight
    if cast_bf16:
        h = h.astype(jnp.bfloat16)
        w = w.astype(jnp.bfloat16)
    if token_weight is not None:
        _record_head_plan(hidden, weight, "weighted", cast_bf16)
        loss = head_loss.weighted_head_loss(
            hidden, weight, bias, lbl, token_weight, cast_bf16)
        return {"Loss": loss.reshape((1,))}
    _record_head_plan(hidden, weight, "per_token", cast_bf16)
    # the exact chain the models used to emit — matmul (transpose_y, f32
    # accumulation under cast_bf16) + bias + log_softmax gather
    logits = jnp.matmul(h, w.T,
                        preferred_element_type=jnp.float32) \
        .astype(jnp.float32)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, lbl[..., None].astype(jnp.int32), axis=-1)
    return {"Loss": -picked}


@register_op("sigmoid_cross_entropy_with_logits", nondiff=("Label",))
def _sigmoid_ce(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore = attrs.get("ignore_index", -100)
    loss = jnp.where(label == ignore, 0.0, loss)
    if attrs.get("normalize", False):
        n = jnp.maximum(jnp.sum(label != ignore), 1)
        loss = loss / n
    return {"Out": loss}


@register_op("square_error_cost")
def _square_error_cost(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    return {"Out": jnp.square(x - y)}


@register_op("smooth_l1_loss", nondiff=("Y",))
def _smooth_l1(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    d = x - y
    if ins.get("InsideWeight"):
        d = d * ins["InsideWeight"][0]
    ad = jnp.abs(d)
    loss = jnp.where(ad < 1.0 / s2, 0.5 * s2 * d * d, ad - 0.5 / s2)
    if ins.get("OutsideWeight"):
        loss = loss * ins["OutsideWeight"][0]
    return {"Out": jnp.sum(loss, axis=tuple(range(1, x.ndim)),
                           keepdims=False)[..., None],
            "Diff": d}


@register_op("huber_loss", nondiff=("Y",))
def _huber(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    delta = attrs.get("delta", 1.0)
    d = y - x
    ad = jnp.abs(d)
    loss = jnp.where(ad <= delta, 0.5 * d * d,
                     delta * (ad - 0.5 * delta))
    return {"Out": loss, "Residual": d}


@register_op("log_loss", nondiff=("Labels",))
def _log_loss(ctx, ins, attrs):
    p, label = ins["Predicted"][0], ins["Labels"][0]
    eps = attrs.get("epsilon", 1e-4)
    return {"Loss": -label * jnp.log(p + eps) -
            (1 - label) * jnp.log(1 - p + eps)}


@register_op("kldiv_loss", nondiff=("Target",))
def _kldiv(ctx, ins, attrs):
    x, target = ins["X"][0], ins["Target"][0]
    loss = target * (jnp.log(jnp.maximum(target, 1e-20)) - x)
    loss = jnp.where(target <= 0, 0.0, loss)
    red = attrs.get("reduction", "mean")
    if red == "mean":
        loss = jnp.mean(loss)
    elif red == "sum":
        loss = jnp.sum(loss)
    elif red == "batchmean":
        loss = jnp.sum(loss) / x.shape[0]
    return {"Loss": loss}


@register_op("bpr_loss", nondiff=("Label",))
def _bpr_loss(ctx, ins, attrs):
    """loss_i = -(1/(C-1)) * sum_{j != label_i} log sigmoid(x_pos - x_j)
    (ref bpr_loss_op.h:63-77: the positive item's logit minus each
    NEGATIVE's, label column excluded from the sum). The round-5 oracle
    sweep caught this kernel with the sigmoid argument flipped and the
    label term included at 1/C weight."""
    x, label = ins["X"][0], ins["Label"][0]
    n, c = x.shape
    lbl = label.reshape(n).astype(jnp.int32)
    pos = jnp.take_along_axis(x, lbl[:, None], axis=1)
    logsig = jax.nn.log_sigmoid(pos - x)          # (N, C)
    neg_mask = 1.0 - jax.nn.one_hot(lbl, c, dtype=x.dtype)
    loss = -jnp.sum(logsig * neg_mask, axis=1, keepdims=True) / (c - 1)
    return {"Y": loss}


@register_op("margin_rank_loss", nondiff=("Label",))
def _margin_rank(ctx, ins, attrs):
    x1, x2, label = ins["X1"][0], ins["X2"][0], ins["Label"][0]
    margin = attrs.get("margin", 0.0)
    out = jax.nn.relu(-label * (x1 - x2) + margin)
    return {"Out": out, "Activated": (out > 0).astype(x1.dtype)}


@register_op("label_smooth", nondiff=("PriorDist",))
def _label_smooth(ctx, ins, attrs):
    x = _x(ins)
    eps = attrs.get("epsilon", 0.0)
    k = x.shape[-1]
    if ins.get("PriorDist"):
        prior = ins["PriorDist"][0]
        return {"Out": (1 - eps) * x + eps * prior}
    return {"Out": (1 - eps) * x + eps / k}


@register_op("mse_loss", nondiff=("Label",))
def _mse(ctx, ins, attrs):
    x, label = ins["Input"][0], ins["Label"][0]
    return {"Out": jnp.square(x - label)}


# ---------------------------------------------------------------------------
# embedding (reference: lookup_table_op.cc; grads become scatter-adds which
# XLA turns into efficient TPU one-hot matmuls / dynamic-update fusions)
# ---------------------------------------------------------------------------

@register_op("lookup_table", nondiff=("Ids",))
def _lookup_table(ctx, ins, attrs):
    w, ids = ins["W"][0], ins["Ids"][0]
    squeeze = ids.ndim >= 2 and ids.shape[-1] == 1
    if squeeze:
        ids = ids.reshape(ids.shape[:-1])
    ids = ids.astype(jnp.int32)
    padding_idx = attrs.get("padding_idx", -1)
    out = jnp.take(w, ids, axis=0)
    if padding_idx is not None and padding_idx >= 0:
        out = jnp.where((ids == padding_idx)[..., None], 0.0, out)
    return {"Out": out}


@register_op("lookup_table_v2", nondiff=("Ids",))
def _lookup_table_v2(ctx, ins, attrs):
    return _lookup_table(ctx, ins, attrs)


@register_op("one_hot", nondiff=("X",))
def _one_hot(ctx, ins, attrs):
    x = _x(ins)
    if x.ndim >= 2 and x.shape[-1] == 1:
        x = x.reshape(x.shape[:-1])
    return {"Out": jax.nn.one_hot(x.astype(jnp.int32), attrs["depth"],
                                  dtype=to_jax_dtype(
                                      attrs.get("dtype", "float32")))}


# ---------------------------------------------------------------------------
# dropout & friends
# ---------------------------------------------------------------------------

_RBG_PROBE = {}


def _rbg_supported():
    """One eager probe per backend: RngBitGenerator availability surfaces
    at COMPILE time, so a trace-time try/except around the traced op could
    never catch it — run a tiny real computation once instead
    (compile-time eval: the caller is usually inside a jit trace, where
    the probe would otherwise be staged, not run). Only the runtime's
    "not implemented on this backend" means threefry, and on a TPU — whose
    hardware generator is the reason this path exists — it is an error,
    not a downgrade."""
    backend = jax.default_backend()
    ok = _RBG_PROBE.get(backend)
    if ok is None:
        try:
            with jax.ensure_compile_time_eval():
                k = jax.random.wrap_key_data(jnp.zeros(4, jnp.uint32),
                                             impl="rbg")
                np.asarray(jax.random.bernoulli(k, 0.5, (8,)))
            ok = True
        except jax.errors.JaxRuntimeError as e:
            if backend == "tpu" or "UNIMPLEMENTED" not in str(e):
                raise
            ok = False
        _RBG_PROBE[backend] = ok
    return ok


def _fast_keep_mask(key, p_keep, shape):
    """Bernoulli(p_keep) via the hardware RNG ('rbg' PRNG impl):
    counter-based threefry costs ~40% of a BERT-base train step in
    per-layer mask generation (measured 1014 -> 1416 samples/s on v5e with
    dropout off); the HW generator makes masks nearly free. Masks stay
    deterministic per (key, backend, compilation) — the per-op key
    derivation in framework/trace.py is unchanged — but unlike threefry
    the bits are NOT invariant across shardings/compilations (the same
    trade T5X/praxis make with unsafe_rbg). PADDLE_TPU_FAST_DROPOUT=0
    restores fully sharding-invariant threefry masks."""
    import os
    if os.environ.get("PADDLE_TPU_FAST_DROPOUT", "1") in ("0", "false"):
        return jax.random.bernoulli(key, p_keep, shape)
    if not _rbg_supported():
        return jax.random.bernoulli(key, p_keep, shape)
    kd = jax.random.key_data(key).reshape(-1).astype(jnp.uint32)
    k4 = jnp.concatenate([kd, kd])[:4]
    rbg_key = jax.random.wrap_key_data(k4, impl="rbg")
    return jax.random.bernoulli(rbg_key, p_keep, shape)


@register_op("dropout", uses_rng=True)
def _dropout(ctx, ins, attrs):
    x = _x(ins)
    p = attrs.get("dropout_prob", 0.5)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if attrs.get("is_test", False):
        if impl == "upscale_in_train":
            return {"Out": x, "Mask": jnp.ones_like(x, dtype=jnp.uint8)}
        return {"Out": x * (1.0 - p),
                "Mask": jnp.ones_like(x, dtype=jnp.uint8)}
    if p <= 0.0:
        return {"Out": x, "Mask": jnp.ones_like(x, dtype=jnp.uint8)}
    keep = _fast_keep_mask(ctx.rng(), 1.0 - p, x.shape)
    if impl == "upscale_in_train":
        out = jnp.where(keep, x / (1.0 - p), 0.0)
    else:
        out = jnp.where(keep, x, 0.0)
    return {"Out": out.astype(x.dtype), "Mask": keep.astype(jnp.uint8)}


@register_op("pad")
def _pad(ctx, ins, attrs):
    x = _x(ins)
    paddings = attrs["paddings"]
    pv = attrs.get("pad_value", 0.0)
    cfg = [(paddings[2 * i], paddings[2 * i + 1]) for i in range(x.ndim)]
    return {"Out": jnp.pad(x, cfg, constant_values=pv)}


@register_op("pad2d")
def _pad2d(ctx, ins, attrs):
    x = _x(ins)
    p = attrs["paddings"]  # [top, bottom, left, right]
    mode = attrs.get("mode", "constant")
    cfg = [(0, 0), (0, 0), (p[0], p[1]), (p[2], p[3])]
    if mode == "constant":
        return {"Out": jnp.pad(x, cfg,
                               constant_values=attrs.get("pad_value", 0.0))}
    jmode = {"reflect": "reflect", "edge": "edge"}[mode]
    return {"Out": jnp.pad(x, cfg, mode=jmode)}


def _interp_src(out_size, in_size, align_corners, align_mode):
    """Source sampling coordinates for one axis — the reference's three
    conventions (interpolate_op.h:80-163): align_corners uses the
    (in-1)/(out-1) corner-pinned ratio; otherwise ratio=in/out with
    align_mode 0 = half-pixel centers, align_mode 1 = src = ratio*dst."""
    i = jnp.arange(out_size, dtype=jnp.float32)
    if align_corners:
        return i * ((in_size - 1) / max(out_size - 1, 1))
    ratio = in_size / out_size
    if align_mode == 0:
        return jnp.clip((i + 0.5) * ratio - 0.5, 0.0, in_size - 1.0)
    return i * ratio


def _lin_axis(x, out_size, axis, align_corners, align_mode):
    in_size = x.shape[axis]
    src = _interp_src(out_size, in_size, align_corners, align_mode)
    lo = jnp.floor(src).astype(jnp.int32)
    lo = jnp.clip(lo, 0, in_size - 1)
    hi = jnp.minimum(lo + 1, in_size - 1)
    # interpolate in float regardless of input dtype (an integer x would
    # truncate the fractions to pure floor-sampling); cast back at the end
    ft = x.dtype if jnp.issubdtype(x.dtype, jnp.floating) else jnp.float32
    d = (src - lo).astype(ft)
    shape = [1] * x.ndim
    shape[axis] = out_size
    d = d.reshape(shape)
    out = (jnp.take(x, lo, axis=axis).astype(ft) * (1 - d) +
           jnp.take(x, hi, axis=axis).astype(ft) * d)
    return out.astype(x.dtype)


@register_op("interp_nearest", nondiff=())
def _interp_nearest(ctx, ins, attrs):
    x = _x(ins)
    oh, ow = attrs["out_h"], attrs["out_w"]
    ac = attrs.get("align_corners", True)
    out = x
    for axis, osz in ((2, oh), (3, ow)):
        in_size = out.shape[axis]
        if ac:
            # reference: src = int(ratio*dst + 0.5), corner-pinned ratio
            idx = jnp.floor(_interp_src(osz, in_size, True, 1)
                            + 0.5).astype(jnp.int32)
        else:
            idx = jnp.floor(_interp_src(osz, in_size, False, 1)
                            ).astype(jnp.int32)
        out = jnp.take(out, jnp.clip(idx, 0, in_size - 1), axis=axis)
    return {"Out": out}


@register_op("interp_bilinear", nondiff=())
def _interp_bilinear(ctx, ins, attrs):
    x = _x(ins)
    oh, ow = attrs["out_h"], attrs["out_w"]
    ac = attrs.get("align_corners", True)
    am = attrs.get("align_mode", 1)
    out = _lin_axis(x, oh, 2, ac, am)
    out = _lin_axis(out, ow, 3, ac, am)
    return {"Out": out}


@register_op("add_position_encoding")
def _add_position_encoding(ctx, ins, attrs):
    x = _x(ins)  # (N, L, D)
    alpha = attrs.get("alpha", 1.0)
    beta = attrs.get("beta", 1.0)
    n, l, d = x.shape
    # pos_offset: incremental decode adds the encoding for absolute position
    # t to a single-token slice (KV-cache path)
    pos = (jnp.arange(l, dtype=jnp.float32)
           + float(attrs.get("pos_offset", 0)))[:, None]
    i = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    angle = pos / jnp.power(10000.0, 2 * i / d)
    pe = jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)
    return {"Out": alpha * x + beta * pe[None, :, :].astype(x.dtype)}
