"""State-space and gated-layer op kernels: `selective_scan` (the Mamba-1
recurrence; Pallas on the TPU, a chunked `lax.scan` elsewhere),
`causal_conv1d` (depthwise, along time) and `rms_norm`.

Reference parity: none — the reference predates state-space layers; the
equations are Gu & Dao 2023 (arXiv:2312.00752) section 3 and the RMSNorm of
Zhang & Sennrich 2019. Gradients come from the generic `grad_of` op: the
scan's custom VJP (ops/pallas/selective_scan.py) or jax's own.
"""
import jax.numpy as jnp
from jax import lax

from .registry import register_op, register_shape_rule
from .shape_rules import ShapeError, TensorMeta, _x


@register_op("selective_scan")
def _selective_scan(ctx, ins, attrs):
    from .pallas.selective_scan import selective_scan
    return {"Out": selective_scan(
        ins["X"][0], ins["Delta"][0], ins["A"][0], ins["B"][0], ins["C"][0],
        ins["D"][0])}


@register_shape_rule("selective_scan")
def _selective_scan_rule(op, ins, attrs):
    x, delta, a = _x(ins), _x(ins, "Delta"), _x(ins, "A")
    b, c, d = _x(ins, "B"), _x(ins, "C"), _x(ins, "D")
    known = [m.shape for m in (x, delta, a, b, c, d)]
    if all(s is not None and None not in s and -1 not in s[1:]
           for s in known):
        _bt, e, n = x.shape[:2], x.shape[2], a.shape[1]
        if (len(x.shape) != 3 or tuple(delta.shape) != tuple(x.shape)
                or tuple(a.shape) != (e, n)
                or tuple(b.shape[1:]) != (x.shape[1], n)
                or tuple(c.shape) != tuple(b.shape)
                or tuple(d.shape) != (e,)):
            raise ShapeError(
                "selective_scan wants X, Delta (B,T,E), A (E,N), B, C "
                "(B,T,N), D (E,); got %s" % (known,))
    return {"Out": [TensorMeta(x.shape, x.dtype)]}


@register_op("causal_conv1d")
def _causal_conv1d(ctx, ins, attrs):
    """Depthwise causal convolution along time: Out[b, t, e] = Bias[e] +
    sum_k W[k, e] * X[b, t - (K-1) + k, e], reading zeros before t = 0.
    X: (B, T, E), W: (K, E). K shifted multiply-adds in float32 (K is 3 or
    4 in the models here, with or without a bias; a silu behind it is the
    layer's `act`: a `conv_general_dilated` with E groups of one channel
    would send a bandwidth-bound pass to the MXU's layout)."""
    x, w = ins["X"][0], ins["W"][0]
    k, t = w.shape[0], x.shape[1]
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    out = sum(xf[:, i:i + t] * wf[i] for i in range(k))
    if ins.get("Bias"):
        out = out + ins["Bias"][0].astype(jnp.float32)
    return {"Out": out.astype(x.dtype)}


@register_shape_rule("causal_conv1d")
def _causal_conv1d_rule(op, ins, attrs):
    x, w = _x(ins), _x(ins, "W")
    if x.shape is not None and w.shape is not None:
        if len(x.shape) != 3 or len(w.shape) != 2 \
                or (x.shape[2] is not None and w.shape[1] is not None
                    and x.shape[2] != w.shape[1]):
            raise ShapeError("causal_conv1d wants X (B,T,E) and W (K,E), K "
                             "the width (3 or 4 in the models here); got %s "
                             "and %s" % (x.shape, w.shape))
    return {"Out": [TensorMeta(x.shape, x.dtype)]}


@register_op("rms_norm")
def _rms_norm(ctx, ins, attrs):
    """Y = X / sqrt(mean(X^2 over the last axis) + epsilon) * Scale, in
    float32, back in X's dtype."""
    x = ins["X"][0]
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                       + attrs.get("epsilon", 1e-5))
    if ins.get("Scale"):
        y = y * ins["Scale"][0].astype(jnp.float32)
    return {"Y": y.astype(x.dtype)}


@register_shape_rule("rms_norm")
def _rms_norm_rule(op, ins, attrs):
    x = _x(ins)
    return {"Y": [TensorMeta(x.shape, x.dtype)]}
