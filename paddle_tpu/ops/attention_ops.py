"""Attention op kernels.

Reference parity: the reference composes attention from matmul/softmax ops
(e.g. PaddlePaddle/models transformer, fluid nets.scaled_dot_product_attention).
TPU-native: one fused op so XLA keeps QK^T / softmax / PV in registers, plus
a Pallas flash-attention path (ops/pallas/) for long sequences that tiles the
computation through VMEM without materializing the (T,T) scores in HBM.
"""
import contextlib
import math

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register_op, register_shape_rule
from .shape_rules import TensorMeta, _x


def _sdpa_xla(q, k, v, mask, scale, causal, window=None,
              block_diffusion=None):
    # q: (B, Hq, Tq, D), k: (B, Hkv, Tk, D), v: (B, Hkv, Tk, Dv): the same
    # grouped heads, sliding window, block-diffusion rule and value width
    # as the flash kernels
    from .pallas.flash_attention import _repeat_kv, visible_mask
    k, v = _repeat_kv(q, k, v)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal or block_diffusion is not None:
        tq, tk = logits.shape[-2], logits.shape[-1]
        logits = jnp.where(visible_mask(tq, tk, window, block_diffusion),
                           logits, -1e30)
    if mask is not None:
        logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


#: the inner scope a WINDOWED call is lowered under (metadata only: the
#: operations and the kernels' names are the same), so that a device trace
#: tells a window layer's flash calls from a full-attention layer's:
#: `.../scaled_dot_product_attention/window_attention/flash_fwd/pallas_call`
WINDOW_SCOPE = "window_attention"


#: the inner scope a BLOCK-DIFFUSION call is lowered under, for the same
#: reason: `.../block_diffusion_attention/flash_fwd/pallas_call`
BLOCK_DIFFUSION_SCOPE = "block_diffusion_attention"


#: what the op's `impl` attr (`layers.fused_attention(impl=)`, a model
#: config's `attn_impl`) may say
IMPLS = ("auto", "flash", "xla", "ring", "ulysses")


@register_op("scaled_dot_product_attention")
def _sdpa(ctx, ins, attrs):
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    mask = ins["Mask"][0] if ins.get("Mask") else None
    scale = attrs.get("scale", None)
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    causal = attrs.get("causal", False)
    window = attrs.get("window", None)
    bd = attrs.get("block_diffusion", None)
    bd = None if bd is None else (int(bd[0]), int(bd[1]))
    from .pallas.flash_attention import (attention_path, check_call,
                                         flash_attention)
    check_call(q.shape, k.shape, v.shape, causal, window, bd, mask)
    plain = (window is None and bd is None and q.shape[1] == k.shape[1]
             and v.shape[-1] == q.shape[-1])
    impl = attrs.get("impl", "auto")
    if impl not in IMPLS:
        raise ValueError("fused_attention(impl=%r): impl is one of %s"
                         % (impl, ", ".join(repr(i) for i in IMPLS)))
    if impl in ("ring", "ulysses"):
        if not plain:
            raise ValueError(
                "fused_attention(impl=%r) runs equal heads, equal widths "
                "and no window; grouped heads, a window, block_diffusion "
                "or Dv != D need impl 'auto', 'flash' or 'xla'" % impl)
        # sequence-parallel attention over the installed mesh's sp axis —
        # the declarative (static-graph) route to the long-context paths
        # in distributed/{ring,ulysses}_attention.py
        from ..distributed.mesh import get_mesh
        axis = attrs.get("sp_axis", "sp")
        mesh = get_mesh()
        if mesh is None or axis not in mesh.axis_names:
            raise ValueError(
                "fused_attention(impl=%r) needs init_mesh/fleet.init with "
                "a %r mesh axis" % (impl, axis))
        if impl == "ring":
            if mask is not None and mask.shape[-2] != 1:
                raise ValueError(
                    "fused_attention(impl='ring') supports key-padding "
                    "masks (..., 1, T) only — the mask's key axis rides "
                    "the ring with K/V; per-query masks need "
                    "impl='ulysses'")
            from ..distributed.ring_attention import ring_attention
            return {"Out": ring_attention(q, k, v, mask=mask, mesh=mesh,
                                          axis_name=axis, causal=causal,
                                          scale=scale)}
        from ..distributed.ulysses_attention import ulysses_attention
        return {"Out": ulysses_attention(q, k, v, mask=mask, mesh=mesh,
                                         axis_name=axis, causal=causal,
                                         scale=scale)}
    with (jax.named_scope(BLOCK_DIFFUSION_SCOPE) if bd is not None
          else contextlib.nullcontext() if window is None
          else jax.named_scope(WINDOW_SCOPE)):
        if impl != "xla":
            # `attention_path` decides from the call's shapes. Its "short"
            # rule is this op's XLA attention; where it finds no tile,
            # `flash_attention` runs its own XLA body (two bodies whose
            # `precision` arguments differ: ROADMAP, named debt)
            from .pallas.interpret import default_interpret
            if attention_path(q.shape, k.shape, v.shape, q.dtype, causal,
                              window, default_interpret(),
                              auto=impl == "auto",
                              block_diffusion=bd).why != "short":
                return {"Out": flash_attention(
                    q, k, v, mask=mask, scale=scale, causal=causal,
                    window=window, block_diffusion=bd)}
        return {"Out": _sdpa_xla(q, k, v, mask, scale, causal, window, bd)}


def rotate_half(x, theta, position_period=None):
    """Rotary positions over the whole head of x (..., T, D), position t at
    row t (t mod `position_period` where one is given: several copies of a
    document side by side, each counted from 0): pairs (i, i + D/2) turn
    by t * theta^(-2i/D). float32 in and out."""
    t, d = x.shape[-2], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    pos = jnp.arange(t) if position_period is None \
        else jnp.arange(t) % int(position_period)
    angle = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


@register_op("rope_qk_norm")
def _rope_qk_norm(ctx, ins, attrs):
    """What stands between the q/k projections and the attention call: an
    RMS norm over each head's D numbers with a learned float32 scale (QScale,
    KScale: (D,); left out where the slot is empty), then rotary positions
    (`rotate_half`), one elementwise pass in float32. Q (B, T, Hq*D) and
    K (B, T, Hkv*D) come back head-major, (B, H, T, D), in their own dtype:
    the layout the attention op reads. With the attr `position_period` = P
    row t turns by t mod P."""
    theta, eps = float(attrs["theta"]), float(attrs.get("epsilon", 1e-5))
    d = int(attrs["head_dim"])
    period = attrs.get("position_period", None)

    def one(x, scale):
        b, t, width = x.shape
        xf = x.astype(jnp.float32).reshape(b, t, width // d, d)
        xf = jnp.transpose(xf, (0, 2, 1, 3))
        if scale:
            xf = xf * jax.lax.rsqrt(
                jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps) \
                * scale[0].astype(jnp.float32)
        return rotate_half(xf, theta, period).astype(x.dtype)

    return {"QOut": one(ins["Q"][0], ins.get("QScale")),
            "KOut": one(ins["K"][0], ins.get("KScale"))}


@register_shape_rule("rope_qk_norm")
def _rope_qk_norm_rule(op, ins, attrs):
    d = int(attrs["head_dim"])
    out = {}
    for slot in ("Q", "K"):
        m = _x(ins, slot)
        shape = None
        if m.shape is not None and len(m.shape) == 3:
            b, t, width = m.shape
            heads = width // d if width not in (None, -1) else None
            shape = (b, heads, t, d)
        out[slot + "Out"] = [TensorMeta(shape, m.dtype)]
    return out


def rotate_part(x, nope_dim, rope_dim, theta):
    """Rotary positions on the LAST `rope_dim` of every `nope_dim + rope_dim`
    numbers of x (..., T, width), position t at row t; the first `nope_dim`
    of each are handed through. Pair i of a part is its neighbouring numbers
    (2i, 2i + 1) (interleaved; `rotate_half` pairs (i, i + D/2) over a whole
    head); it turns by t * theta^(-2i/rope_dim):
    (a, b) -> (a cos - b sin, a sin + b cos). No slice, concat or transpose:
    out = x cos + (x P) sin, P the signed permutation that hands every number
    its partner (-b to a's place, a to b's, nothing where nothing is turned),
    block-diagonal over lane-aligned runs of the width, so the partner comes
    off the MXU exactly (one non-zero product a sum) and the rest is one
    elementwise pass with cos 1 and sin 0 over the part that is not turned.
    x in any float dtype; float32 out, so a bfloat16 caller rounds the
    forward once. The `jax.vjp` pullback of a bfloat16 x rounds its two
    terms (dy cos, and dy sin through the permutation) to bfloat16 apart
    and then their sum: three roundings where the rotation back of dy in
    float32 would make one. On the chip, at the Kimi-VL cell's size, that
    moves no reading of the first gradient (PERF.md section 6, PR 40)."""
    if rope_dim < 2 or rope_dim % 2:
        raise ValueError("partial_rope: rotary positions pair numbers: "
                         "rope_dim %d" % rope_dim)
    t, width = x.shape[-2], x.shape[-1]
    d = nope_dim + rope_dim
    if width % d:
        raise ValueError("partial_rope: width %d is no multiple of "
                         "nope_dim + rope_dim = %d" % (width, d))
    # a run of whole heads that is a whole number of 128-lane tiles, so that
    # splitting the width into runs moves nothing
    run = d * 128 // math.gcd(d, 128)
    run = run if width % run == 0 else d
    lanes = np.arange(run)
    j = lanes % d - nope_dim                    # place inside the part
    second, turned = j % 2 == 1, j >= 0
    inv = np.where(turned, float(theta) ** (-2.0 * (j // 2) / rope_dim), 0.0)
    partner = lanes + np.where(second, -1, 1)
    swap = np.zeros((run, run), np.float32)
    swap[partner[turned], lanes[turned]] = np.where(second, 1.0,
                                                    -1.0)[turned]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    runs = x.reshape(x.shape[:-1] + (width // run, run))
    partners = jax.lax.dot_general(
        runs, jnp.asarray(swap, x.dtype), (((runs.ndim - 1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST
        if x.dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32)
    out = runs.astype(jnp.float32) * jnp.cos(angle)[:, None, :] \
        + partners * jnp.sin(angle)[:, None, :]
    return out.reshape(x.shape)


@register_op("partial_rope")
def _partial_rope(ctx, ins, attrs):
    """Latent attention's decoupled rotary part, between the projections and
    the key's concat: Q (B, T, H * (nope + rope)) has the last `rope_dim`
    numbers of each head turned, KPe (B, T, rope_dim), the one key part all
    heads share, is turned whole (once, before it is broadcast). Same
    shapes and dtypes out, the forward computed in float32 and rounded once
    (`rotate_part`, which says what the pullback rounds); positions are an
    iota over T, nothing is fed from the host."""
    nope, rope = int(attrs["nope_dim"]), int(attrs["rope_dim"])
    theta = float(attrs["theta"])

    def one(x, nope_dim):
        return rotate_part(x, nope_dim, rope, theta).astype(x.dtype)

    return {"QOut": one(ins["Q"][0], nope), "KPeOut": one(ins["KPe"][0], 0)}


@register_shape_rule("partial_rope")
def _partial_rope_rule(op, ins, attrs):
    return {slot + "Out": [TensorMeta(_x(ins, slot).shape,
                                      _x(ins, slot).dtype)]
            for slot in ("Q", "KPe")}
