"""Attention op kernels.

Reference parity: the reference composes attention from matmul/softmax ops
(e.g. PaddlePaddle/models transformer, fluid nets.scaled_dot_product_attention).
TPU-native: one fused op so XLA keeps QK^T / softmax / PV in registers, plus
a Pallas flash-attention path (ops/pallas/) for long sequences that tiles the
computation through VMEM without materializing the (T,T) scores in HBM.
"""
import functools

import os

import jax
import jax.numpy as jnp

from .registry import register_op


def _sdpa_xla(q, k, v, mask, scale, causal, window=None):
    # q: (B, Hq, Tq, D), k: (B, Hkv, Tk, D), v: (B, Hkv, Tk, Dv): the same
    # grouped heads, sliding window and value width as the flash kernels
    from .pallas.flash_attention import _repeat_kv, visible_mask
    k, v = _repeat_kv(q, k, v)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        logits = jnp.where(visible_mask(tq, tk, window), logits, -1e30)
    if mask is not None:
        logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


def _sp_routable(impl, q, k, mask, n):
    """Whether this call CAN run sequence-parallel over an n-way axis —
    the env hint must stay a hint: shapes that don't shard keep their
    auto fallback instead of raising inside shard_map."""
    if q.shape[-2] % n or k.shape[-2] % n or q.shape[-2] != k.shape[-2]:
        return False
    if impl == "ulysses":
        if q.shape[1] % n:
            return False
        if mask is not None:
            ax = mask.ndim - 1 if mask.shape[-2] == 1 else mask.ndim - 2
            return mask.shape[ax] % n == 0
        return True
    if mask is not None:
        return mask.shape[-2] == 1 and mask.shape[-1] % n == 0
    return True


@register_op("scaled_dot_product_attention")
def _sdpa(ctx, ins, attrs):
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    mask = ins["Mask"][0] if ins.get("Mask") else None
    scale = attrs.get("scale", None)
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    causal = attrs.get("causal", False)
    window = attrs.get("window", None)
    from .pallas.flash_attention import check_call
    check_call(q.shape, k.shape, v.shape, causal, window)
    plain = (window is None and q.shape[1] == k.shape[1]
             and v.shape[-1] == q.shape[-1])
    impl = attrs.get("impl", "auto")
    if impl == "auto":
        # perf escape hatch: force a path fleet-wide. For ring/ulysses
        # the env value is a HINT, not a hard override — ops that can't
        # run sequence-parallel (additive mask, no sp mesh installed)
        # keep their auto fallback instead of raising.
        env_impl = os.environ.get("PADDLE_TPU_ATTN_IMPL", "auto")
        if env_impl in ("ring", "ulysses"):
            from ..distributed.mesh import get_mesh
            m = get_mesh()
            if m is not None and attrs.get("sp_axis", "sp") in m.axis_names:
                n = m.shape[attrs.get("sp_axis", "sp")]
                if plain and _sp_routable(env_impl, q, k, mask, n):
                    impl = env_impl
        else:
            impl = env_impl
    if impl == "auto" and q.shape[-2] * k.shape[-2] <= 256 * 256:
        # short sequences: XLA's fused attention beats the tiled kernel
        # (measured 1026 vs 912 samples/s on BERT-base seq128, v5e) — the
        # (T,T) tile only pays for itself once it stops fitting in VMEM
        impl = "xla"
    if impl in ("ring", "ulysses"):
        if not plain:
            raise ValueError(
                "fused_attention(impl=%r) runs equal heads, equal widths "
                "and no window; grouped heads, a window or Dv != D need "
                "impl 'auto', 'flash' or 'xla'" % impl)
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
    if impl in ("auto", "flash"):
        # the shape rules above (and flash_attention's own tile guards)
        # choose the path; an error from the chosen kernel propagates
        from .pallas.flash_attention import flash_attention
        return {"Out": flash_attention(q, k, v, mask=mask, scale=scale,
                                       causal=causal, window=window)}
    return {"Out": _sdpa_xla(q, k, v, mask, scale, causal, window)}
