"""Ring attention — sequence/context parallelism over the mesh.

First-class long-context support: the sequence axis is sharded over mesh
axis "sp"; each device holds a Q/K/V shard and K/V blocks rotate around the
ring via lax.ppermute while partial softmax statistics accumulate in
log-sum-exp form (online softmax). Communication rides ICI neighbor links —
bandwidth-optimal, memory O(T/n) per chip, exact (not approximate) attention.

No reference counterpart (the reference caps at single-device attention);
this is the capability the north star demands for pod-scale long sequences.
"""
import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from .pipeline import _pvary as _vary


def _ring_perm(n):
    """Neighbor rotation i -> i+1; backward MUST replay the forward's exact
    rotation order (both sides call this one factory)."""
    return [(i, (i + 1) % n) for i in range(n)]


def _block_logits(q, kk, my_idx, kv_idx, scale, causal, mm=None):
    """Scaled (and causally masked) logits of the local Q shard against a
    visiting K block. `mm` is the visiting ADDITIVE key-padding mask block
    (..., 1, Tk_block) riding the ring with its K/V block."""
    tl = q.shape[2]
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, kk,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = my_idx * tl + jnp.arange(tl)
        k_pos = kv_idx * tl + jnp.arange(tl)
        mask = q_pos[:, None] >= k_pos[None, :]
        logits = jnp.where(mask[None, None], logits, -1e30)
    if mm is not None:
        logits = logits + mm.astype(jnp.float32)
    return logits


def _ring_forward(q, k, v, axis_name, causal, scale, mask=None):
    """Online-softmax ring pass. Returns (out, lse) where lse is the
    per-row log-sum-exp — the only statistic backward needs. `mask` is
    this shard's additive key-padding block (..., 1, Tk_local); it rides
    the ring with its K/V block."""
    n = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, h, tl, d = q.shape

    acc = _vary(jnp.zeros((b, h, tl, d), jnp.float32), axis_name)
    row_max = _vary(jnp.full((b, h, tl), -jnp.inf, jnp.float32), axis_name)
    row_sum = _vary(jnp.zeros((b, h, tl), jnp.float32), axis_name)

    perm = _ring_perm(n)
    has_mask = mask is not None

    def block(carry, step):
        if has_mask:
            acc, row_max, row_sum, kk, vv, mm = carry
        else:
            acc, row_max, row_sum, kk, vv = carry
            mm = None
        kv_idx = (my_idx - step) % n
        logits = _block_logits(q, kk, my_idx, kv_idx, scale, causal, mm)
        blk_max = jnp.max(logits, axis=-1)
        new_max = jnp.maximum(row_max, blk_max)
        correction = jnp.exp(row_max - new_max)
        p = jnp.exp(logits - new_max[..., None])
        acc = acc * correction[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vv.astype(jnp.float32))
        row_sum = row_sum * correction + jnp.sum(p, axis=-1)
        kk = lax.ppermute(kk, axis_name, perm)
        vv = lax.ppermute(vv, axis_name, perm)
        if has_mask:
            mm = lax.ppermute(mm, axis_name, perm)
            return (acc, new_max, row_sum, kk, vv, mm), None
        return (acc, new_max, row_sum, kk, vv), None

    carry0 = (acc, row_max, row_sum, k, v)
    if has_mask:
        carry0 = carry0 + (mask,)
    carry, _ = lax.scan(block, carry0, jnp.arange(n))
    acc, row_max, row_sum = carry[0], carry[1], carry[2]
    safe_sum = jnp.maximum(row_sum, 1e-30)
    out = acc / safe_sum[..., None]
    lse = row_max + jnp.log(safe_sum)
    return out.astype(q.dtype), lse


def _make_local(axis_name, causal, scale):
    """Per-shard ring attention with a custom vjp that REPLAYS the ring in
    backward (flash-attention-style recompute): residuals are only
    (q, k, v, out, lse) — O(T/n) per chip — never the n visiting K/V
    blocks a plain autodiff-through-scan would stash. dK/dV accumulators
    rotate around the ring in lockstep with their K/V blocks and arrive
    home after n hops with every device's contribution."""

    def _bwd_ring(q, k, v, mask, out, lse, dout):
        """Shared ring-replay backward; mask (or None) rides the ring in
        lockstep with its K/V block exactly as in forward."""
        n = lax.axis_size(axis_name)
        my_idx = lax.axis_index(axis_name)
        dout32 = dout.astype(jnp.float32)
        # delta_i = sum_j dOut_ij * Out_ij (standard flash backward term)
        delta = jnp.sum(dout32 * out.astype(jnp.float32), axis=-1)
        dq0 = _vary(jnp.zeros(q.shape, jnp.float32), axis_name)
        dk0 = _vary(jnp.zeros(k.shape, jnp.float32), axis_name)
        dv0 = _vary(jnp.zeros(v.shape, jnp.float32), axis_name)
        perm = _ring_perm(n)
        has_mask = mask is not None

        def block(carry, step):
            if has_mask:
                dq, kk, vv, dkk, dvv, mm = carry
            else:
                dq, kk, vv, dkk, dvv = carry
                mm = None
            kv_idx = (my_idx - step) % n
            logits = _block_logits(q, kk, my_idx, kv_idx, scale, causal,
                                   mm)
            p = jnp.exp(logits - lse[..., None])      # (B,H,Tq,Tk)
            dvv = dvv + jnp.einsum("bhqk,bhqd->bhkd", p, dout32)
            dp = jnp.einsum("bhqd,bhkd->bhqk", dout32,
                            vv.astype(jnp.float32))
            ds = p * (dp - delta[..., None]) * scale
            dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds,
                                 kk.astype(jnp.float32))
            dkk = dkk + jnp.einsum("bhqk,bhqd->bhkd", ds,
                                   q.astype(jnp.float32))
            kk = lax.ppermute(kk, axis_name, perm)
            vv = lax.ppermute(vv, axis_name, perm)
            dkk = lax.ppermute(dkk, axis_name, perm)
            dvv = lax.ppermute(dvv, axis_name, perm)
            if has_mask:
                mm = lax.ppermute(mm, axis_name, perm)
                return (dq, kk, vv, dkk, dvv, mm), None
            return (dq, kk, vv, dkk, dvv), None

        carry0 = (dq0, k, v, dk0, dv0)
        if has_mask:
            carry0 = carry0 + (mask,)
        carry, _ = lax.scan(block, carry0, jnp.arange(n))
        dq, dk, dv = carry[0], carry[3], carry[4]
        return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)

    @jax.custom_vjp
    def attn(q, k, v):
        out, _ = _ring_forward(q, k, v, axis_name, causal, scale)
        return out

    def fwd(q, k, v):
        out, lse = _ring_forward(q, k, v, axis_name, causal, scale)
        return out, (q, k, v, out, lse)

    def bwd(res, dout):
        q, k, v, out, lse = res
        return _bwd_ring(q, k, v, None, out, lse, dout)

    attn.defvjp(fwd, bwd)

    @jax.custom_vjp
    def attn_masked(q, k, v, mask):
        out, _ = _ring_forward(q, k, v, axis_name, causal, scale, mask)
        return out

    def fwd_m(q, k, v, mask):
        out, lse = _ring_forward(q, k, v, axis_name, causal, scale, mask)
        return out, (q, k, v, mask, out, lse)

    def bwd_m(res, dout):
        q, k, v, mask, out, lse = res
        dq, dk, dv = _bwd_ring(q, k, v, mask, out, lse, dout)
        # additive key-padding masks come from stop_gradient feeds; a
        # symbolic-zero cotangent keeps the vjp total
        return dq, dk, dv, jnp.zeros_like(mask)

    attn_masked.defvjp(fwd_m, bwd_m)
    return attn, attn_masked


def ring_attention(q, k, v, mask=None, mesh=None, axis_name="sp",
                   causal=False, scale=None):
    """q,k,v: (B, H, T, D) arrays (or sharded jax.Arrays); T sharded on
    `axis_name`. `mask` is an optional ADDITIVE key-padding mask
    broadcastable as (..., 1, T) — e.g. BERT's (B, 1, 1, T) attn bias;
    its key axis is sharded over the ring and each block travels with
    its K/V block. Per-query masks (Tq > 1 in dim -2) can't ride the
    ring (the query shard stays home) — use ulysses_attention for those.
    Returns attention output with the same sharding as q."""
    from .mesh import get_mesh
    mesh = mesh or get_mesh()
    if mesh is None or axis_name not in mesh.axis_names:
        raise ValueError("ring_attention needs a mesh with axis %r"
                         % axis_name)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    spec = P(None, None, axis_name, None)
    attn, attn_masked = _make_local(axis_name, causal, scale)
    if mask is None:
        fn = shard_map(attn, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
        return fn(q, k, v)
    if mask.ndim < 2 or mask.shape[-2] != 1:
        raise ValueError(
            "ring_attention mask must be a key-padding mask broadcastable "
            "as (..., 1, T); got shape %r — per-query masks need "
            "ulysses_attention" % (tuple(mask.shape),))
    mspec = P(*([None] * (mask.ndim - 1) + [axis_name]))
    fn = shard_map(attn_masked, mesh=mesh,
                   in_specs=(spec, spec, spec, mspec), out_specs=spec)
    return fn(q, k, v, mask)
