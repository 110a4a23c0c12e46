"""Ulysses-style sequence parallelism — all-to-all context sharding.

The second long-context strategy next to ring attention
(ring_attention.py): instead of rotating K/V blocks around a ring, two
`lax.all_to_all` exchanges re-shard the tensors from sequence-sharded
(B, H, T/n, D) to head-sharded (B, H/n, T, D), run EXACT full attention
per local head group through the Pallas flash kernel (O(T) memory), and
swap back. Trade-offs vs ring:

  * communication is 2 all-to-alls of activation size, independent of
    sequence length steps — better when T is huge and H/n >= 1;
  * each device sees the FULL sequence for its heads, so any attention
    variant (masks, dropout, alibi) works unchanged;
  * requires num_heads % n == 0 (ring has no such constraint).

No reference counterpart (the reference caps at single-device
attention); pattern from the DeepSpeed-Ulysses paper, re-expressed as
shard_map + lax.all_to_all over a mesh axis so XLA schedules the
exchanges on ICI.
"""
from jax import lax
from jax.sharding import PartitionSpec as P

from .mesh import shard_map_unchecked


def _local_attention(q, k, v, scale, causal, mask=None):
    """Exact attention on the local head group over the FULL sequence —
    through the Pallas flash kernel (O(T) memory, VMEM-tiled online
    softmax; falls back to fused XLA attention off-TPU / for small
    tiles), so long sequences never materialize (T, T) scores."""
    from ..ops.pallas.flash_attention import flash_attention
    return flash_attention(q, k, v, mask=mask, scale=scale, causal=causal)


def _make_local(axis_name, causal, scale, mask_gather_axis=None):
    def local(q, k, v, *mask_arg):
        # (B, H, T/n, D) local -> all_to_all -> (B, H/n, T, D) local:
        # split the head axis across the group, concatenate the seq axis
        qh = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2,
                            tiled=True)
        kh = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2,
                            tiled=True)
        vh = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2,
                            tiled=True)
        mask = None
        if mask_arg:
            # additive masks have no head axis to exchange (dim 1 is
            # broadcast): gather the full sequence axis instead — each
            # device now sees the full sequence for its head group, so
            # any mask shape works unchanged
            mask = lax.all_gather(mask_arg[0], axis_name,
                                  axis=mask_gather_axis, tiled=True)
        out = _local_attention(qh, kh, vh, scale, causal, mask)
        # inverse exchange: heads back together, sequence re-sharded
        return lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)
    return local


def ulysses_attention(q, k, v, mask=None, mesh=None, axis_name="sp",
                      causal=False, scale=None):
    """q,k,v: (B, H, T, D) arrays (or sharded jax.Arrays); T sharded on
    `axis_name`. num_heads must divide by the axis size. `mask` is an
    optional ADDITIVE attention mask: key-padding (..., 1, T) masks are
    sharded on their key axis, per-query (..., Tq, Tk) masks on their
    query axis; either is all-gathered inside the shard (each device
    sees the full sequence for its head group, so any mask works).
    Returns attention output with the same sharding as the inputs."""
    from .mesh import get_mesh
    mesh = mesh or get_mesh()
    if mesh is None or axis_name not in mesh.axis_names:
        raise ValueError("ulysses_attention needs a mesh with axis %r"
                         % axis_name)
    n = mesh.shape[axis_name]
    if q.shape[1] % n:
        raise ValueError(
            "ulysses_attention: num_heads (%d) must divide the %r axis "
            "size (%d) — use ring_attention for head counts that don't"
            % (q.shape[1], axis_name, n))
    if scale is None:
        scale = q.shape[-1] ** -0.5
    spec = P(None, None, axis_name, None)
    in_specs = (spec, spec, spec)
    args = (q, k, v)
    gather_axis = None
    if mask is not None:
        # shard the mask on its sequence axis: key axis for key-padding
        # masks (dim -2 == 1), query axis for per-query masks
        gather_axis = mask.ndim - 1 if mask.shape[-2] == 1 else mask.ndim - 2
        if mask.shape[gather_axis] % n:
            raise ValueError(
                "ulysses_attention: mask axis %d (size %d) must divide "
                "the %r axis size (%d)"
                % (gather_axis, mask.shape[gather_axis], axis_name, n))
        mspec = [None] * mask.ndim
        mspec[gather_axis] = axis_name
        in_specs = in_specs + (P(*mspec),)
        args = args + (mask,)
    local = _make_local(axis_name, causal, scale, gather_axis)
    # the flash pallas_call's output avals carry no vma annotation
    return shard_map_unchecked(local, mesh, in_specs, spec)(*args)
