"""Collective communication op kernels.

Reference parity: paddle/fluid/operators/collective/{c_allreduce_*,
c_allgather,c_reducescatter,c_broadcast}.cc (NCCL). TPU-native: XLA
collectives (lax.psum/all_gather/psum_scatter/ppermute) over the ICI mesh.

These kernels are meaningful when traced under shard_map with a bound mesh
axis (paddle_tpu.distributed). Single-device traces degrade to identity, so
the same program runs anywhere — mirroring the reference where ring_id 0 on
one rank is a no-op.
"""
import contextlib
import threading

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .registry import register_op
from . import quant_ops


def _axis(ctx, attrs):
    """Axis name for the collective; None → not inside shard_map → no-op."""
    name = attrs.get("axis_name", "dp")
    bound = getattr(ctx, "bound_axes", ())
    return name if name in bound else None


def _make_allreduce(op_name, reduce_fn):
    @register_op(op_name, differentiable=True)
    def _kernel(ctx, ins, attrs, _fn=reduce_fn):
        x = ins["X"][0]
        ax = _axis(ctx, attrs)
        return {"Out": x if ax is None else _fn(x, ax)}
    return _kernel


_make_allreduce("c_allreduce_sum", lax.psum)
_make_allreduce("c_allreduce_max", lax.pmax)
_make_allreduce("c_allreduce_min", lax.pmin)
_make_allreduce("c_allreduce_prod",
                lambda x, ax: jnp.exp(lax.psum(jnp.log(x), ax)))


@register_op("c_allgather")
def _c_allgather(ctx, ins, attrs):
    x = ins["X"][0]
    ax = _axis(ctx, attrs)
    if ax is None:
        return {"Out": x}
    return {"Out": lax.all_gather(x, ax, axis=0, tiled=True)}


@register_op("c_reducescatter")
def _c_reducescatter(ctx, ins, attrs):
    x = ins["X"][0]
    ax = _axis(ctx, attrs)
    if ax is None:
        return {"Out": x}
    return {"Out": lax.psum_scatter(x, ax, scatter_dimension=0, tiled=True)}


@register_op("c_broadcast")
def _c_broadcast(ctx, ins, attrs):
    x = ins["X"][0]
    ax = _axis(ctx, attrs)
    if ax is None:
        return {"Out": x}
    root = attrs.get("root", 0)
    idx = lax.axis_index(ax)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return {"Out": lax.psum(masked, ax)}


@register_op("c_sync_comm_stream")
def _c_sync(ctx, ins, attrs):
    # XLA orders collectives itself; kept for program parity.
    return {"Out": list(ins["X"])}


@register_op("barrier", differentiable=False)
def _barrier(ctx, ins, attrs):
    x = ins["X"][0]
    ax = _axis(ctx, attrs)
    if ax is None:
        return {"Out": x}
    return {"Out": x + 0 * lax.psum(jnp.zeros((), x.dtype), ax)}


@register_op("ppermute")
def _ppermute(ctx, ins, attrs):
    """Ring shift (building block of ring attention / pipeline parallel)."""
    x = ins["X"][0]
    ax = _axis(ctx, attrs)
    if ax is None:
        return {"Out": x}
    n = lax.axis_size(ax)
    shift = attrs.get("shift", 1)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return {"Out": lax.ppermute(x, ax, perm)}


# ---------------------------------------------------------------------------
# block-quantized all-reduce (EQuARX, PAPERS.md)
# ---------------------------------------------------------------------------

def quantized_psum(x, axis_name, block_size=quant_ops.DEFAULT_BLOCK_SIZE,
                   bits=quant_ops.DEFAULT_BITS, mean=False):
    """Quantize -> sum-over-axis -> dequantize, wire-honest: each member
    quantizes its LOCAL contribution (int8 payload + per-block fp32
    scale), the int8 blocks + scales are what cross the axis
    (lax.all_gather of int8), and every member dequantizes + sums the
    gathered contributions in fp32. Deterministic and bitwise-identical
    on every member (the gather axis fixes the summation order), so
    replicated state updated from the result stays replicated.

    ``mean=True`` divides by the axis size — the data-parallel gradient
    sync (global grad = mean over shards of local grads of local-mean
    losses). Accuracy model matches EQuARX: one quantization per
    contribution, exact fp32 accumulation of the dequantized values.
    """
    q, scale = quant_ops.block_quantize(x, block_size, bits)
    gq = lax.all_gather(q, axis_name)          # (n, n_blocks, block) int8
    gs = lax.all_gather(scale, axis_name)      # (n, n_blocks) fp32
    qmax = 2.0 ** (int(bits) - 1) - 1
    deq = gq.astype(jnp.float32) \
        * (jnp.maximum(gs, 1e-12) / qmax)[..., None]
    tot = jnp.sum(deq, axis=0)
    if mean:
        tot = tot / lax.axis_size(axis_name)
    size = int(np.prod(x.shape)) if x.shape else 1
    return tot.reshape(-1)[:size].reshape(x.shape).astype(x.dtype)


@register_op("c_allreduce_sum_quant")
def _c_allreduce_sum_quant(ctx, ins, attrs):
    """Block-quantized c_allreduce_sum: same contract as c_allreduce_sum
    (identity outside shard_map) but the wire carries int8 blocks + fp32
    scales instead of full-width values. attrs: block_size, bits."""
    x = ins["X"][0]
    ax = _axis(ctx, attrs)
    if ax is None:
        return {"Out": x}
    return {"Out": quantized_psum(
        x, ax, block_size=int(attrs.get("block_size",
                                        quant_ops.DEFAULT_BLOCK_SIZE)),
        bits=int(attrs.get("bits", quant_ops.DEFAULT_BITS)))}


# ---------------------------------------------------------------------------
# gradient-sync scope: how the compiler's quantize_collectives option
# reaches the trace engine
# ---------------------------------------------------------------------------

class QuantizedSyncContext(object):
    """Per-compile gradient-sync policy + static byte accounting.

    Installed around the step trace by CompiledProgram when
    ``BuildStrategy.quantize_collectives`` is on; framework/trace.py
    consults :func:`current_grad_sync` and calls :meth:`sync` once per
    parameter gradient as it is produced, so every downstream consumer
    (grad clip, regularizer, gradient-merge ACCUMULATION, optimizer)
    sees the synced value — the same semantics pjit's implicit psum
    gives, with fp32 accumulation staying exact because only the
    cross-host sync is quantized.

    ``raw_bytes``/``wire_bytes`` accumulate at TRACE time (shapes are
    static), i.e. exactly once per compiled step; the dispatch wrapper
    multiplies by the window length and feeds
    ``resilience.record_bytes("collective", ...)`` per dispatch.
    """

    def __init__(self, axis_name, block_size=quant_ops.DEFAULT_BLOCK_SIZE,
                 bits=quant_ops.DEFAULT_BITS, mean=True, min_size=None,
                 merge_window=False):
        self.axis_name = axis_name
        self.block_size = int(block_size)
        self.bits = int(bits)
        self.mean = bool(mean)
        # tensors below one block ride the EXACT full-width sync: a
        # sub-block payload (biases, LayerNorm scales) costs MORE on the
        # wire quantized (block padding + scale) than raw, and its
        # accuracy is the cheapest to keep
        self.min_size = self.block_size if min_size is None \
            else int(min_size)
        # merge_window: params under a detected gradient-merge
        # accumulator defer their sync to the MERGE BOUNDARY (once per
        # k steps, under lax.cond on the program's own apply predicate)
        # instead of syncing the raw gradient every micro step — see
        # sync_merged and framework/trace._maybe_sync_param_grads
        self.merge_window = bool(merge_window)
        self.raw_bytes = 0
        self.wire_bytes = 0
        self.synced = []      # grad var names, in trace order
        self.synced_exact = []
        self.synced_merged = []   # grads synced once-per-k at the boundary

    def sync(self, name, g):
        size = int(np.prod(g.shape)) if g.shape else 1
        itemsize = jnp.dtype(g.dtype).itemsize
        if size < self.min_size:
            self.raw_bytes += size * itemsize
            self.wire_bytes += size * itemsize
            self.synced_exact.append(name)
            red = lax.pmean if self.mean else lax.psum
            return red(g, self.axis_name)
        raw, wire = quant_ops.quantized_wire_bytes(
            size, itemsize, self.block_size, self.bits)
        self.raw_bytes += raw
        self.wire_bytes += wire
        self.synced.append(name)
        return quantized_psum(g, self.axis_name, self.block_size,
                              self.bits, mean=self.mean)

    def sync_merged(self, name, g, pred, every_k=None):
        """Merge-boundary sync: the dp reduction runs under lax.cond on
        the program's own apply predicate, so the k-1 non-apply steps of
        every merge window ship ZERO gradient bytes (the accumulation
        stays local, exact fp32 — the bitwise invariant holds on the
        LOCAL sums). Byte accounting amortizes by every_k when the
        merge factor is statically known (avg=True merges expose it via
        the scale op); an unknown k books the full per-step cost — a
        conservative over-count, never an under-count."""
        size = int(np.prod(g.shape)) if g.shape else 1
        itemsize = jnp.dtype(g.dtype).itemsize
        if size < self.min_size:
            raw = wire = size * itemsize
            self.synced_exact.append(name)
            red = lax.pmean if self.mean else lax.psum

            def sync_fn(v):
                return red(v, self.axis_name)
        else:
            raw, wire = quant_ops.quantized_wire_bytes(
                size, itemsize, self.block_size, self.bits)
            self.synced.append(name)

            def sync_fn(v):
                return quantized_psum(v, self.axis_name, self.block_size,
                                      self.bits, mean=self.mean)
        scale = 1.0 / every_k if every_k else 1.0
        self.raw_bytes += raw * scale
        self.wire_bytes += wire * scale
        self.synced_merged.append(name)
        return lax.cond(jnp.reshape(pred, ()).astype(bool), sync_fn,
                        lambda v: v, g)


_sync_tls = threading.local()


@contextlib.contextmanager
def grad_sync_scope(sync_ctx):
    """Install ``sync_ctx`` for traces started on this thread (jit traces
    run synchronously in the caller, so a thread-local is exact)."""
    prev = getattr(_sync_tls, "ctx", None)
    _sync_tls.ctx = sync_ctx
    try:
        yield sync_ctx
    finally:
        _sync_tls.ctx = prev


def current_grad_sync():
    return getattr(_sync_tls, "ctx", None)
