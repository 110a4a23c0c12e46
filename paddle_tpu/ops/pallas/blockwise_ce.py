"""Blockwise softmax cross-entropy + fused MLM head (Pallas TPU).

The per-step hot spot after attention in every BASELINE LM config: the
``[tokens, vocab]`` logits tensor of the MLM/LM head. Two entries:

``blockwise_softmax_cross_entropy(logits, labels)``
    Streams existing logits block-by-block over the vocab axis with
    online logsumexp + gather-at-label accumulation, so the forward
    never materializes the ``[tokens, vocab]`` log-softmax/softmax
    intermediates XLA's lowering builds. Backward emits
    ``dlogits = (softmax - onehot) * dloss`` tile-by-tile straight from
    the ``lse`` residual (the input cotangent itself is unavoidable —
    it has the input's shape).

``fused_mlm_head_loss(hidden, weight, labels, bias=None)``
    The full fusion: computes ``hidden @ weight + bias`` INSIDE the
    kernel one ``(block_t, block_v)`` tile at a time, so the logits
    tensor never exists in HBM in forward OR backward — dhidden/dweight/
    dbias recompute each probability tile from the saved per-token
    logsumexp, flash-attention-style. Peak memory drops from
    O(tokens*vocab) to O(tokens*hidden + hidden*vocab).

Layout contract: 2-D problems — ``logits (T, V)``, ``hidden (T, D)``,
``weight (D, V)``, ``labels (T,) int``; callers collapse leading dims.
Per-token loss and residuals ride a sublane dim of 8 (Mosaic wants the
last-two block dims (8, 128)-aligned; row 0 is the real data — same
convention as flash_attention's lse). On CPU the kernels run in
interpret mode so tier-1 exercises the real kernel logic.

Entries return ``None`` when the shape cannot tile (caller falls back
to its XLA lowering) — the same size-guard contract as flash_attention.
"""
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

from .flash_attention import _dot_precision
from .costmodel import fit_blocks  # noqa: F401 - the kernels' tiling
# math lives in costmodel (pure, jax-free) so the cost model and the
# size guards can never disagree; re-exported here for the callers/
# tests that always imported it from this module
from .. import pallas_dispatch as pd

_NEG_INF = -1e30


def _label_zero_cot(labels):
    """Cotangent for an integer labels input: float0 zeros (the value
    jax.vjp expects for int primals; discarded by every caller)."""
    return np.zeros(np.shape(labels), dtype=jax.dtypes.float0)


def _rows8(x, dtype):
    """Broadcast a (T,) vector to (8, T) — the sublane-padded layout the
    per-token inputs/outputs ride through Mosaic."""
    return jnp.broadcast_to(jnp.asarray(x, dtype)[None, :],
                            (8,) + (x.shape[0],))


def _online_lse_update(s, m_ref, l_ref):
    """One blockwise logsumexp accumulation step over score tile `s`
    ((BT, BV) f32) against the running (max, sum) scratch pair."""
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    l_new = corr * l_ref[:, :1] + jnp.sum(jnp.exp(s - m_new), axis=-1,
                                          keepdims=True)
    m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)


def _label_hit(lab_ref, vj, block_t, block_v):
    """Bool (BT, BV) tile: does column j hold this row's label?"""
    lab = lab_ref[0].astype(jnp.int32)
    col = vj * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_t, block_v), 1)
    return col == lab[:, None]


def _finalize_loss(loss_ref, lse_ref, m_ref, l_ref, ll_ref):
    """Emit per-token loss = lse - logit[label] and the lse residual."""
    lse = m_ref[:, 0] + jnp.log(jnp.maximum(l_ref[:, 0], 1e-30))
    loss_ref[:] = jnp.broadcast_to((lse - ll_ref[:, 0])[None, :],
                                   loss_ref.shape).astype(loss_ref.dtype)
    lse_ref[:] = jnp.broadcast_to(lse[None, :],
                                  lse_ref.shape).astype(lse_ref.dtype)


def _p_ds(s, lse_ref, dl_ref, lab_ref, vj, block_t, block_v):
    """Probability tile p = exp(s - lse) and the logit cotangent
    ds = (p - onehot(label)) * dloss — the shared core of every
    backward kernel."""
    lse = lse_ref[0].astype(jnp.float32)
    dl = dl_ref[0].astype(jnp.float32)
    p = jnp.exp(s - lse[:, None])
    hit = _label_hit(lab_ref, vj, block_t, block_v)
    return (p - jnp.where(hit, 1.0, 0.0)) * dl[:, None]


# ---------------------------------------------------------------------------
# logits-level blockwise CE (the softmax_with_cross_entropy op lowering)
# ---------------------------------------------------------------------------

def _ce_fwd_kernel(x_ref, lab_ref, loss_ref, lse_ref, m_ref, l_ref, ll_ref,
                   *, block_t, block_v):
    vj = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        ll_ref[:] = jnp.zeros_like(ll_ref)

    s = x_ref[...].astype(jnp.float32)               # (BT, BV)
    _online_lse_update(s, m_ref, l_ref)
    hit = _label_hit(lab_ref, vj, block_t, block_v)
    ll_ref[:] = ll_ref[:] + jnp.broadcast_to(
        jnp.sum(jnp.where(hit, s, 0.0), axis=-1, keepdims=True),
        ll_ref.shape)

    @pl.when(vj == nv - 1)
    def _fin():
        _finalize_loss(loss_ref, lse_ref, m_ref, l_ref, ll_ref)


def _ce_bwd_kernel(x_ref, lab_ref, lse_ref, dl_ref, dx_ref,
                   *, block_t, block_v):
    vj = pl.program_id(1)
    s = x_ref[...].astype(jnp.float32)
    ds = _p_ds(s, lse_ref, dl_ref, lab_ref, vj, block_t, block_v)
    dx_ref[...] = ds.astype(dx_ref.dtype)


def _ce_call_fwd(logits, labels, block_t, block_v, interpret):
    t, v = logits.shape
    grid = (t // block_t, v // block_v)
    loss, lse = pl.pallas_call(
        functools.partial(_ce_fwd_kernel, block_t=block_t,
                          block_v=block_v),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, block_v), lambda ti, vj: (ti, vj)),
            pl.BlockSpec((8, block_t), lambda ti, vj: (0, ti)),
        ],
        out_specs=[
            pl.BlockSpec((8, block_t), lambda ti, vj: (0, ti)),
            pl.BlockSpec((8, block_t), lambda ti, vj: (0, ti)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((8, t), jnp.float32),
            jax.ShapeDtypeStruct((8, t), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_t, 128), jnp.float32)
                        for _ in range(3)],
        name="blockwise_ce_fwd",
        interpret=interpret,
    )(logits, _rows8(labels, jnp.int32))
    return loss[0], lse[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _ce(logits, labels, block_t, block_v, interpret):
    loss, _ = _ce_call_fwd(logits, labels, block_t, block_v, interpret)
    return loss


def _ce_fwd(logits, labels, block_t, block_v, interpret):
    loss, lse = _ce_call_fwd(logits, labels, block_t, block_v, interpret)
    return loss, (logits, labels, lse)


def _ce_bwd(block_t, block_v, interpret, res, dloss):
    logits, labels, lse = res
    t, v = logits.shape
    dx = pl.pallas_call(
        functools.partial(_ce_bwd_kernel, block_t=block_t,
                          block_v=block_v),
        grid=(t // block_t, v // block_v),
        in_specs=[
            pl.BlockSpec((block_t, block_v), lambda ti, vj: (ti, vj)),
            pl.BlockSpec((8, block_t), lambda ti, vj: (0, ti)),
            pl.BlockSpec((8, block_t), lambda ti, vj: (0, ti)),
            pl.BlockSpec((8, block_t), lambda ti, vj: (0, ti)),
        ],
        out_specs=pl.BlockSpec((block_t, block_v),
                               lambda ti, vj: (ti, vj)),
        out_shape=jax.ShapeDtypeStruct((t, v), logits.dtype),
        name="blockwise_ce_bwd_dx",
        interpret=interpret,
    )(logits, _rows8(labels, jnp.int32), _rows8(lse, jnp.float32),
      _rows8(dloss, jnp.float32))
    return dx, _label_zero_cot(labels)


_ce.defvjp(_ce_fwd, _ce_bwd)


def blockwise_softmax_cross_entropy(logits, labels, block_t=128,
                                    block_v=512, interpret=None):
    """Per-token softmax CE loss (f32, shape (T,)) streamed over vocab
    blocks of existing ``logits (T, V)``; ``labels (T,) int``. Returns
    None when the shape cannot tile — callers then take their XLA path.
    """
    if interpret is None:
        interpret = pd.default_interpret()
    t, v = logits.shape
    fit = fit_blocks(t, v, block_t, block_v, interpret)
    if fit is None:
        return None
    bt, bv = fit
    return _ce(jnp.asarray(logits), jnp.asarray(labels), bt, bv,
               bool(interpret))


# ---------------------------------------------------------------------------
# fused MLM head: hidden @ weight + bias -> CE, logits never in HBM
# ---------------------------------------------------------------------------

def _head_tile(h_ref, w_ref, b_ref, precision):
    """One (BT, BV) logits tile computed in-VMEM from the hidden and
    weight blocks — the materialization this kernel exists to avoid."""
    h = h_ref[...].astype(jnp.float32)               # (BT, D)
    w = w_ref[...].astype(jnp.float32)               # (D, BV)
    s = jax.lax.dot_general(h, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32,
                            precision=precision)
    return h, s + b_ref[0][None, :].astype(jnp.float32)


def _head_fwd_kernel(h_ref, w_ref, b_ref, lab_ref, loss_ref, lse_ref,
                     m_ref, l_ref, ll_ref, *, block_t, block_v,
                     precision):
    vj = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        ll_ref[:] = jnp.zeros_like(ll_ref)

    _, s = _head_tile(h_ref, w_ref, b_ref, precision)
    _online_lse_update(s, m_ref, l_ref)
    hit = _label_hit(lab_ref, vj, block_t, block_v)
    ll_ref[:] = ll_ref[:] + jnp.broadcast_to(
        jnp.sum(jnp.where(hit, s, 0.0), axis=-1, keepdims=True),
        ll_ref.shape)

    @pl.when(vj == nv - 1)
    def _fin():
        _finalize_loss(loss_ref, lse_ref, m_ref, l_ref, ll_ref)


def _head_dh_kernel(h_ref, w_ref, b_ref, lab_ref, lse_ref, dl_ref,
                    dh_ref, dh_acc, *, block_t, block_v, precision):
    vj = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(vj == 0)
    def _init():
        dh_acc[:] = jnp.zeros_like(dh_acc)

    _, s = _head_tile(h_ref, w_ref, b_ref, precision)
    ds = _p_ds(s, lse_ref, dl_ref, lab_ref, vj, block_t, block_v)
    # dh += ds @ w^T
    dh_acc[:] = dh_acc[:] + jax.lax.dot_general(
        ds, w_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision)

    @pl.when(vj == nv - 1)
    def _fin():
        dh_ref[...] = dh_acc[:].astype(dh_ref.dtype)


def _head_dwb_kernel(h_ref, w_ref, b_ref, lab_ref, lse_ref, dl_ref,
                     dw_ref, db_ref, dw_acc, db_acc, *, block_t, block_v,
                     precision):
    # grid (nv, nt): t innermost so dw/db accumulate per weight column
    vj = pl.program_id(0)
    ti = pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(ti == 0)
    def _init():
        dw_acc[:] = jnp.zeros_like(dw_acc)
        db_acc[:] = jnp.zeros_like(db_acc)

    h, s = _head_tile(h_ref, w_ref, b_ref, precision)
    ds = _p_ds(s, lse_ref, dl_ref, lab_ref, vj, block_t, block_v)
    # dw += h^T @ ds ; db += sum_t ds
    dw_acc[:] = dw_acc[:] + jax.lax.dot_general(
        h, ds, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision)
    db_acc[:] = db_acc[:] + jnp.broadcast_to(
        jnp.sum(ds, axis=0, keepdims=True), db_acc.shape)

    @pl.when(ti == nt - 1)
    def _fin():
        dw_ref[...] = dw_acc[:].astype(dw_ref.dtype)
        db_ref[...] = db_acc[:].astype(db_ref.dtype)


def _head_call_fwd(hidden, weight, bias, labels, block_t, block_v,
                   interpret):
    t, d = hidden.shape
    v = weight.shape[1]
    prec = _dot_precision(hidden.dtype)
    loss, lse = pl.pallas_call(
        functools.partial(_head_fwd_kernel, block_t=block_t,
                          block_v=block_v, precision=prec),
        grid=(t // block_t, v // block_v),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda ti, vj: (ti, 0)),
            pl.BlockSpec((d, block_v), lambda ti, vj: (0, vj)),
            pl.BlockSpec((8, block_v), lambda ti, vj: (0, vj)),
            pl.BlockSpec((8, block_t), lambda ti, vj: (0, ti)),
        ],
        out_specs=[
            pl.BlockSpec((8, block_t), lambda ti, vj: (0, ti)),
            pl.BlockSpec((8, block_t), lambda ti, vj: (0, ti)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((8, t), jnp.float32),
            jax.ShapeDtypeStruct((8, t), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_t, 128), jnp.float32)
                        for _ in range(3)],
        name="blockwise_ce_head_fwd",
        interpret=interpret,
    )(hidden, weight, _rows8(bias, jnp.float32),
      _rows8(labels, jnp.int32))
    return loss[0], lse[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _head(hidden, weight, bias, labels, block_t, block_v, interpret):
    loss, _ = _head_call_fwd(hidden, weight, bias, labels, block_t,
                             block_v, interpret)
    return loss


def _head_fwd(hidden, weight, bias, labels, block_t, block_v, interpret):
    loss, lse = _head_call_fwd(hidden, weight, bias, labels, block_t,
                               block_v, interpret)
    return loss, (hidden, weight, bias, labels, lse)


def _head_bwd(block_t, block_v, interpret, res, dloss):
    hidden, weight, bias, labels, lse = res
    t, d = hidden.shape
    v = weight.shape[1]
    prec = _dot_precision(hidden.dtype)
    lab8 = _rows8(labels, jnp.int32)
    lse8 = _rows8(lse, jnp.float32)
    dl8 = _rows8(dloss, jnp.float32)
    bias8 = _rows8(bias, jnp.float32)
    common = dict(block_t=block_t, block_v=block_v, precision=prec)

    dh = pl.pallas_call(
        functools.partial(_head_dh_kernel, **common),
        grid=(t // block_t, v // block_v),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda ti, vj: (ti, 0)),
            pl.BlockSpec((d, block_v), lambda ti, vj: (0, vj)),
            pl.BlockSpec((8, block_v), lambda ti, vj: (0, vj)),
            pl.BlockSpec((8, block_t), lambda ti, vj: (0, ti)),
            pl.BlockSpec((8, block_t), lambda ti, vj: (0, ti)),
            pl.BlockSpec((8, block_t), lambda ti, vj: (0, ti)),
        ],
        out_specs=pl.BlockSpec((block_t, d), lambda ti, vj: (ti, 0)),
        out_shape=jax.ShapeDtypeStruct((t, d), hidden.dtype),
        scratch_shapes=[pltpu.VMEM((block_t, d), jnp.float32)],
        name="blockwise_ce_head_bwd_dh",
        interpret=interpret,
    )(hidden, weight, bias8, lab8, lse8, dl8)

    dw, db8 = pl.pallas_call(
        functools.partial(_head_dwb_kernel, **common),
        grid=(v // block_v, t // block_t),
        in_specs=[
            pl.BlockSpec((block_t, d), lambda vj, ti: (ti, 0)),
            pl.BlockSpec((d, block_v), lambda vj, ti: (0, vj)),
            pl.BlockSpec((8, block_v), lambda vj, ti: (0, vj)),
            pl.BlockSpec((8, block_t), lambda vj, ti: (0, ti)),
            pl.BlockSpec((8, block_t), lambda vj, ti: (0, ti)),
            pl.BlockSpec((8, block_t), lambda vj, ti: (0, ti)),
        ],
        out_specs=[
            pl.BlockSpec((d, block_v), lambda vj, ti: (0, vj)),
            pl.BlockSpec((8, block_v), lambda vj, ti: (0, vj)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, v), weight.dtype),
            jax.ShapeDtypeStruct((8, v), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((d, block_v), jnp.float32),
            pltpu.VMEM((8, block_v), jnp.float32),
        ],
        name="blockwise_ce_head_bwd_dwb",
        interpret=interpret,
    )(hidden, weight, bias8, lab8, lse8, dl8)

    return dh, dw, db8[0].astype(bias.dtype), _label_zero_cot(labels)


_head.defvjp(_head_fwd, _head_bwd)


def fused_mlm_head_loss(hidden, weight, labels, bias=None, block_t=128,
                        block_v=512, interpret=None):
    """Per-token CE loss of the LM/MLM head without ever materializing
    the ``[tokens, vocab]`` logits: ``hidden (T, D)``, ``weight (D, V)``,
    ``labels (T,) int``, optional ``bias (V,)``. Returns f32 ``(T,)``
    loss, or None when the shape cannot tile (caller computes the head
    through XLA instead). Differentiable wrt hidden/weight/bias; the
    backward recomputes each probability tile from the saved per-token
    logsumexp, so neither direction touches a (T, V) buffer."""
    if interpret is None:
        interpret = pd.default_interpret()
    t, d = hidden.shape
    v = weight.shape[1]
    fit = fit_blocks(t, v, block_t, block_v, interpret)
    if fit is None or d % 8:
        return None
    bt, bv = fit
    b = jnp.zeros((v,), jnp.float32) if bias is None else jnp.asarray(bias)
    return _head(jnp.asarray(hidden), jnp.asarray(weight), b,
                 jnp.asarray(labels), bt, bv, bool(interpret))
