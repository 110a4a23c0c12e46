"""Pallas TPU flash attention.

The hot op of every BASELINE transformer config. Tiles Q/K/V blocks through
VMEM with online-softmax accumulation — the (T,T) score matrix never touches
HBM, so attention becomes MXU-bound instead of HBM-bound for long sequences.

Forward: Pallas kernel, grid (B*H, Tq/BQ, Tk/BK), f32 accumulators in VMEM
scratch persisting across the (innermost, sequential) k-block dimension;
emits the softmax statistics (row max m, normalizer l) alongside the output.
Backward: Pallas dK/dV and dQ kernels that recompute p = exp(s - m) / l
per tile from the saved (out, m, l) residuals — flash-attention-2 style, no
(T,T) matrix in HBM in either direction, with the additive mask applied
in-kernel. The statistics stay separate on purpose: folding them into
lse = m + log(l) puts the hardware log/exp approximation error into the
exponent — measured on a v5e, sum(p) then sat ~3e-5 off 1 and the f32
gradients 2e-5..9e-5 off a float64 oracle (XLA's own: 1e-6). The mask cotangent (needed only for learned biases) is a
separate XLA expression that DCEs away when unused.

Layout contract: q, k, v are (B, H, T, D); additive mask broadcastable
(B, 1, 1, Tk) or (B, 1, Tq, Tk). On CPU (tests) the kernel runs in
interpret mode.
"""
import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
    _HAS_TPU_PALLAS = True
except Exception:  # pragma: no cover
    pltpu = None
    _HAS_TPU_PALLAS = False

from .. import pallas_dispatch as pd

_NEG_INF = -1e30


def _dot_precision(dtype):
    """MXU precision for kernel matmuls given the user-facing dtype.

    f32 (and fp16: 10 mantissa bits > bf16's 7) inputs at DEFAULT
    precision run a single bf16 pass on the MXU (~1e-3 relative error) —
    a user asking for f32/fp16 attention gets full-precision math
    (HIGHEST = multi-pass), matching the reference's true-precision CUDA
    kernels. bf16 inputs stay on the fast path: their products are exact
    in the f32 accumulator, so DEFAULT already matches the oracle."""
    return (jax.lax.Precision.DEFAULT
            if jnp.dtype(dtype) == jnp.bfloat16 else
            jax.lax.Precision.HIGHEST)


def _causal_keep(qi, kj, causal_offset, block_q, block_k):
    """Bool (BQ, BK) tile of the bottom-right-aligned causal mask
    (query i sees keys j <= i + causal_offset) — shared by all kernels."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return q_pos + causal_offset >= k_pos


def _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, stats_ref, delta_ref, mask_ref,
              qi, kj, *, scale, causal, causal_offset, block_q, block_k,
              mask_mode, precision):
    """Recompute the probability tile p = exp(s - m) / l — the forward's
    own normalization — and the logit cotangent ds = p * (dO V^T - delta)
    from the forward residuals: the shared core of both backward
    kernels."""
    q = q_ref[0].astype(jnp.float32)            # (BQ, D)
    k = k_ref[0].astype(jnp.float32)            # (BK, D)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)          # (BQ, D)
    m = stats_ref[0, 0]                         # (BQ,) row max
    inv_l = 1.0 / stats_ref[0, 1]               # (BQ,) 1 / normalizer
    delta = delta_ref[0, 0].astype(jnp.float32)  # row 0 is real
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision) * scale  # (BQ, BK)
    if mask_mode == "qk":
        s = s + mask_ref[0, 0].astype(jnp.float32)
    elif mask_mode == "k":
        s = s + mask_ref[0, 0, 0][None, :].astype(jnp.float32)
    p = jnp.exp(s - m[:, None]) * inv_l[:, None]
    if causal:
        p = jnp.where(_causal_keep(qi, kj, causal_offset, block_q,
                                   block_k), p, 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision)                    # (BQ, BK)
    ds = p * (dp - delta[:, None])
    return q, k, do, p, ds


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, stats_ref, acc_ref,
                m_ref, l_ref, *, scale, causal, causal_offset, block_q,
                block_k, mask_mode, precision):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def body():
        q = q_ref[0].astype(jnp.float32)          # (BQ, D)
        k = k_ref[0].astype(jnp.float32)          # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision) * scale  # (BQ, BK)
        if mask_mode == "qk":
            s = s + mask_ref[0, 0].astype(jnp.float32)
        elif mask_mode == "k":
            s = s + mask_ref[0, 0, 0][None, :].astype(jnp.float32)
        if causal:
            # bottom-right aligned for Tq != Tk (matches _xla_attention's
            # tril(..., tk - tq)): query i sees keys j <= i + (tk - tq)
            s = jnp.where(_causal_keep(qi, kj, causal_offset, block_q,
                                       block_k), s, _NEG_INF)

        m_prev = m_ref[:, :1]                      # (BQ, 1)
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)                     # (BQ, BK)
        corr = jnp.exp(m_prev - m_new)             # (BQ, 1)
        l_new = corr * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision)                   # (BQ, D)
        acc_ref[:] = acc_ref[:] * corr + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # skip k-blocks strictly above the (offset) diagonal
        @pl.when(kj * block_k <= qi * block_q + (block_q - 1) +
                 causal_offset)
        def _():
            body()
    else:
        body()

    @pl.when(kj == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # softmax statistics for the Pallas backward, packed into the
        # sublane dim of 8 that Mosaic's (8, 128) block alignment needs
        # anyway: row 0 = running max m, rows 1.. = normalizer l
        row = jax.lax.broadcasted_iota(jnp.int32, stats_ref.shape[1:], 0)
        stats_ref[0] = jnp.where(row == 0, m_ref[:, 0][None, :],
                                 l[:, 0][None, :])


def _mask_spec(mask, h, q_dtype, block_q, block_k, kj_innermost):
    """(mask_mode, mask_input, BlockSpec) for an additive mask broadcastable
    (B,1,1,Tk) ["k" mode] or (B,1,Tq,Tk) ["qk"]. Grid index order is
    (bh, i, j) for the forward/dQ kernels (kj_innermost) and (bh, j, i)
    for dK/dV."""
    if mask is None:
        return "none", jnp.zeros((1, 1, 1, 1), q_dtype), pl.BlockSpec(
            (1, 1, 1, 1), lambda bb, a, b_: (0, 0, 0, 0))
    if mask.shape[2] == 1:
        if kj_innermost:
            def _idx(bb, i, j, hh=h):
                return (bb // hh, 0, 0, j)
        else:
            def _idx(bb, j, i, hh=h):
                return (bb // hh, 0, 0, j)
        return "k", mask, pl.BlockSpec((1, 1, 1, block_k), _idx)
    if kj_innermost:
        def _idx(bb, i, j, hh=h):
            return (bb // hh, 0, i, j)
    else:
        def _idx(bb, j, i, hh=h):
            return (bb // hh, 0, i, j)
    return "qk", mask, pl.BlockSpec((1, 1, block_q, block_k), _idx)


def _pallas_forward(q, k, v, mask, scale, causal, block_q, block_k,
                    interpret):
    if not _HAS_TPU_PALLAS:
        raise NotImplementedError("pallas tpu backend unavailable")
    b, h, tq, d = q.shape
    tk = k.shape[2]
    bh = b * h
    q3 = q.reshape(bh, tq, d)
    k3 = k.reshape(bh, tk, d)
    v3 = v.reshape(bh, tk, d)

    grid = (bh, tq // block_q, tk // block_k)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bb, i, j: (bb, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda bb, i, j: (bb, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda bb, i, j: (bb, j, 0)),
    ]
    mask_mode, mask_in, mask_spec = _mask_spec(mask, h, q.dtype, block_q,
                                               block_k, kj_innermost=True)
    in_specs.append(mask_spec)

    scratch = [
        pltpu.VMEM((block_q, d), jnp.float32),
        pltpu.VMEM((block_q, 128), jnp.float32),
        pltpu.VMEM((block_q, 128), jnp.float32),
    ]

    out, stats = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          causal_offset=tk - tq, block_q=block_q,
                          block_k=block_k, mask_mode=mask_mode,
                          precision=_dot_precision(q.dtype)),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bb, i, j: (bb, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda bb, i, j: (bb, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 8, tq), jnp.float32),
        ],
        scratch_shapes=scratch,
        name="flash_fwd",
        interpret=interpret,
    )(q3, k3, v3, mask_in)
    return out.reshape(b, h, tq, d), stats


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, stats_ref, delta_ref,
                    mask_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, causal_offset, block_q, block_k,
                    mask_mode, precision):
    """dK/dV for one k-block, accumulating over q-blocks (innermost grid
    dim). Recomputes p from the residuals — no (T,T) in HBM."""
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def body():
        q, _, do, p, ds = _bwd_p_ds(
            q_ref, k_ref, v_ref, do_ref, stats_ref, delta_ref, mask_ref,
            qi, kj, scale=scale, causal=causal,
            causal_offset=causal_offset, block_q=block_q,
            block_k=block_k, mask_mode=mask_mode, precision=precision)
        # dv += p^T dO ; dk += scale * ds^T q
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        dk_acc[:] = dk_acc[:] + scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)

    if causal:
        @pl.when(qi * block_q + (block_q - 1) + causal_offset >=
                 kj * block_k)
        def _():
            body()
    else:
        body()

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, stats_ref, delta_ref,
                   mask_ref, dq_ref, dq_acc, *, scale, causal,
                   causal_offset, block_q, block_k, mask_mode, precision):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def body():
        _, k, _, _, ds = _bwd_p_ds(
            q_ref, k_ref, v_ref, do_ref, stats_ref, delta_ref, mask_ref,
            qi, kj, scale=scale, causal=causal,
            causal_offset=causal_offset, block_q=block_q,
            block_k=block_k, mask_mode=mask_mode, precision=precision)
        dq_acc[:] = dq_acc[:] + scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)

    if causal:
        @pl.when(kj * block_k <= qi * block_q + (block_q - 1) +
                 causal_offset)
        def _():
            body()
    else:
        body()

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _pallas_backward(q, k, v, mask, out, stats, g, scale, causal, block_q,
                     block_k, interpret):
    b, h, tq, d = q.shape
    tk = k.shape[2]
    bh = b * h
    q3 = q.reshape(bh, tq, d)
    k3 = k.reshape(bh, tk, d)
    v3 = v.reshape(bh, tk, d)
    do3 = g.reshape(bh, tq, d)
    # stats (from the forward) and delta carry a sublane dim of 8 for
    # Mosaic block alignment
    # delta = rowsum(dO * O): cheap elementwise pass in XLA
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, tq)
    delta = jnp.broadcast_to(delta, (bh, 8, tq))

    mask_mode, mask_in, dkv_mask_spec = _mask_spec(
        mask, h, q.dtype, block_q, block_k, kj_innermost=False)
    common = dict(scale=scale, causal=causal, causal_offset=tk - tq,
                  block_q=block_q, block_k=block_k, mask_mode=mask_mode,
                  precision=_dot_precision(q.dtype))
    dkv_specs = [
        pl.BlockSpec((1, block_q, d), lambda bb, j, i: (bb, i, 0)),   # q
        pl.BlockSpec((1, block_k, d), lambda bb, j, i: (bb, j, 0)),   # k
        pl.BlockSpec((1, block_k, d), lambda bb, j, i: (bb, j, 0)),   # v
        pl.BlockSpec((1, block_q, d), lambda bb, j, i: (bb, i, 0)),   # do
        pl.BlockSpec((1, 8, block_q), lambda bb, j, i: (bb, 0, i)),   # m, l
        pl.BlockSpec((1, 8, block_q), lambda bb, j, i: (bb, 0, i)),   # delta
        dkv_mask_spec,
    ]
    dk3, dv3 = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(bh, tk // block_k, tq // block_q),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda bb, j, i: (bb, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda bb, j, i: (bb, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        name="flash_bwd_dkv",
        interpret=interpret,
    )(q3, k3, v3, do3, stats, delta, mask_in)

    _, _, dq_mask_spec = _mask_spec(mask, h, q.dtype, block_q, block_k,
                                    kj_innermost=True)
    dq_specs = [
        pl.BlockSpec((1, block_q, d), lambda bb, i, j: (bb, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda bb, i, j: (bb, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda bb, i, j: (bb, j, 0)),
        pl.BlockSpec((1, block_q, d), lambda bb, i, j: (bb, i, 0)),
        pl.BlockSpec((1, 8, block_q), lambda bb, i, j: (bb, 0, i)),
        pl.BlockSpec((1, 8, block_q), lambda bb, i, j: (bb, 0, i)),
        dq_mask_spec,
    ]
    dq3 = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(bh, tq // block_q, tk // block_k),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda bb, i, j: (bb, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        name="flash_bwd_dq",
        interpret=interpret,
    )(q3, k3, v3, do3, stats, delta, mask_in)

    return (dq3.reshape(b, h, tq, d), dk3.reshape(b, h, tk, d),
            dv3.reshape(b, h, tk, d))


def _xla_attention(q, k, v, mask, scale, causal):
    prec = _dot_precision(q.dtype)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32,
                        precision=prec) * scale
    if mask is not None:
        logits = logits + mask.astype(jnp.float32)
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((tq, tk), bool), tk - tq)
        logits = jnp.where(cm, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v,
                      precision=prec)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, mask, scale, causal, block_q, block_k, interpret):
    out, _ = _pallas_forward(q, k, v, mask, scale, causal, block_q, block_k,
                             interpret)
    return out


def _flash_fwd(q, k, v, mask, scale, causal, block_q, block_k, interpret):
    out, stats = _pallas_forward(q, k, v, mask, scale, causal, block_q,
                                 block_k, interpret)
    return out, (q, k, v, mask, out, stats)


def _xla_dmask(q, k, v, mask, out, lse, g, scale, causal):
    """Mask cotangent via the straight softmax-backward formula. This DOES
    materialize (B,H,Tq,Tk) — but it is emitted as a standalone expression,
    so when the mask grad is unused (padding masks, the BERT/ERNIE case)
    XLA dead-code-eliminates it and only the Pallas kernels remain."""
    prec = _dot_precision(q.dtype)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32), precision=prec) * scale
    s = s + mask.astype(jnp.float32)
    p = jnp.exp(s - lse[..., None])
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        p = jnp.where(jnp.tril(jnp.ones((tq, tk), bool), tk - tq), p, 0.0)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    dp = jnp.einsum("bhqd,bhkd->bhqk", g.astype(jnp.float32),
                    v.astype(jnp.float32), precision=prec)
    ds = p * (dp - delta[..., None])
    reduce_axes = tuple(ax for ax in range(4)
                        if mask.shape[ax] == 1 and ds.shape[ax] > 1)
    return jnp.sum(ds, axis=reduce_axes, keepdims=True).astype(mask.dtype)


def _flash_bwd(scale, causal, block_q, block_k, interpret, res, g):
    q, k, v, mask, out, stats = res
    # Pallas backward: recompute p from the (m, l, delta) residuals with
    # the mask applied in-kernel — the (T,T) matrix never touches HBM for
    # dq/dk/dv in either direction
    dq, dk, dv = _pallas_backward(q, k, v, mask, out, stats, g, scale,
                                  causal, block_q, block_k, interpret)
    if mask is None:
        return dq, dk, dv, None
    lse = (stats[:, 0] + jnp.log(stats[:, 1])).reshape(q.shape[:3])
    dmask = _xla_dmask(q, k, v, mask, out, lse, g, scale, causal)
    return dq, dk, dv, dmask


_flash.defvjp(_flash_fwd, _flash_bwd)


def _env_block(name, default=128):
    """Parse a block-size override; '' counts as unset (same contract as
    PADDLE_TPU_PALLAS_INTERPRET) and junk/too-small values fall back to
    the default LOUDLY — a bad tuning knob must not silently route every
    attention call to the XLA fallback via the auto-path try/except."""
    raw = os.environ.get(name, "")
    if not raw:
        return default
    try:
        val = int(raw)
    except ValueError:
        val = -1
    # must be a power of two >= 128: anything else either trips Mosaic's
    # 128-lane block alignment or gets halved down by the divisibility
    # loop until the size guards route EVERY call to the XLA fallback
    if val < 128 or val & (val - 1):
        import warnings
        warnings.warn("%s=%r is not a power-of-two block size >= 128; "
                      "using %d" % (name, raw, default))
        return default
    return val


def flash_attention(q, k, v, mask=None, scale=1.0, causal=False,
                    block_q=None, block_k=None, interpret=None):
    """Flash attention entry. q,k,v: (B,H,T,D). Falls back to interpret
    mode off-TPU so tests exercise the same kernel, and to plain fused XLA
    attention when shapes are too small to tile.

    Block sizes default to 128x128; PADDLE_TPU_FLASH_BLOCK_Q/_K override
    fleet-wide (apply the winner of `bench.py flashtune`)."""
    if block_q is None:
        block_q = _env_block("PADDLE_TPU_FLASH_BLOCK_Q")
    if block_k is None:
        block_k = _env_block("PADDLE_TPU_FLASH_BLOCK_K")
    if interpret is None:
        interpret = pd.default_interpret()
    tq, tk = q.shape[2], k.shape[2]
    if causal and tq > tk:
        # rows i < tq - tk see no keys at all; only the XLA reference
        # defines that edge (uniform over all-masked logits)
        return _xla_attention(q, k, v, mask, scale, causal)
    bq, bk = min(block_q, tq), min(block_k, tk)
    while tq % bq:
        bq //= 2
    while tk % bk:
        bk //= 2
    if bq < 8 or bk < 8 or q.shape[-1] % 8:
        return _xla_attention(q, k, v, mask, scale, causal)
    if not interpret and (bq < 128 or bk < 128):
        # Mosaic wants the last-two block dims 128-lane aligned (the stats
        # block puts block_q on the lane dim); sub-128 tiles are only
        # exercised in interpret mode — on device route them to XLA.
        return _xla_attention(q, k, v, mask, scale, causal)
    return _flash(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                  None if mask is None else jnp.asarray(mask),
                  scale, causal, bq, bk, interpret)
