"""Fused LayerNorm forward + backward (Pallas TPU).

The pre/post-attention normalization of every BASELINE transformer
block. Forward computes mean/variance and the normalized output in one
pass over each (block_rows, cols) tile resident in VMEM — XLA's
lowering reads x once for the moments and again for the normalize —
saving the (mean, rstd) residuals per row. Backward is one pass too:
dx via the fused layernorm-backward formula, with dscale/dbias
accumulated across row blocks in VMEM scratch (the grid's sequential
dimension), so no (rows, cols)-sized intermediate beyond the
unavoidable dx.

Layout: 2-D ``x (rows, cols)`` normalized over the last axis; callers
collapse leading dims per begin_norm_axis. Per-row residuals ride the
(8, rows) sublane-padded layout (row 0 real — same convention as
flash_attention's lse). Rows are zero-padded to the block multiple
(padded rows normalize garbage that is sliced off; their zero
cotangents contribute nothing to dscale/dbias). On CPU the kernels run
in interpret mode; `fused_layer_norm` returns None when the shape
cannot tile (compiled Mosaic wants cols 128-aligned) and callers keep
the XLA lowering.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

from .blockwise_ce import _rows8
from .. import pallas_dispatch as pd


def _ln_fwd_kernel(x_ref, scale_ref, bias_ref, y_ref, mean_ref, rstd_ref,
                   *, eps):
    x = x_ref[...].astype(jnp.float32)              # (BR, C)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = (xc * rstd) * scale_ref[0][None, :].astype(jnp.float32) \
        + bias_ref[0][None, :].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[...] = jnp.broadcast_to(mean[:, 0][None, :], mean_ref.shape)
    rstd_ref[...] = jnp.broadcast_to(rstd[:, 0][None, :], rstd_ref.shape)


def _ln_bwd_kernel(x_ref, g_ref, scale_ref, mean_ref, rstd_ref,
                   dx_ref, dscale_ref, dbias_ref, ds_acc, db_acc):
    i = pl.program_id(0)
    n = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        ds_acc[:] = jnp.zeros_like(ds_acc)
        db_acc[:] = jnp.zeros_like(db_acc)

    x = x_ref[...].astype(jnp.float32)              # (BR, C)
    g = g_ref[...].astype(jnp.float32)
    mean = mean_ref[0][:, None]                     # (BR, 1)
    rstd = rstd_ref[0][:, None]
    xhat = (x - mean) * rstd
    gs = g * scale_ref[0][None, :].astype(jnp.float32)
    mg = jnp.mean(gs, axis=-1, keepdims=True)
    mgx = jnp.mean(gs * xhat, axis=-1, keepdims=True)
    dx_ref[...] = (rstd * (gs - mg - xhat * mgx)).astype(dx_ref.dtype)
    ds_acc[:] = ds_acc[:] + jnp.broadcast_to(
        jnp.sum(g * xhat, axis=0, keepdims=True), ds_acc.shape)
    db_acc[:] = db_acc[:] + jnp.broadcast_to(
        jnp.sum(g, axis=0, keepdims=True), db_acc.shape)

    @pl.when(i == n - 1)
    def _fin():
        dscale_ref[...] = ds_acc[:].astype(dscale_ref.dtype)
        dbias_ref[...] = db_acc[:].astype(dbias_ref.dtype)


def _pad_rows(x, rows_p, dtype):
    pad = rows_p - x.shape[0]
    if pad:
        x = jnp.concatenate(
            [x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    return x.astype(dtype)


def _ln_call_fwd(x, scale, bias, eps, block_rows, interpret):
    rows, cols = x.shape
    rows_p = -(-rows // block_rows) * block_rows
    x2 = _pad_rows(x, rows_p, x.dtype)
    blk = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    row8 = pl.BlockSpec((8, block_rows), lambda i: (0, i))
    vec = pl.BlockSpec((8, cols), lambda i: (0, 0))
    y, mean, rstd = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, eps=float(eps)),
        grid=(rows_p // block_rows,),
        in_specs=[blk, vec, vec],
        out_specs=[blk, row8, row8],
        out_shape=[
            jax.ShapeDtypeStruct((rows_p, cols), x.dtype),
            jax.ShapeDtypeStruct((8, rows_p), jnp.float32),
            jax.ShapeDtypeStruct((8, rows_p), jnp.float32),
        ],
        name="layer_norm_fwd",
        interpret=interpret,
    )(x2, _rows8(scale, jnp.float32), _rows8(bias, jnp.float32))
    return y[:rows], mean[0, :rows], rstd[0, :rows]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ln(x, scale, bias, eps, block_rows, interpret):
    y, _, _ = _ln_call_fwd(x, scale, bias, eps, block_rows, interpret)
    return y


def _ln_fwd(x, scale, bias, eps, block_rows, interpret):
    y, mean, rstd = _ln_call_fwd(x, scale, bias, eps, block_rows,
                                 interpret)
    return y, (x, scale, bias, mean, rstd)


def _ln_bwd(eps, block_rows, interpret, res, g):
    x, scale, bias, mean, rstd = res
    rows, cols = x.shape
    rows_p = -(-rows // block_rows) * block_rows
    x2 = _pad_rows(x, rows_p, x.dtype)
    g2 = _pad_rows(g, rows_p, g.dtype)
    mean_p = jnp.pad(mean, (0, rows_p - rows))
    rstd_p = jnp.pad(rstd, (0, rows_p - rows))
    blk = pl.BlockSpec((block_rows, cols), lambda i: (i, 0))
    row8 = pl.BlockSpec((8, block_rows), lambda i: (0, i))
    vec = pl.BlockSpec((8, cols), lambda i: (0, 0))
    dx, ds8, db8 = pl.pallas_call(
        _ln_bwd_kernel,
        grid=(rows_p // block_rows,),
        in_specs=[blk, blk, vec, row8, row8],
        out_specs=[blk, vec, vec],
        out_shape=[
            jax.ShapeDtypeStruct((rows_p, cols), x.dtype),
            jax.ShapeDtypeStruct((8, cols), jnp.float32),
            jax.ShapeDtypeStruct((8, cols), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((8, cols), jnp.float32),
            pltpu.VMEM((8, cols), jnp.float32),
        ],
        name="layer_norm_bwd",
        interpret=interpret,
    )(x2, g2, _rows8(scale, jnp.float32), _rows8(mean_p, jnp.float32),
      _rows8(rstd_p, jnp.float32))
    return (dx[:rows], ds8[0].astype(scale.dtype),
            db8[0].astype(bias.dtype))


_ln.defvjp(_ln_fwd, _ln_bwd)


def fused_layer_norm(x, scale, bias, eps=1e-5, block_rows=128,
                     interpret=None):
    """Fused LayerNorm over the last axis of 2-D ``x (rows, cols)`` with
    ``scale (cols,)`` / ``bias (cols,)``. Returns y in x.dtype, or None
    when the shape cannot tile (caller keeps the XLA lowering).
    Differentiable wrt x/scale/bias with one-pass Pallas fwd and bwd."""
    if interpret is None:
        interpret = pd.default_interpret()
    rows, cols = x.shape
    br = min(block_rows, max(rows, 1))
    if rows < 1 or cols < 8:
        return None
    # compiled Mosaic wants cols 128-lane aligned AND br a 128-multiple
    # (the (8, block_rows) mean/rstd residuals put br on the lane dim —
    # same constraint as flash_attention's lse): round br down to the
    # alignment, bail to XLA when nothing fits; interpret mode takes
    # any tile
    if not interpret:
        br = (br // 128) * 128
        if cols % 128 or br < 128:
            return None
    br = max(br, 1)
    return _ln(jnp.asarray(x), jnp.asarray(scale), jnp.asarray(bias),
               float(eps), int(br), bool(interpret))
