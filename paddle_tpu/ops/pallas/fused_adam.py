"""Fused Adam update (Pallas TPU).

The per-parameter optimizer sweep: `optimizer_ops._adam` emits a chain
of ~10 elementwise XLA ops per parameter (two moment EMAs, sqrt, div,
subtract, three dtype casts). This kernel does the whole
read-modify-write — m/v/param in, m/v/param out — in ONE pass per
parameter tile, so each tensor is streamed through VMEM exactly once
per step instead of once per intermediate (the tensor-processing-
primitives argument from PAPERS.md applied to the update sweep).

Layout: the parameter is flattened, zero-padded to a (rows, 128) lane
layout and tiled over row blocks; the bias-corrected learning rate
``lr_t = lr * sqrt(1 - b2^t) / (1 - b1^t)`` is a traced (1, 1) scalar
input (beta powers update outside — they are O(1)). Math is f32 like
the XLA kernel: bf16 params round-trip through f32, moments stay f32.

On CPU the kernel runs in interpret mode (tier-1 exercises the real
kernel logic); `fused_adam` returns None when the parameter is too
small to tile, and the caller keeps the XLA chain.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

from .. import pallas_dispatch as pd

_LANES = 128


def _adam_kernel(lr_ref, p_ref, g_ref, m1_ref, m2_ref,
                 pn_ref, m1n_ref, m2n_ref, *, beta1, beta2, eps):
    lr_t = lr_ref[0, 0]
    g = g_ref[...].astype(jnp.float32)
    m1n = beta1 * m1_ref[...] + (1.0 - beta1) * g
    m2n = beta2 * m2_ref[...] + (1.0 - beta2) * g * g
    pn = p_ref[...].astype(jnp.float32) - lr_t * m1n / (jnp.sqrt(m2n) + eps)
    pn_ref[...] = pn.astype(pn_ref.dtype)
    m1n_ref[...] = m1n
    m2n_ref[...] = m2n


def _to_lanes(x, rows, dtype):
    """Flatten to (rows, 128) with zero padding (padded cells update to
    zero under Adam-from-zero-state and are sliced off anyway)."""
    flat = x.reshape(-1).astype(dtype)
    pad = rows * _LANES - flat.shape[0]
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), dtype)])
    return flat.reshape(rows, _LANES)


def fused_adam(p, g, m1, m2, lr_t, beta1=0.9, beta2=0.999, eps=1e-8,
               block_rows=256, interpret=None):
    """One-pass Adam: returns (p_new, m1_new, m2_new) with p_new in
    p.dtype and f32 moments, or None when the parameter is too small to
    tile (< one (8, 128) f32 tile — the XLA chain is cheaper there).
    `lr_t` is the bias-corrected scalar learning rate (traced)."""
    if interpret is None:
        interpret = pd.default_interpret()
    n = int(p.size)
    rows = -(-n // _LANES)                      # ceil
    if rows < 8:
        return None
    # pad rows to a multiple of 8 first (f32 sublane tile), then to the
    # block multiple, so compiled blocks are always (8k, 128)-aligned;
    # padded cells update to zero and are sliced off below
    rows = -(-rows // 8) * 8
    br = min(block_rows, rows)
    if not interpret and br % 8:
        return None
    rows_p = -(-rows // br) * br
    p2 = _to_lanes(p, rows_p, p.dtype)
    g2 = _to_lanes(g, rows_p, jnp.float32)
    m12 = _to_lanes(m1, rows_p, jnp.float32)
    m22 = _to_lanes(m2, rows_p, jnp.float32)
    lr2 = jnp.asarray(lr_t, jnp.float32).reshape(1, 1)
    blk = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    pn, m1n, m2n = pl.pallas_call(
        functools.partial(_adam_kernel, beta1=float(beta1),
                          beta2=float(beta2), eps=float(eps)),
        grid=(rows_p // br,),
        in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0)),
                  blk, blk, blk, blk],
        out_specs=[blk, blk, blk],
        out_shape=[
            jax.ShapeDtypeStruct((rows_p, _LANES), p.dtype),
            jax.ShapeDtypeStruct((rows_p, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows_p, _LANES), jnp.float32),
        ],
        name="fused_adam",
        interpret=bool(interpret),
    )(lr2, p2, g2, m12, m22)

    def _back(x, dtype):
        return x.reshape(-1)[:n].reshape(p.shape).astype(dtype)

    return (_back(pn, p.dtype), _back(m1n, jnp.float32),
            _back(m2n, jnp.float32))
