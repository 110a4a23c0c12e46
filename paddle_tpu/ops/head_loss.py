"""The weighted form of the LM head's loss: ``Σ_t w_t · ce_t`` of
``hidden (T, D) @ weight (V, D)ᵀ (+ bias)`` against ``label (T,)``, formed
block by block over the token axis so that no ``[tokens, vocab]`` array
outlives one block, in either direction, and no matmul runs twice.

The loss is a scalar, so its cotangent is one too, and each block's
gradients can be formed the moment its probabilities exist: the forward
rule of the ``custom_vjp`` walks the blocks under ``lax.scan`` with
``dWeight``, ``dBias`` and the loss as the carry, emits ``dHidden`` block
by block and keeps those three as the residuals; the backward rule scales
them by the cotangent. The primal rule (a forward-only program) walks the
same blocks for the loss alone. Every dot has the projection's operand
dtype and accumulates in float32, and the softmax is float32, as in the
per-token form (``ops/nn_ops._fused_mlm_head_loss``).

Block ``b`` of ``n`` holds the rows ``b, n + b, 2n + b, …``: a token axis
that a mesh cuts into contiguous runs (a dp-sharded batch) is then cut
the same way inside every block, where a block of contiguous rows would
put one device's rows in each loop step.
"""
from functools import partial

import jax
import jax.numpy as jnp

_BLOCK_STEP, _BLOCK_CAP = 512, 4096


def block_rows(rows):
    """Rows a block: the largest multiple of 512, at most 4096, that
    divides the row count; else all of them in one block."""
    for cand in range(_BLOCK_CAP, 0, -_BLOCK_STEP):
        if rows % cand == 0:
            return cand
    return rows


def _blocked(x, n):
    """(T, …) -> (n, T/n, …), block b holding rows b, n + b, 2n + b, …"""
    return jnp.swapaxes(x.reshape((x.shape[0] // n, n) + x.shape[1:]), 0, 1)


def _unblocked(x):
    x = jnp.swapaxes(x, 0, 1)
    return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])


def _block_loss(h, w, bias, lbl, tw):
    """One block's ``Σ w·ce`` with what its gradients need: the
    exponentials ``e`` (T_b, V), their row sums ``s`` and the label mask."""
    logits = jnp.matmul(h, w.T, preferred_element_type=jnp.float32) \
        .astype(jnp.float32)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    shifted = logits - jnp.max(logits, axis=-1, keepdims=True)
    e = jnp.exp(shifted)
    s = jnp.sum(e, axis=-1, keepdims=True)
    at_label = jax.lax.broadcasted_iota(jnp.int32, e.shape, 1) == lbl
    picked = jnp.sum(jnp.where(at_label, shifted, 0.0), axis=-1,
                     keepdims=True)
    return jnp.sum(tw * (jnp.log(s) - picked)), e, s, at_label


def _walk(hidden, weight, bias, label, token_weight, cast_bf16, rows,
          with_grads):
    """The loss (float32 scalar) and, ``with_grads``, (dHidden, dWeight,
    dBias) for a cotangent of one, in the inputs' dtypes."""
    h, w = hidden, weight
    if cast_bf16:
        h, w = h.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
    n = h.shape[0] // (rows or block_rows(h.shape[0]))
    xs = (_blocked(h, n), _blocked(label.astype(jnp.int32)[:, None], n),
          _blocked(token_weight.astype(jnp.float32), n))

    def loss_only(acc, x):
        return acc + _block_loss(x[0], w, bias, x[1], x[2])[0], None

    def with_gradients(carry, x):
        acc, dw, db = carry
        h_b, lbl, tw = x
        loss, e, s, at_label = _block_loss(h_b, w, bias, lbl, tw)
        p = e * (tw / s)
        d32 = jnp.where(at_label, p - tw, p)
        d = d32.astype(w.dtype)
        dh = jnp.matmul(d, w, preferred_element_type=jnp.float32)
        dw = dw + jnp.matmul(d.T, h_b, preferred_element_type=jnp.float32)
        if db is not None:
            db = db + jnp.sum(d32, axis=0)
        return (acc + loss, dw, db), dh.astype(hidden.dtype)

    zero = jnp.zeros((), jnp.float32)
    if not with_grads:
        return jax.lax.scan(loss_only, zero, xs)[0], None
    carry = (zero, jnp.zeros(w.shape, jnp.float32),
             None if bias is None else jnp.zeros(bias.shape, jnp.float32))
    (loss, dw, db), dh = jax.lax.scan(with_gradients, carry, xs)
    return loss, (_unblocked(dh), dw.astype(weight.dtype),
                  None if db is None else db.astype(bias.dtype))


@partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def weighted_head_loss(hidden, weight, bias, label, token_weight,
                       cast_bf16=False, rows=None):
    """``Σ_t token_weight_t · ce_t``, a float32 scalar. hidden (T, D),
    weight (V, D), bias (V,) or None, label (T,) int, token_weight (T, 1)
    (no gradient flows to the last two). ``rows`` (tests) replaces
    `block_rows`' choice and has to divide T."""
    return _walk(hidden, weight, bias, label, token_weight, cast_bf16, rows,
                 False)[0]


def _fwd(hidden, weight, bias, label, token_weight, cast_bf16, rows):
    return _walk(hidden, weight, bias, label, token_weight, cast_bf16, rows,
                 True)


def _bwd(_cast_bf16, _rows, grads, g):
    dh, dw, db = (None if d is None else (g * d).astype(d.dtype)
                  for d in grads)
    return dh, dw, db, None, None


weighted_head_loss.defvjp(_fwd, _bwd)
