#!/usr/bin/env python
"""Microbenchmark of the delta rule's sub-block math, alone on the chip.

One line a form: the two pieces of `ops/linear_attn_ops.py:_intra` that have
a hand-written forward and backward (the unit-lower-triangular solve of a
chunk and the decay products inside a sub-block), each jitted by itself at
the `kimi-linear-48b-a3b.t8192-b2` cell's shapes by default (one group of 16
chunks, B 2, H 16, K 128: `low` (16, 2, 16, 64, 64), q, k and the cumulative
decay (16, 2, 16, 4, 16, 128), float32), beside PR 33's spellings, which
live on here alone: the inverse built by `x.at[..., i, :].set(row)` with a
block forward substitution and the plain products, both under jax's own
pullback. `ms` is wall time a call over `--calls` calls dispatched back to
back behind one `block_until_ready`; a "+ pullback" line runs the forward
and the pullback of a fixed cotangent; `gap` is the largest difference from
PR 33's spelling over its largest number. A step runs a group's forward three times and its
pullback once, in 8 groups and 4 layers: a step's share is 32 times a line.
Alone, XLA fuses and lays out as it likes: read a form's time inside the
step from the step's trace, not from here.

`--kernels` times the whole op instead, at the cell's call (B 2, T 8192, H 16,
K = V 128, bfloat16 with g float32): the XLA form and the `kda_fwd` /
`kda_bwd` Pallas kernels (`ops/pallas/delta_rule.py`), forward alone and
forward + backward, ms a call, the median and the range of `--runs` runs of
`--calls` calls each, one line a `--heads-a-step` tried; `gap` is against
the XLA form on the same chip.

  python tools/mb_kda_intra.py                # on the chip tool
  python tools/mb_kda_intra.py --kernels --heads-a-step 1,2,4
  JAX_PLATFORMS=cpu python tools/mb_kda_intra.py --walk-through --chunks 2 \
      --batch 1 --heads 2 --d-k 32 --calls 2  # no device time: exits 1 without the flag
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402

from paddle_tpu.ops import linear_attn_ops as la            # noqa: E402
from paddle_tpu.ops.pallas import delta_rule                # noqa: E402

_F32 = jnp.float32
_mm32 = la._mm32


def rows_by_scatter(low):
    """PR 33's 16-row inverse: each row written into the array."""
    r = low.shape[-1]
    eye = jnp.eye(r, dtype=_F32)
    x = jnp.broadcast_to(eye, low.shape)
    for i in range(1, r):
        row = eye[i] - jnp.sum(low[..., i, :, None] * x, axis=-2)
        x = x.at[..., i, :].set(row)
    return x


def diagonal_blocks(low):
    sub, ns = la.SUB, low.shape[-1] // la.SUB
    return jnp.stack([low[..., i * sub:(i + 1) * sub, i * sub:(i + 1) * sub]
                      for i in range(ns)], axis=-3)


def solve_by_block_rows(low, rows_inverse):
    """PR 33's solve: the diagonal blocks by `rows_inverse`, then a block
    forward substitution, one block row after the other."""
    sub, ns, lead = la.SUB, low.shape[-1] // la.SUB, low.shape[:-2]
    diag_inv = rows_inverse(diagonal_blocks(low))
    x = diag_inv[..., 0, :, :]
    for i in range(1, ns):
        before = i * sub
        row = -_mm32("...rs,...sj->...rj", diag_inv[..., i, :, :], _mm32(
            "...rs,...sj->...rj", low[..., before:before + sub, :before], x))
        x = jnp.concatenate([
            jnp.concatenate([x, jnp.zeros(lead + (before, sub), _F32)], -1),
            jnp.concatenate([row, diag_inv[..., i, :, :]], -1)], axis=-2)
    return x


def plain_products(q_b, k_b, cum_b, scale):
    """PR 33's products, for jax to pull back."""
    sub = la.SUB
    within = jnp.tril(jnp.ones((sub, sub), bool))
    decay = jnp.exp(jnp.where(
        within[..., None], cum_b[..., :, None, :] - cum_b[..., None, :, :],
        -jnp.inf))
    kk = jnp.sum(k_b[..., :, None, :] * k_b[..., None, :, :] * decay,
                 axis=-1) * jnp.tril(jnp.ones((sub, sub), _F32), -1)
    qk = jnp.sum(q_b[..., :, None, :] * k_b[..., None, :, :] * decay,
                 axis=-1) * scale
    return kk, qk


def with_pullback(fn, cot):
    def run(*operands):
        out, pull = jax.vjp(fn, *operands)
        return out, pull(cot)
    return run


def forms(chunks, batch, heads, d_k):
    """[(name, fn, operands, index of the form it is compared with)]."""
    lead, c, sub = (chunks, batch, heads), la.CHUNK, la.SUB
    keys = jax.random.split(jax.random.PRNGKey(0), 8)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    def blocks(x):
        return x.reshape(lead + (c // sub, sub, d_k))

    q = unit(jax.random.normal(keys[0], lead + (c, d_k)))
    k = unit(jax.random.normal(keys[1], lead + (c, d_k)))
    g = -0.3 * jax.nn.softplus(jax.random.normal(keys[2], lead + (c, d_k)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[3], lead + (c,)))
    scale = d_k ** -0.5
    cum = jnp.cumsum(g, axis=-2)
    q_b, k_b, cum_b = blocks(q), blocks(k), blocks(cum)
    low = beta[..., None] * jnp.tril(jnp.einsum(
        "...ik,...jk->...ij", k * jnp.exp(cum), k * jnp.exp(-cum)), -1)
    d_x = jax.random.normal(keys[4], lead + (c, c))
    d_kk = jax.random.normal(keys[5], lead + (c // sub, sub, sub))
    d_qk = jax.random.normal(keys[6], lead + (c // sub, sub, sub))

    # each solve masks its operand itself, as `_intra` does, so that a
    # pullback is compared on the strictly lower triangle alone
    def old_solve(low):
        return solve_by_block_rows(jnp.tril(low, -1), rows_by_scatter)

    def new_solve(low):
        return la._unit_lower_inverse(jnp.tril(low, -1))

    def old_products(q_b, k_b, cum_b):
        return plain_products(q_b, k_b, cum_b, scale)

    def new_products(q_b, k_b, cum_b):
        return la._decay_products(q_b, k_b, cum_b, scale)

    trio = (q_b, k_b, cum_b)
    return [
        ("solve, PR 33: rows by .at[].set, block rows", old_solve, (low,), 0),
        ("solve, now: blocks as a finite series, 2 x 2 block recursion",
         new_solve, (low,), 0),
        ("solve + pullback, PR 33: jax's, through 15 row updates",
         with_pullback(old_solve, d_x), (low,), 2),
        ("solve + pullback, now: -strictly_lower(X^T dX X^T)",
         with_pullback(new_solve, d_x), (low,), 2),
        ("in-block decay products, PR 33", old_products, trio, 4),
        ("in-block decay products, now", new_products, trio, 4),
        ("products + pullback, PR 33: jax's (16, 16, K) cotangents",
         with_pullback(old_products, (d_kk, d_qk)), trio, 6),
        ("products + pullback, now: three products summed over rows",
         with_pullback(new_products, (d_kk, d_qk)), trio, 6),
    ]


def gap(got, want):
    """Largest |got - want| of any leaf over that leaf's largest |want|."""
    return max(float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))
               for a, b in zip(jax.tree_util.tree_leaves(got),
                               jax.tree_util.tree_leaves(want)))


def op_inputs(batch, seq, heads, d_k, dtype):
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    shape = (batch, seq, heads, d_k)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q, k = (unit(jax.random.normal(key, shape)) for key in keys[:2])
    v = jax.random.normal(keys[2], shape)
    g = -0.3 * jax.nn.softplus(jax.random.normal(keys[3], shape))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3]))
    cot = jax.random.normal(keys[5], shape)
    return tuple(x.astype(dtype) for x in (q, k, v)) + (g,) \
        + (beta.astype(dtype), cot.astype(dtype))


def time_calls(fn, operands, calls, runs):
    """ms a call: (median, least, most) over `runs` runs of `calls` calls
    dispatched back to back behind one `block_until_ready`."""
    jax.block_until_ready(fn(*operands))
    jax.block_until_ready(fn(*operands))
    found = []
    for _ in range(runs):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*operands)
        jax.block_until_ready(out)
        found.append((time.perf_counter() - t0) * 1e3 / calls)
    found.sort()
    return found[len(found) // 2], found[0], found[-1]


def kernels_mode(args, interpret):
    *operands, cot = op_inputs(args.batch, args.seq, args.heads, args.d_k,
                               jnp.bfloat16)
    scale = args.d_k ** -0.5

    def both(fn):
        def run(*xs):
            out, pull = jax.vjp(fn, *xs)
            return out, pull(cot)
        return run

    def xla(*xs):
        return la._kda(*xs, scale)

    def kernels(heads_a_step):
        def run(*xs):       # a function a tile: jit keys its cache on it
            delta_rule.pick_heads = lambda h: heads_a_step
            return delta_rule.kda(*xs, scale, interpret)
        return run

    print("%-44s %9s %9s %9s" % ("form", "median ms", "least", "most"))
    want = None
    table = [("xla", xla)] + [
        ("kda_fwd, kda_bwd: %d heads a step" % n, kernels(n))
        for n in args.heads_a_step]
    for name, fn in table:
        jax.clear_caches()  # the kernels' own jitted calls hold the last tile
        for what, run in (("forward", jax.jit(fn)),
                          ("forward + backward", jax.jit(both(fn)))):
            ms = time_calls(run, operands, args.calls, args.runs)
            print("%-44s %9.3f %9.3f %9.3f"
                  % (((name + ", " + what)[:44],) + ms), flush=True)
        got = jax.jit(both(fn))(*operands)
        if want is None:
            want = got
        else:
            f32 = jax.tree_util.tree_map(lambda x: x.astype(_F32),
                                         (got, want))
            print("%-44s gap %.2e" % (name[:44], gap(*f32)))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--kernels", action="store_true",
                    help="time the whole op: the XLA form against the "
                         "kda_fwd / kda_bwd kernels")
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--heads-a-step", default=None,
                    type=lambda s: [int(n) for n in s.split(",")],
                    help="heads a grid step holds, each tried in turn "
                         "(default: what the op picks)")
    ap.add_argument("--chunks", type=int, default=la.GROUP)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--d-k", type=int, default=128)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--walk-through", action="store_true",
                    help="run off the TPU too: the times are no device times")
    args = ap.parse_args()
    dev = jax.devices()[0]
    print("device platform=%s kind=%r; a group of %d chunks of %d tokens, "
          "sub-blocks of %d, B %d, H %d, K %d, float32, %d calls a form"
          % (dev.platform, dev.device_kind, args.chunks, la.CHUNK, la.SUB,
             args.batch, args.heads, args.d_k, args.calls))
    if dev.platform != "tpu":
        if not args.walk_through:
            sys.exit("not a TPU: no device time to report (--walk-through "
                     "runs the forms all the same)")
        print("not a TPU: the times below are no device times")
    if args.kernels:
        if args.heads_a_step is None:
            args.heads_a_step = [delta_rule.pick_heads(args.heads)]
        return kernels_mode(args, interpret=dev.platform != "tpu")
    table = forms(args.chunks, args.batch, args.heads, args.d_k)
    results = []
    for name, fn, operands, _against in table:
        fn = jax.jit(fn)
        results.append(jax.block_until_ready(fn(*operands)))
        jax.block_until_ready(fn(*operands))
        t0 = time.perf_counter()
        for _ in range(args.calls):     # in order on one chip: the last
            out = fn(*operands)         # result's arrival ends them all
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) * 1e3 / args.calls
        print("%-66s %8.3f ms" % (name, ms), flush=True)
    for (name, _fn, _operands, against), got in zip(table, results):
        if table[against][0] != name:
            print("%-66s gap %.2e" % (name, gap(got, results[against])))


if __name__ == "__main__":
    main()
