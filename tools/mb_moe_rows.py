#!/usr/bin/env python
"""Microbenchmark of the expert layer's row movement, alone on the chip.

One line a form: what `ops/moe_ops.py` does between token order and expert
order (and PR 31's forms it was measured against), each jitted by itself at
the `lfm2-8b-a1b.t8192-b2` cell's shapes by default: X (16,384, 2,048)
bfloat16, top-4 of 32 experts with 8 held, a row buffer of 69,632 rows, an
expert width of 1,792. `ms` is wall time a call over `--calls` calls
dispatched back to back behind one `block_until_ready`; `GB/s` is the bytes
the form has to move (operands read once + results written once) over that
time, so a form that writes an intermediate out reads low. Alone, XLA may
hold a 64 MiB operand in VMEM (the token-order arrays; never the buffer),
where a row gather runs several times faster than out of HBM: read a form's
time inside the step from the step's trace, not from here.

  python tools/mb_moe_rows.py                 # on the chip tool
  JAX_PLATFORMS=cpu python tools/mb_moe_rows.py --walk-through --tokens 256 \
      --d 128 --ffn 128 --calls 2             # no device time: exits 1 without the flag
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402

from paddle_tpu.ops import moe_ops                          # noqa: E402
from paddle_tpu.ops.pallas import grouped_matmul as gmm     # noqa: E402


TOP_K, EXPERTS, HELD = 4, 32, 8     # the cell's routing


def forms(tokens, d, ffn):
    """[(name, fn, args, bytes the form must move)], bfloat16."""
    k, dtype = TOP_K, jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    picks = jnp.argsort(jax.random.uniform(keys[0], (tokens, EXPERTS)),
                        axis=1)[:, :k].astype(jnp.int32)
    pos, row_pair, _sizes, _tg = jax.jit(
        lambda p: moe_ops.dispatch_plan(p, 0, HELD))(picks)
    rows = row_pair.shape[0]
    x = jax.random.normal(keys[1], (tokens, d)).astype(dtype)
    buf = jax.random.normal(keys[2], (rows, d)).astype(dtype)
    d_out = jax.random.normal(keys[3], (tokens, d)).astype(dtype)
    w = jax.random.uniform(keys[4], (tokens, k), jnp.float32)
    both = jax.random.normal(keys[5], (rows, 2 * ffn)).astype(dtype)
    row_b = d * 2
    pairs = tokens * k
    key = jnp.where(picks.reshape(-1) < HELD, picks.reshape(-1), HELD)

    def gathered(buf, pos):     # PR 31's read: one (tokens, k, d) gather
        return jnp.take(buf, jnp.minimum(pos, rows - 1), axis=0)

    def gather4_wsum(buf, w, pos):                  # PR 31's _combine
        got = gathered(buf, pos).astype(jnp.float32)
        part = jnp.where((pos < rows)[..., None], got * w[..., None], 0.0)
        return jnp.sum(part, axis=1).astype(buf.dtype)

    def gather4_dot(buf, d_out, pos):               # PR 31's dw
        got = gathered(buf, pos).astype(jnp.float32)
        dw = jnp.sum(got * d_out.astype(jnp.float32)[:, None, :], axis=-1)
        return jnp.where(pos < rows, dw, 0.0)

    def one_gather_then_reduce(buf, pos):
        got = jax.lax.optimization_barrier(gathered(buf, pos))
        return jnp.sum(jnp.where((pos < rows)[..., None],
                                 got.astype(jnp.float32), 0.0),
                       axis=1).astype(buf.dtype)

    def combine_bwd(buf, w, d_out):
        _out, vjp = jax.vjp(lambda y, w_: moe_ops._combine(
            y, w_, pos, row_pair), buf, w)
        return vjp(d_out)

    def silu(both):
        gate, up = jnp.split(both, 2, axis=1)
        return (jax.nn.silu(gate.astype(jnp.float32))
                * up.astype(jnp.float32)).astype(both.dtype)

    return [
        ("argsort, stable, %d int32 keys" % pairs,
         lambda a: jnp.argsort(a, stable=True), (key,), 2 * 4 * pairs),
        ("dispatch_plan whole (picks -> pos, row_pair, sizes, tile_group)",
         lambda p: moe_ops.dispatch_plan(p, 0, HELD), (picks,),
         4 * (2 * pairs + rows)),
        ("take of %d rows, X -> the buffer (moe_ops._rows_of_tokens)" % rows,
         lambda x, rp: moe_ops._rows_of_tokens(x, rp, k), (x, row_pair),
         2 * rows * row_b),
        ("take of %d rows of the buffer (one pick)" % tokens,
         lambda b, p: jnp.take(b, p[:, 0], axis=0, mode="clip"), (buf, pos),
         2 * tokens * row_b),
        ("PR 31: (tokens, %d) gather, float32 weighted sum" % k,
         gather4_wsum, (buf, w, pos), (k + 1) * tokens * row_b),
        ("PR 31: (tokens, %d) gather, dot against dOut" % k,
         gather4_dot, (buf, d_out, pos), (k + 1) * tokens * row_b),
        ("%d takes accumulated in float32 (moe_ops.sum_of_picks)" % k,
         moe_ops.sum_of_picks, (buf, pos), (k + 1) * tokens * row_b),
        ("%d takes, weighted (moe_combine's forward)" % k,
         moe_ops.sum_of_picks, (buf, pos, w), (k + 1) * tokens * row_b),
        ("one gather held in bfloat16, then the float32 reduce",
         one_gather_then_reduce, (buf, pos), (k + 1) * tokens * row_b),
        ("moe_combine's backward: dy (one take) and dw (%d takes, dotted)"
         % k, combine_bwd, (buf, w, d_out),
         (2 * rows + (k + 1) * tokens) * row_b),
        ("silu(gate) * up over the whole buffer (moe_experts' pass)",
         silu, (both,), 3 * rows * ffn * 2),
    ]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--d", type=int, default=2048)
    ap.add_argument("--ffn", type=int, default=1792)
    ap.add_argument("--calls", type=int, default=30)
    ap.add_argument("--walk-through", action="store_true",
                    help="run off the TPU too: the times are no device times")
    args = ap.parse_args()
    dev = jax.devices()[0]
    pairs = args.tokens * TOP_K
    print("device platform=%s kind=%r; X (%d, %d) bfloat16, top-%d of %d "
          "experts, %d held, buffer %d rows, expert width %d, %d calls a form"
          % (dev.platform, dev.device_kind, args.tokens, args.d, TOP_K,
             EXPERTS, HELD, gmm.buffer_rows(pairs, HELD, gmm.row_tile(pairs)),
             args.ffn, args.calls))
    if dev.platform != "tpu":
        if not args.walk_through:
            sys.exit("not a TPU: no device time to report (--walk-through "
                     "runs the forms all the same)")
        print("not a TPU: the times below are no device times")
    for name, fn, operands, nbytes in forms(args.tokens, args.d, args.ffn):
        fn = jax.jit(fn)
        jax.block_until_ready(fn(*operands))
        jax.block_until_ready(fn(*operands))
        t0 = time.perf_counter()
        for _ in range(args.calls):     # in order on one chip: the last
            out = fn(*operands)         # result's arrival ends them all
        jax.block_until_ready(out)
        ms = (time.perf_counter() - t0) * 1e3 / args.calls
        print("%-66s %8.3f ms %8.1f GB/s" % (name, ms, nbytes / ms / 1e6))


if __name__ == "__main__":
    main()
