#!/usr/bin/env python
"""Microbenchmark of the expert layer's row passes, alone on the chip.

One line a form, each new form under the form it replaces: what
`ops/moe_ops.py` does between token order and expert order, and over the
rows of the buffer, each jitted by itself at the shapes of one of the two
expert cells: `--cell lfm2` (the default: X (16,384, 2,048) bfloat16, top-4
of 32 experts with 8 held, a row buffer of 69,632 rows, an expert width of
1,792) or `--cell kimi` (X (16,384, 2,304), top-8 of 256 with 8 held,
135,168 rows, width 1,024). "whole" is PR 32's spelling, which costs by the
buffer (or by every pick of every token) and lives here and in
`tests/test_moe_ops.py` alone (but for X -> buffer, where the op keeps it);
"in use" is the op's, which costs by the rows the plan laid out. `--rows-in-use N` draws picks that put N pairs on the held
experts (the plan lays out N rows and each group's padding); without it the
picks are uniform (held share = held / experts). `ms` is wall time a call
over `--calls` calls dispatched back to back behind one `block_until_ready`;
`GB/s` is the bytes the form has to move at the rows in use (operands read
once + results written once) over that time. Alone, XLA may hold a 64 MiB
operand in VMEM (the token-order arrays; never the buffer), where a row
gather runs several times faster than out of HBM; and a form that writes
over an operand it has read (`moe_combine`'s backward over y, the silu
pass's backward over its input) pays a copy of that operand here, where the
operand lives on for the next call, and none in the step, where it is dead:
read a form's time inside the step from the step's trace, not from here.

  python tools/mb_moe_rows.py --cell kimi --rows-in-use 8192   # on the chip tool
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
import numpy as np              # noqa: E402

from paddle_tpu.ops import moe_ops                          # noqa: E402
from paddle_tpu.ops.pallas import grouped_matmul as gmm     # noqa: E402


#: a cell's routing and widths: tokens, d, expert width, top_k, experts, held
CELLS = {"lfm2": (16384, 2048, 1792, 4, 32, 8),
         "kimi": (16384, 2304, 1024, 8, 256, 8)}


def draw_picks(tokens, top_k, experts, held, held_pairs, seed=0):
    """picks (tokens, top_k) int32, distinct a token. `held_pairs` None:
    uniform over the experts. Else that many pairs on experts 0 .. held - 1,
    spread over the tokens as evenly as their number allows."""
    rng = np.random.default_rng(seed)
    if held_pairs is None:
        return np.argsort(rng.random((tokens, experts)),
                          axis=1)[:, :top_k].astype(np.int32)
    most = min(top_k, held)
    if not 0 <= held_pairs <= tokens * most \
            or top_k - held_pairs // tokens > experts - held:
        sys.exit("--rows-in-use %d: %d tokens with %d picks over %d held of "
                 "%d experts hold 0 .. %d pairs"
                 % (held_pairs, tokens, top_k, held, experts, tokens * most))
    mine = np.full((tokens,), held_pairs // tokens)
    mine[rng.permutation(tokens)[:held_pairs % tokens]] += 1
    here = np.argsort(rng.random((tokens, held)), axis=1)[:, :top_k]
    away = held + np.argsort(rng.random((tokens, experts - held)),
                             axis=1)[:, :top_k]
    slot = np.arange(top_k)[None, :]
    picks = np.where(slot < mine[:, None], here[:, :top_k],
                     np.take_along_axis(away, np.maximum(
                         slot - mine[:, None], 0), axis=1))
    shuffle = np.argsort(rng.random((tokens, top_k)), axis=1)
    return np.take_along_axis(picks, shuffle, axis=1).astype(np.int32)


def forms(tokens, d, ffn, top_k, experts, held, held_pairs):
    """(header facts, [(name, fn, args, bytes the form must move)])."""
    k, dtype = top_k, jnp.bfloat16
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    picks = jnp.asarray(draw_picks(tokens, k, experts, held, held_pairs))
    pos, row_pair, held_pair, sizes, _tg = jax.jit(
        lambda p: moe_ops.dispatch_plan(p, 0, held))(picks)
    rows, pairs = row_pair.shape[0], tokens * k
    tm = gmm.row_tile(pairs)
    in_use = int(moe_ops.rows_laid_out(np.asarray(sizes), tm))
    landed = int(np.asarray(sizes).sum())
    x = jax.random.normal(keys[1], (tokens, d)).astype(dtype)
    buf = jax.random.normal(keys[2], (rows, d)).astype(dtype)
    d_out = jax.random.normal(keys[3], (tokens, d)).astype(dtype)
    w = jax.random.uniform(keys[4], (tokens, k), jnp.float32)
    both = jax.random.normal(keys[5], (rows, 2 * ffn)).astype(dtype)
    d_act = both[:, :ffn]
    row_b = d * 2
    key = jnp.where(picks.reshape(-1) < held, picks.reshape(-1), held)
    chunk = moe_ops._chunk_rows(rows, tm)

    def chunked_rows_of_tokens(x, row_pair, in_use):
        """X -> buffer over the rows in use, chunk by chunk: measured, and
        not what the op does (PERF.md, PR 37: XLA holds X in VMEM for the
        one take, and in the step this form kept one more buffer alive)."""
        def block(start, _outs):
            pair = jax.lax.dynamic_slice_in_dim(row_pair, start, chunk)
            return (jnp.take(x, jnp.maximum(pair, 0) // k, axis=0,
                             mode="clip"),)
        return moe_ops._by_chunks(
            (moe_ops._anything((rows, x.shape[1]), x.dtype, x),), in_use,
            chunk, block)[0]

    def whole_combine_bwd(y, w, d_out):
        w_row = jnp.where(row_pair >= 0, jnp.take(
            w.reshape(-1), jnp.maximum(row_pair, 0), mode="clip"), 0.0)
        dy = (moe_ops._rows_of_tokens(d_out, row_pair, k).astype(jnp.float32)
              * w_row[:, None]).astype(y.dtype)
        dw = []
        for j in range(k):
            got = jnp.take(y, pos[:, j], axis=0, mode="clip")
            got = jnp.where((pos[:, j] < rows)[:, None], got, 0)
            dw.append(jnp.sum(got.astype(jnp.float32)
                              * d_out.astype(jnp.float32), axis=-1))
        return dy, jnp.stack(dw, axis=1)

    def combine_bwd(y, w, d_out, sizes):
        _out, vjp = jax.vjp(lambda y_, w_: moe_ops._combine(
            y_, w_, pos, row_pair, held_pair, sizes), y, w)
        return vjp(d_out)

    def laid_out(sizes):        # traced, as in the ops: no static trip count
        return moe_ops.rows_laid_out(sizes, tm)

    def gated_bwd(both, d_act, sizes):
        _act, vjp = jax.vjp(lambda b: moe_ops._gated(b, sizes, tm), both)
        return vjp(d_act)

    def whole_gate_bwd(both, d_act):
        _act, vjp = jax.vjp(moe_ops._gate, both)
        return vjp(d_act)

    sum_b = (landed + 2 * tokens) * row_b      # held rows in, a sum a token
    facts = dict(rows=rows, in_use=in_use, landed=landed, tm=tm, chunk=chunk,
                 bounded=bool(moe_ops.takes_bounded_form(in_use, rows)))
    return facts, [
        ("argsort, stable, %d int32 keys" % pairs,
         lambda a: jnp.argsort(a, stable=True), (key,), 2 * 4 * pairs),
        ("dispatch_plan (picks -> pos, row_pair, held_pair, sizes, tiles)",
         lambda p: moe_ops.dispatch_plan(p, 0, held), (picks,),
         4 * (3 * pairs + rows)),
        ("X -> buffer: one take of %d rows (moe_ops._rows_of_tokens)" % rows,
         lambda x, rp: moe_ops._rows_of_tokens(x, rp, k), (x, row_pair),
         2 * in_use * row_b),
        ("X -> buffer, in use: chunks of %d rows (measured, not taken)"
         % chunk, lambda x, rp, s: chunked_rows_of_tokens(x, rp, laid_out(s)),
         (x, row_pair, sizes), 2 * in_use * row_b),
        ("X -> buffer, every chunk of the buffer (the loop at its worst)",
         chunked_rows_of_tokens, (x, row_pair, jnp.int32(rows)),
         2 * rows * row_b),
        ("take of %d rows of the buffer (one pick)" % tokens,
         lambda b, p: jnp.take(b, p[:, 0], axis=0, mode="clip"), (buf, pos),
         2 * tokens * row_b),
        ("buffer -> tokens, whole: %d takes (moe_ops.sum_of_picks)" % k,
         moe_ops.sum_of_picks, (buf, pos), sum_b),
        ("buffer -> tokens, in use: the walk (moe_ops.sum_of_held_picks)",
         lambda b, p, hp, s: moe_ops.sum_of_held_picks(b, p, hp, jnp.sum(s)),
         (buf, pos, held_pair, sizes), sum_b),
        ("buffer -> tokens, weighted, whole: %d takes (moe_combine)" % k,
         moe_ops.sum_of_picks, (buf, pos, w), sum_b),
        ("buffer -> tokens, weighted, in use: the walk",
         lambda b, p, hp, s, w_: moe_ops.sum_of_held_picks(
             b, p, hp, jnp.sum(s), w_),
         (buf, pos, held_pair, sizes, w), sum_b),
        ("buffer -> tokens, weighted, as the op chooses (its cond)",
         lambda b, p, hp, s, w_: moe_ops._picked_sum(b, p, hp, s, w_),
         (buf, pos, held_pair, sizes, w), sum_b),
        ("moe_combine's backward, whole: dy one take, dw %d takes dotted"
         % k, whole_combine_bwd, (buf, w, d_out),
         (3 * in_use + tokens) * row_b),
        ("moe_combine's backward, in use: one pass, dy and the row dot",
         combine_bwd, (buf, w, d_out, sizes), (3 * in_use + tokens) * row_b),
        ("silu(gate) * up, whole (the buffer's %d rows)" % rows,
         moe_ops._gate, (both,), 3 * in_use * ffn * 2),
        ("silu(gate) * up, in use (moe_ops._gated)",
         lambda b, s: moe_ops._gated(b, s, tm), (both, sizes),
         3 * in_use * ffn * 2),
        ("its backward, whole", whole_gate_bwd, (both, d_act),
         5 * in_use * ffn * 2),
        ("its backward, in use", gated_bwd, (both, d_act, sizes),
         5 * in_use * ffn * 2),
    ]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", choices=sorted(CELLS), default="lfm2",
                    help="whose shapes and routing (default lfm2)")
    ap.add_argument("--rows-in-use", type=int, default=None, metavar="N",
                    help="put N pairs on the held experts (default: "
                         "uniform picks)")
    ap.add_argument("--tokens", type=int)
    ap.add_argument("--d", type=int)
    ap.add_argument("--ffn", type=int)
    ap.add_argument("--calls", type=int, default=30)
    ap.add_argument("--walk-through", action="store_true",
                    help="run off the TPU too: the times are no device times")
    args = ap.parse_args()
    tokens, d, ffn, top_k, experts, held = CELLS[args.cell]
    tokens, d, ffn = args.tokens or tokens, args.d or d, args.ffn or ffn
    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.walk_through:
        sys.exit("not a TPU: no device time to report (--walk-through "
                 "runs the forms all the same)")
    facts, lines = forms(tokens, d, ffn, top_k, experts, held,
                         args.rows_in_use)
    print("device platform=%s kind=%r; cell %s: X (%d, %d) bfloat16, top-%d "
          "of %d experts, %d held, expert width %d; buffer %d rows in tiles "
          "of %d, %d pairs landed, %d rows laid out (%.1f%%), bounded=%d, "
          "chunks of %d rows; %d calls a form"
          % (dev.platform, dev.device_kind, args.cell, tokens, d, top_k,
             experts, held, ffn, facts["rows"], facts["tm"], facts["landed"],
             facts["in_use"], 100.0 * facts["in_use"] / facts["rows"],
             facts["bounded"], facts["chunk"], args.calls))
    if dev.platform != "tpu":
        print("not a TPU: the times below are no device times")
    for name, fn, operands, nbytes in lines:
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
