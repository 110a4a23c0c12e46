#!/usr/bin/env python
"""Microbenchmark of the three grouped-matmul kernels, alone on the chip.

One line a kernel (`moe_gmm_fwd`, `moe_gmm_dx`, `moe_gmm_dw` of
`paddle_tpu/ops/pallas/grouped_matmul.py`) for each of a cell's two calls,
W13 (d, 2 x width) and W2 (width, d), in bfloat16 over the cell's row
buffer: `--cell kimi-vl` (16,384 tokens x top-6 on 8 held experts, d 2,048,
width 1,408, 102,400 rows), `lfm2` (top-4, 2,048 / 1,792, 69,632 rows),
`smallthinker` (32,768 tokens x top-6, 2,560 / 768, 200,704 rows), `kimi`
(top-8, 2,304 / 1,024, 135,168 rows) or `nemotron` (top-6, 2,688 / 1,856,
102,400 rows; its experts are not gated, so its first call is W1 (d, width):
the width off the 128-lane grid, whose one tile is the whole width).
`--rows-in-use N` puts N pairs on the held experts, split unevenly from
`--seed` (default: what the cell's traced steps counted). `--tiles` names
whose tiles, and may repeat: `plan` (the module's `plan`), `old` (the capped
divisors `plan` took until PR 41, which live here alone) or explicit ones,
`fwd=1408x2048,dw=1408x512` (a kernel not named is not run, nor a pair at the
call it does not divide; a pair is the kernel's pair in `Tiles`' order).

`ms` is wall time a call over `--calls` calls dispatched back to back
behind one `block_until_ready`; `roofline` is the least time of the call at
the rows that belong to a group (the larger of 2 rows K N over 197 TFLOP/s
and each row and matrix moved once over 819 GB/s, as
`benchmark/flops_moe.py` counts) over that time; `model` is what `hbm_bytes`
says the tiles move over the rows laid out, in ms at 819 GB/s, `reread` its
ratio to the least bytes, `vmem` what `vmem_bytes` says the blocks take.

  python tools/mb_gmm_tiles.py --cell kimi-vl --tiles old --tiles plan
  JAX_PLATFORMS=cpu python tools/mb_gmm_tiles.py --walk-through --tokens 256 \\
      --d 256 --ffn 128 --calls 1     # no device time: exits 1 without the flag
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

from paddle_tpu.ops.pallas import grouped_matmul as gmm     # noqa: E402

PEAK_FLOPS, PEAK_BYTES = 197e12, 819e9      # one v5e chip, bfloat16

#: a cell's calls: tokens, d, expert width, top_k, held experts, and the
#: pairs its traced steps put on them (ledger, PR 40: rows in use less
#: half a tile of padding a group)
CELLS = {"kimi-vl": (16384, 2048, 1408, 6, 8, 98304),
         "lfm2": (16384, 2048, 1792, 4, 8, 18944),
         "smallthinker": (32768, 2560, 768, 6, 8, 196608),
         "kimi": (16384, 2304, 1024, 8, 8, 5568),
         "nemotron": (16384, 2688, 1856, 6, 8, 98304)}
#: cells whose experts are not gated: the first matrix is (d, width)
PLAIN = ("nemotron",)


def old_plan(k, n, tm):
    """The tiles until PR 41: the largest 128-multiple divisor under a
    fixed cap."""
    def divisor(dim, cap):
        return max(t for t in range(128, min(dim, cap) + 1, 128)
                   if dim % t == 0)
    return gmm.Tiles(tm, (divisor(n, 512), divisor(k, 2048)),
                     (divisor(k, 512), divisor(n, 2048)),
                     (divisor(k, 1024), divisor(n, 512)))


def tiles_of(spec, rows, k, n, tm):
    """{kernel: (a, b)} of one `--tiles` argument at one call."""
    if spec in ("plan", "old"):
        what = gmm.plan(rows, k, n, tm) if spec == "plan" \
            else old_plan(k, n, tm)
        return {kernel: getattr(what, kernel) for kernel in gmm.KERNELS}
    out = {}
    for part in spec.split(","):
        kernel, _, pair = part.partition("=")
        a, _, b = pair.partition("x")
        if kernel not in gmm.KERNELS or not (a.isdigit() and b.isdigit()):
            sys.exit("--tiles %r: plan, old, or fwd=AxB[,dx=AxB][,dw=AxB]"
                     % spec)
        out[kernel] = (int(a), int(b))
    return out


def split(pairs, groups, seed):
    """`pairs` rows over `groups` groups, unevenly: a router's shares."""
    share = np.random.default_rng(seed).dirichlet(np.full(groups, 8.0))
    sizes = np.floor(share * pairs).astype(np.int64)
    sizes[0] += pairs - sizes.sum()
    return sizes.astype(np.int32)


def kernel_call(kernel, tiles, lay, tm, groups, interpret):
    """The jitted kernel alone: (x or dy, w or dy) -> its result."""
    tg, te, used = lay["tile_group"], lay["tile_end"], lay["tiles"]
    a, b = tiles
    if kernel == "dw":
        return jax.jit(lambda x, dy: gmm._gmm_dw(
            x, dy, tg, te, used, groups, tm, a, b, interpret))
    return jax.jit(lambda x, w: gmm._gmm(
        x, w, tg, used, tm, a, b, kernel == "dx", interpret))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", choices=sorted(CELLS), default="kimi-vl")
    ap.add_argument("--rows-in-use", type=int, default=None, metavar="N",
                    help="pairs on the held experts (default: the cell's)")
    ap.add_argument("--tiles", action="append", metavar="SPEC",
                    help="plan (default), old, or fwd=AxB,dx=AxB,dw=AxB; "
                         "may repeat")
    ap.add_argument("--tokens", type=int)
    ap.add_argument("--d", type=int)
    ap.add_argument("--ffn", type=int)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--walk-through", action="store_true",
                    help="run off the TPU too (interpret mode): the times "
                         "are no device times")
    args = ap.parse_args()
    tokens, d, ffn, top_k, groups, in_use = CELLS[args.cell]
    tokens, d, ffn = args.tokens or tokens, args.d or d, args.ffn or ffn
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.walk_through:
        sys.exit("not a TPU: no device time to report (--walk-through "
                 "runs the kernels all the same, in interpret mode)")
    pairs = tokens * top_k
    tm = gmm.row_tile(pairs)
    rows = gmm.buffer_rows(pairs, groups, tm)
    in_use = min(pairs, in_use if args.rows_in_use is None
                 else args.rows_in_use)
    sizes = split(in_use, groups, args.seed)
    lay = jax.jit(lambda s: gmm.layout(s, rows, tm))(jnp.asarray(sizes))
    laid_out = int(lay["tiles"]) * tm
    print("device platform=%s kind=%r; cell %s: %d pairs over %d groups in "
          "a buffer of %d rows, tiles of %d, %d rows in groups %r, %d rows "
          "laid out; bfloat16; %d calls a line"
          % (dev.platform, dev.device_kind, args.cell, pairs, groups, rows,
             tm, in_use, sizes.tolist(), laid_out, args.calls))
    if not on_tpu:
        print("not a TPU: the times below are no device times")
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    first_n = ffn if args.cell in PLAIN else 2 * ffn
    for k, n in ((d, first_n), (ffn, d)):
        x = jax.random.normal(keys[0], (rows, k)).astype(jnp.bfloat16)
        dy = jax.random.normal(keys[1], (rows, n)).astype(jnp.bfloat16)
        w = (0.02 * jax.random.normal(keys[2], (groups, k, n))).astype(
            jnp.bfloat16)
        matmul_s = 2.0 * in_use * k * n / PEAK_FLOPS
        bytes_s = gmm.least_bytes(in_use, k, n, groups, 2) / PEAK_BYTES
        least_s = max(matmul_s, bytes_s)
        print("call (K %d, N %d): matmul %.3f ms at peak, least bytes "
              "%.3f ms" % (k, n, 1e3 * matmul_s, 1e3 * bytes_s))
        for spec in args.tiles or ["plan"]:
            for kernel, tiles in tiles_of(spec, rows, k, n, tm).items():
                a, b = gmm.tiled_widths(kernel, k, n)
                if a % tiles[0] or b % tiles[1]:
                    continue        # explicit tiles meant for the other call
                operands = {"fwd": (x, w), "dx": (dy, w), "dw": (x, dy)}[
                    kernel]
                fn = kernel_call(kernel, tiles, lay, tm, groups, not on_tpu)
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*operands))
                first = time.perf_counter() - t0
                jax.block_until_ready(fn(*operands))
                t0 = time.perf_counter()
                for _ in range(args.calls):     # in order on one chip
                    out = fn(*operands)
                jax.block_until_ready(out)
                ms = (time.perf_counter() - t0) * 1e3 / args.calls
                moved = gmm.hbm_bytes(kernel, laid_out, k, n, tm, tiles,
                                      groups, 2)
                print("  %-5s moe_gmm_%-3s tiles %-10s grid %-12s %8.3f ms "
                      "roofline %5.1f%%  model %6.3f ms reread %5.2f vmem "
                      "%5.1f MiB  first call %.1f s"
                      % (spec if spec in ("plan", "old") else "given",
                         kernel, "%dx%d" % tiles, "%dx%dx%d" % gmm.grid(
                             kernel, laid_out, k, n, tm, tiles), ms,
                         100.0 * least_s * 1e3 / ms,
                         1e3 * moved / PEAK_BYTES,
                         moved / gmm.least_bytes(laid_out, k, n, groups, 2),
                         gmm.vmem_bytes(kernel, tm, tiles, 2) / 2.0 ** 20,
                         first))


if __name__ == "__main__":
    main()
