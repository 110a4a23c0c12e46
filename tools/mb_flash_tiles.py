#!/usr/bin/env python
"""Microbenchmark of the flash-attention kernels' tiles, alone on the chip.

One JSON line a shape: ms a call of each kernel of
`paddle_tpu/ops/pallas/flash_attention.py` (forward, dK/dV, dQ, and the
fused backward) per (block_q, block_k), in bfloat16, at the attention
shapes of the benchmark's GPT cells, of BERT's phase 2 (key-padding mask),
of the two Kimi cells' latent attention (D 192, Dv 128), and of the five
grouped calls, where the fused backward sums dK/dV over the group: SDAR's
(32 query heads on 4 key heads under the block-diffusion rule),
SmallThinker's global layer (28 on 4), LFM2's (32 on 8), Nemotron's (32 on
2) and Phi's full layer (20 on 10, Dv 128). "rule" is the tile
`flash_attention.pick_blocks` gives each kernel at that shape, "best" the
fastest tile timed, and "backward" what `backward_rule` gives the call: the
code applies both by itself, so a sweep that disagrees with the rule is a
reason to change `pick_blocks`, not to set a knob.

  python tools/mb_flash_tiles.py [--only 32:4 --only 28:4]
  JAX_PLATFORMS=cpu python tools/mb_flash_tiles.py --walk-through
      # tiny shapes in interpret mode, no device time: exits 1 without the flag
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from paddle_tpu.ops.pallas import flash_attention as fa     # noqa: E402

#: (b, (hq, hkv), t, d, dv or None, causal, key_mask, block_diffusion or
#: None, the (block_q, block_k) swept beside 128x128, those over t left out)
SQUARE = tuple((bq, bk) for bq in (256, 512, 1024) for bk in (256, 512, 1024))
GROUP_TILES = ((1024, 1024), (512, 512), (1024, 512))
CHIP_SHAPES = [(4, (12, 12), 4096, 64, None, True, False, None, SQUARE),
               (16, (12, 12), 1024, 64, None, True, False, None, SQUARE),
               (32, (12, 12), 512, 64, None, False, True, None, SQUARE),
               (2, (16, 16), 8192, 192, 128, True, False, None, SQUARE),
               (1, (32, 4), 16384, 128, None, False, False, (4, 8192),
                GROUP_TILES),
               (2, (28, 4), 16384, 128, None, True, False, None,
                GROUP_TILES),
               (2, (32, 8), 8192, 64, None, True, False, None, GROUP_TILES),
               (2, (32, 2), 8192, 128, None, True, False, None, GROUP_TILES),
               (2, (20, 10), 8192, 64, 128, True, False, None, GROUP_TILES)]
WALK_THROUGH_SHAPES = [
    (1, (2, 2), 256, 32, None, True, False, None, ((256, 256),)),
    (1, (2, 2), 256, 48, 32, True, False, None, ((256, 256),)),
    (1, (4, 2), 256, 32, None, False, False, (4, 128), ())]


def flash_kernel_ms(b, h, t, d, blocks, causal=True, key_mask=False,
                    dtype="bfloat16", interpret=False, budget_s=0.25,
                    dv=None, hkv=None, block_diffusion=None):
    """Milliseconds a call of each of the four flash kernels (forward,
    dK/dV, dQ, and "bwd": the fused backward that stands for the last two
    where `flash_attention.backward_rule` says so) takes at `blocks` =
    (block_q, block_k), each kernel timed on its own: warm (the compile),
    then enough back-to-back calls to fill `budget_s` behind one
    `block_until_ready`. `dv` is the value width where it is not `d`, `hkv`
    the key/value heads where they are fewer than `h`, `block_diffusion`
    the rule (L, T) over t = 2T rows. A kernel the compiler refuses reads
    "failed: ..."."""
    rng = np.random.RandomState(0)
    dv = dv or d
    q, k, v, g = (jnp.asarray(rng.randn(b, heads, t, width), dtype)
                  for heads, width in ((h, d), (hkv or h, d), (hkv or h, dv),
                                       (h, dv)))
    mask = None
    if key_mask:
        pad = np.zeros((b, 1, 1, t), np.float32)
        pad[..., 3 * t // 4:] = -1e9
        mask = jnp.asarray(pad, dtype)
    scale = 1.0 / np.sqrt(d)
    bq, bk = blocks
    fwd = jax.jit(lambda q, k, v: fa._pallas_forward(
        q, k, v, mask, scale, causal, bq, bk, interpret,
        block_diffusion=block_diffusion))
    mode = fa._mask_mode(mask)
    calls = {"fwd": (fwd, (q, k, v))}
    try:
        out, stats = fwd(q, k, v)
        ops = jax.jit(fa._bwd_inputs)(q, k, v, mask, out, stats, g)
        for name, kernel in (("bwd_dkv", fa._pallas_bwd_dkv),
                             ("bwd_dq", fa._pallas_bwd_dq),
                             ("bwd", fa._pallas_bwd)):
            calls[name] = (jax.jit(lambda *o, kernel=kernel: kernel(
                o, h, mode, scale, causal, bq, bk, interpret,
                block_diffusion=block_diffusion)), ops)
    except Exception as e:  # the forward itself was refused
        return {"fwd": "failed: %s" % str(e)[-200:]}
    ms = {}
    for name, (fn, args) in calls.items():
        try:
            jax.block_until_ready(fn(*args))
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            once = time.perf_counter() - t0
            n = max(2, min(50, int(budget_s / max(once, 1e-4))))
            t0 = time.perf_counter()
            for _ in range(n):
                res = fn(*args)
            jax.block_until_ready(res)
            ms[name] = round((time.perf_counter() - t0) / n * 1e3, 3)
        except Exception as e:
            ms[name] = "failed: %s" % str(e)[-200:]
    return ms


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--walk-through", action="store_true",
                    help="run off the TPU too (tiny shapes, interpret "
                         "mode): the times are no device times")
    ap.add_argument("--only", action="append", metavar="TEXT",
                    help="time only the shapes whose label holds TEXT "
                         "(say 32:4); may be given more than once")
    args = ap.parse_args()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.walk_through:
        sys.exit("not a TPU: no device time to report (--walk-through "
                 "runs the kernels all the same, in interpret mode)")
    kernels = fa.KERNELS + fa.FUSED_KERNELS[1:]
    for b, (hq, hkv), t, d, dv, causal, key_mask, bd, swept in (
            CHIP_SHAPES if on_tpu else WALK_THROUGH_SHAPES):
        label = "%dx%d%sx%dx%d%s%s" % (
            b, hq, ":%d" % hkv if hkv != hq else "", t, d,
            "|%d" % dv if dv else "",
            "-bd%d" % bd[0] if bd else "" if causal else "-kmask")
        if args.only and not any(text in label for text in args.only):
            continue
        tiles = [(128, 128)] + [tile for tile in swept if max(tile) <= t]
        table = {"%dx%d" % tile: flash_kernel_ms(
            b, hq, t, d, tile, causal, key_mask, interpret=not on_tpu, dv=dv,
            hkv=hkv, block_diffusion=bd)
            for tile in tiles}
        best = {}
        for kern in kernels:
            timed = {tile: row[kern] for tile, row in table.items()
                     if isinstance(row.get(kern), float)}
            best[kern] = min(timed, key=timed.get) if timed else None
        shape, kv_shape = (b, hq, t, d), (b, hkv, t, d)
        print(json.dumps({
            "shape": label,
            "platform": dev.platform, "device_kind": dev.device_kind,
            "device_times": on_tpu, "ms": table, "best": best,
            "rule": {kern: "%dx%d" % fa.pick_blocks(
                t, t, d, "bfloat16", kern, causal, dv=dv, block_diffusion=bd,
                group=hq // hkv) for kern in kernels},
            "backward": fa.backward_rule(shape, kv_shape,
                                         (b, hkv, t, dv or d), "bfloat16",
                                         causal, None, bd)}),
            flush=True)


if __name__ == "__main__":
    main()
