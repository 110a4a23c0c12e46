#!/usr/bin/env python
"""Microbenchmark of the flash-attention kernels' tiles, alone on the chip.

One JSON line a shape: ms a call of each kernel of
`paddle_tpu/ops/pallas/flash_attention.py` (forward, dK/dV, dQ, and the
fused backward) per (block_q, block_k), in bfloat16, at the attention
shapes of the benchmark's GPT cells, of BERT's phase 2 (key-padding mask)
and of the two Kimi cells' latent attention (D 192, Dv 128). "rule" is the
tile `flash_attention.pick_blocks` gives each kernel at that shape, "best"
the fastest tile timed, and "backward" what `backward_rule` gives the
call: the code applies both by itself, so a sweep that disagrees with the
rule is a reason to change `pick_blocks`, not to set a knob.

  python tools/mb_flash_tiles.py
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

#: (b, h, t, d, dv or None, causal, key_mask) and the tile sides swept
CHIP_SHAPES = ([(4, 12, 4096, 64, None, True, False),
                (16, 12, 1024, 64, None, True, False),
                (32, 12, 512, 64, None, False, True),
                (2, 16, 8192, 192, 128, True, False)], (256, 512, 1024))
WALK_THROUGH_SHAPES = ([(1, 2, 256, 32, None, True, False),
                        (1, 2, 256, 48, 32, True, False)], (256,))


def flash_kernel_ms(b, h, t, d, blocks, causal=True, key_mask=False,
                    dtype="bfloat16", interpret=False, budget_s=0.25,
                    dv=None):
    """Milliseconds a call of each of the four flash kernels (forward,
    dK/dV, dQ, and "bwd": the fused backward that stands for the last two
    where `flash_attention.backward_rule` says so) takes at `blocks` =
    (block_q, block_k), each kernel timed on its own: warm (the compile),
    then enough back-to-back calls to fill `budget_s` behind one
    `block_until_ready`. `dv` is the value width where it is not `d`. A
    kernel the compiler refuses reads "failed: ..."."""
    rng = np.random.RandomState(0)
    dv = dv or d
    q, k, v, g = (jnp.asarray(rng.randn(b, h, t, width), dtype)
                  for width in (d, d, dv, dv))
    mask = None
    if key_mask:
        pad = np.zeros((b, 1, 1, t), np.float32)
        pad[..., 3 * t // 4:] = -1e9
        mask = jnp.asarray(pad, dtype)
    scale = 1.0 / np.sqrt(d)
    bq, bk = blocks
    fwd = jax.jit(lambda q, k, v: fa._pallas_forward(
        q, k, v, mask, scale, causal, bq, bk, interpret))
    mode = fa._mask_mode(mask)
    calls = {"fwd": (fwd, (q, k, v))}
    try:
        out, stats = fwd(q, k, v)
        ops = jax.jit(fa._bwd_inputs)(q, k, v, mask, out, stats, g)
        for name, kernel in (("bwd_dkv", fa._pallas_bwd_dkv),
                             ("bwd_dq", fa._pallas_bwd_dq),
                             ("bwd", fa._pallas_bwd)):
            calls[name] = (jax.jit(lambda *o, kernel=kernel: kernel(
                o, h, mode, scale, causal, bq, bk, interpret)), ops)
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
    args = ap.parse_args()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.walk_through:
        sys.exit("not a TPU: no device time to report (--walk-through "
                 "runs the kernels all the same, in interpret mode)")
    shapes, sizes = CHIP_SHAPES if on_tpu else WALK_THROUGH_SHAPES
    kernels = fa.KERNELS + fa.FUSED_KERNELS[1:]
    for b, h, t, d, dv, causal, key_mask in shapes:
        tiles = [(128, 128)] + [(bq, bk) for bq in sizes for bk in sizes
                                if bq <= t and bk <= t]
        table = {"%dx%d" % tile: flash_kernel_ms(
            b, h, t, d, tile, causal, key_mask, interpret=not on_tpu, dv=dv)
            for tile in dict.fromkeys(tiles)}
        best = {}
        for kern in kernels:
            timed = {tile: row[kern] for tile, row in table.items()
                     if isinstance(row.get(kern), float)}
            best[kern] = min(timed, key=timed.get) if timed else None
        shape = (b, h, t, d)
        print(json.dumps({
            "shape": "%dx%dx%dx%d%s%s" % (b, h, t, d,
                                          "|%d" % dv if dv else "",
                                          "" if causal else "-kmask"),
            "platform": dev.platform, "device_kind": dev.device_kind,
            "device_times": on_tpu, "ms": table, "best": best,
            "rule": {kern: "%dx%d" % fa.pick_blocks(
                t, t, d, "bfloat16", kern, causal, dv=dv)
                for kern in kernels},
            "backward": fa.backward_rule(shape, shape, (b, h, t, dv or d),
                                         "bfloat16", causal, None)}),
            flush=True)


if __name__ == "__main__":
    main()
