#!/usr/bin/env python
"""Microbenchmark of the flash kernels under the block-diffusion rule, alone
on the chip (beside `tools/mb_flash_tiles.py`, whose `flash_kernel_ms` times
equal heads only).

One JSON line a form of the SDAR cell's attention call, (1, 32 | 4, 16384,
128) in bfloat16 over an 8,192-token document's two copies in blocks of 4:
the rule in the kernels (`block_diffusion=(4, 8192)`: a quarter of the
square and the diagonals run), the same call under `causal=True` (half of
the square: what 2T rows cost without the rule) and under the dense additive
(1, 1, 2T, 2T) mask that says the same as the rule (every tile runs and
reads its 2 MiB of the 512 MiB mask). ms a call, forward alone and forward
with the pullback, and for the rule each tile side swept. A lead for
`pick_blocks`, never a claim.

  python tools/mb_flash_bd.py
  JAX_PLATFORMS=cpu python tools/mb_flash_bd.py --walk-through
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


def call_ms(fn, args, budget_s=0.5):
    """ms a call of jitted `fn`: warm (the compile), then enough
    back-to-back calls to fill `budget_s` behind one
    `block_until_ready`."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    once = time.perf_counter() - t0
    n = max(2, min(50, int(budget_s / max(once, 1e-4))))
    t0 = time.perf_counter()
    for _ in range(n):
        res = fn(*args)
    jax.block_until_ready(res)
    return round((time.perf_counter() - t0) / n * 1e3, 3)


def forms(hq, hkv, t, d, length, sides, interpret):
    """{form: {"fwd": ms, "fwd_bwd": ms}} at q (1, hq, 2T, d) on hkv key
    heads."""
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(1, h, 2 * t, d), jnp.bfloat16)
               for h in (hq, hkv, hkv))
    scale, bd = d ** -0.5, (length, t)
    dense = jnp.where(fa.visible_mask(2 * t, 2 * t, block_diffusion=bd),
                      0.0, -1e9).astype(jnp.bfloat16)[None, None]
    calls = {"causal": (dict(causal=True), ()),
             "dense_mask": (dict(), (dense,))}
    for side in sides:
        calls["block_diffusion %dx%d" % (side, side)] = (
            dict(block_diffusion=bd, block_q=side, block_k=side), ())
    calls["block_diffusion rule"] = (dict(block_diffusion=bd), ())
    out = {}
    for name, (kwargs, mask) in calls.items():
        def fwd(q, k, v, *m, kwargs=kwargs):
            return fa.flash_attention(q, k, v, *m, scale=scale,
                                      interpret=interpret, **kwargs)

        def both(q, k, v, *m, fwd=fwd):
            o, vjp = jax.vjp(lambda q, k, v: fwd(q, k, v, *m), q, k, v)
            return vjp(o)

        try:
            out[name] = {"fwd": call_ms(jax.jit(fwd), (q, k, v) + mask),
                         "fwd_bwd": call_ms(jax.jit(both), (q, k, v) + mask)}
        except Exception as e:      # a tile the compiler refuses
            out[name] = "failed: %s" % str(e)[-200:]
    return out


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
    hq, hkv, t, d, length, sides = (32, 4, 8192, 128, 4, (512, 1024)) \
        if on_tpu else (8, 1, 64, 16, 4, (32,))
    shape = ((1, hq, 2 * t, d), (1, hkv, 2 * t, d), (1, hkv, 2 * t, d))
    path = fa.attention_path(*shape, jnp.bfloat16, False, None, not on_tpu,
                             block_diffusion=(length, t))
    plan = fa.plan(*shape, False, None, path.blocks, path.backward,
                   (length, t))
    print(json.dumps({
        "shape": "1x%d|%dx%dx%d blocks of %d" % (hq, hkv, 2 * t, d, length),
        "platform": dev.platform, "device_kind": dev.device_kind,
        "device_times": on_tpu,
        "rule": {kern: "%dx%d" % tile for kern, tile in zip(
            fa._kernels_of(path.blocks), path.blocks)},
        "backward": path.backward,
        "tiles": {kern: [plan[kern]["tiles_run"], plan[kern]["tiles_grid"]]
                  for kern in fa._kernels_of(path.blocks)},
        "ms": forms(hq, hkv, t, d, length, sides, not on_tpu)}), flush=True)


if __name__ == "__main__":
    main()
