#!/usr/bin/env python
"""Microbenchmark of the Mamba-2 chunked recurrence, alone on the chip.

One line a form: the XLA form (`ops/ssm_ops.py:_ssd`) and the `ssd_fwd` /
`ssd_bwd` Pallas kernels (`ops/pallas/ssd.py`), each jitted by itself at
the `nemotron-twotower-30b-a3b.t8192-b2` cell's call by default (B 2, T
8192, H 64, P 64, 8 groups, state 128, chunks of 128; bfloat16 with dt
float32), forward alone and forward + backward, one line a `--heads-a-step`
tried. `ms` is wall time a call over `--calls` calls dispatched back to
back behind one `block_until_ready`: the median and the range of `--runs`
runs. The operands enter as the layer holds them, (B, T, H * P) and (B, T,
G * N): the op's own views of them are the same bytes. Under each form, the
largest difference of y and of each gradient from the XLA form in float32
at HIGHEST on the same values, over that leaf's largest number: a kernel
that rounds where the XLA form does lies as far from it as the XLA form.
Alone, XLA fuses and lays out as it likes: read a form's time inside the
step from the step's trace, not from here.

  python tools/mb_ssd.py --heads-a-step 2,4,8        # on the chip tool
  JAX_PLATFORMS=cpu python tools/mb_ssd.py --walk-through --seq 256 \
      --batch 1 --heads 4 --groups 2 --calls 1 --runs 1
                        # no device time: exits 1 without the flag
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402

from mb_kda_intra import time_calls                 # noqa: E402
from paddle_tpu.ops import ssm_ops                  # noqa: E402
from paddle_tpu.ops.pallas import ssd               # noqa: E402

_F32 = jnp.float32
LEAVES = ("y", "dx", "d dt", "d a", "dB", "dC")


def op_inputs(args, dtype):
    """(x (B, T, H P), dt (B, T, H) after softplus, a (H,), b, c (B, T, G
    N)) and a cotangent of y."""
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    tokens = (args.batch, args.seq)
    x = jax.random.normal(keys[0], tokens + (args.heads * args.head_dim,))
    dt = jax.nn.softplus(jax.random.normal(keys[1], tokens + (args.heads,))
                         - 3.0)
    a = -jnp.exp(jax.random.uniform(keys[2], (args.heads,), minval=0.0,
                                    maxval=2.7))
    b, c = (jax.random.normal(key, tokens + (args.groups * args.state,))
            * args.state ** -0.5 for key in keys[3:5])
    cot = jax.random.normal(keys[5], x.shape)
    return (x.astype(dtype), dt, a, b.astype(dtype), c.astype(dtype)), cot


def of_chunks(fn, args):
    """`fn` on `_ssd`'s operands, from and to the layer's shapes."""
    chunks = args.seq // ssd.CHUNK

    def run(x, dt, a, b, c):
        cut = lambda m, *last: m.reshape(
            (args.batch, chunks, ssd.CHUNK) + last)
        y = fn(cut(x, args.heads, args.head_dim), cut(dt, args.heads), a,
               cut(b, args.groups, args.state),
               cut(c, args.groups, args.state))
        return y.reshape(x.shape)
    return run


def gaps(got, want):
    """Each leaf's largest |got - want| over its largest |want|."""
    return [float(jnp.max(jnp.abs(g.astype(_F32) - w))
                  / jnp.max(jnp.abs(w)))
            for g, w in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want))]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seq", type=int, default=8192)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--heads", type=int, default=64)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--groups", type=int, default=8)
    ap.add_argument("--state", type=int, default=128)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--runs", type=int, default=5)
    ap.add_argument("--heads-a-step", default=None,
                    type=lambda s: [int(n) for n in s.split(",")],
                    help="heads a grid step holds, each tried in turn "
                         "(default: what the op picks)")
    ap.add_argument("--walk-through", action="store_true",
                    help="run off the TPU too: the times are no device times")
    args = ap.parse_args()
    dev = jax.devices()[0]
    print("device platform=%s kind=%r; B %d, T %d, H %d, P %d, %d groups, "
          "state %d, chunks of %d, bfloat16, %d calls a run, %d runs"
          % (dev.platform, dev.device_kind, args.batch, args.seq,
             args.heads, args.head_dim, args.groups, args.state, ssd.CHUNK,
             args.calls, args.runs))
    interpret = dev.platform != "tpu"
    if interpret:
        if not args.walk_through:
            sys.exit("not a TPU: no device time to report (--walk-through "
                     "runs the forms all the same)")
        print("not a TPU: the times below are no device times")
    picked = ssd.pick_heads
    if args.heads_a_step is None:
        args.heads_a_step = [picked(args.heads // args.groups,
                                    args.head_dim)]
    operands, cot = op_inputs(args, jnp.bfloat16)

    def both(fn):       # the cotangent an operand: no constant of its size
        def run(cot, *xs):
            out, pull = jax.vjp(fn, *xs)
            return out, pull(cot)
        return run

    def kernels(heads_a_step):
        def run(*xs):       # a function a tiling: jit keys its cache on it
            ssd.pick_heads = lambda per_group, head_dim: heads_a_step
            return ssd.ssd(*xs, interpret)
        return run

    exact = [m.astype(_F32) for m in operands]
    with jax.default_matmul_precision("highest"):
        want = jax.jit(both(of_chunks(ssm_ops._ssd, args)))(cot, *exact)
    print("%-44s %9s %9s %9s" % ("form", "median ms", "least", "most"))
    table = [("xla", ssm_ops._ssd)] + [
        ("ssd_fwd, ssd_bwd: %d heads a step" % n, kernels(n))
        for n in args.heads_a_step]
    for name, fn in table:
        jax.clear_caches()  # the kernels' own jitted calls hold the last tiling
        fn = of_chunks(fn, args)
        for what, run, xs in (
                ("forward", jax.jit(fn), operands),
                ("forward + backward", jax.jit(both(fn)), (cot,) + operands)):
            ms = time_calls(run, xs, args.calls, args.runs)
            print("%-44s %9.3f %9.3f %9.3f"
                  % (((name + ", " + what)[:44],) + ms), flush=True)
        print("%-44s %s" % ((name + ", from float32")[:44], "  ".join(
            "%s %.1e" % pair for pair in zip(
                LEAVES, gaps(run(*xs), want)))), flush=True)
    ssd.pick_heads = picked


if __name__ == "__main__":
    main()
