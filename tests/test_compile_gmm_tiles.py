"""Compile-only, for a described v5e:2x2 topology with no chip attached: the
three grouped-matmul kernels at the tiles `plan` picks by the bytes they
move, at the two calls of each of the five expert cells (the expert width
of 1408 = 11 x 128 of `kimi-vl-a3b.t8192-b2` first: whole-dimension tiles,
no padding, no ragged tile; `nemotron-twotower-30b-a3b.t8192-b2`'s 1856 =
14.5 x 128, off the lane grid: its one tile is the whole width, on either
side of the matmul), in bfloat16, and at float32 operands, whose
blocks are twice the size. Mosaic has to take every block (alignment, VMEM)
and the call has to hold three custom calls. The topology is described
inside `tests/benchmark_suite/test_compile_fullsize.py`'s fixture, which
skips where it cannot be."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(HERE), os.path.join(HERE, "benchmark_suite")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_compile_fullsize import no_compile_cache, topo  # noqa: E402,F401

GMM = ("moe_gmm_fwd", "moe_gmm_dx", "moe_gmm_dw")

#: (pairs, K, N, dtype) -> the tiles of forward, dX and dW; 8 held experts
CALLS = {
    "kimi-vl-w13": (98304, 2048, 2816, "bfloat16"),
    "kimi-vl-w2": (98304, 1408, 2048, "bfloat16"),
    "lfm2-w13": (65536, 2048, 3584, "bfloat16"),
    "lfm2-w2": (65536, 1792, 2048, "bfloat16"),
    "smallthinker-w13": (196608, 2560, 1536, "bfloat16"),
    "smallthinker-w2": (196608, 768, 2560, "bfloat16"),
    "kimi-w13": (131072, 2304, 2048, "bfloat16"),
    "kimi-w2": (131072, 1024, 2304, "bfloat16"),
    "nemotron-w1": (98304, 2688, 1856, "bfloat16"),
    "nemotron-w2": (98304, 1856, 2688, "bfloat16"),
    "kimi-vl-w13-float32": (98304, 2048, 2816, "float32"),
    "kimi-vl-w2-float32": (98304, 1408, 2048, "float32"),
}

#: what `plan` has to say at an expert width of 1408 (the pinned pair of
#: `test_compile_kimivl.py` held the capped tiles: (256, 2048), (512, 1408),
#: (1024, 256) and (512, 1408), (128, 2048), (128, 512))
TILES_1408 = {
    "kimi-vl-w13": (512, (1408, 2048), (1024, 2816), (2048, 1408)),
    "kimi-vl-w2": (512, (2048, 1408), (1408, 2048), (1408, 2048)),
}


@pytest.mark.parametrize("call", list(CALLS))
def test_the_kernels_compile_at_the_tiles_plan_picks(
        topo, no_compile_cache, call):            # noqa: F811
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    one = SingleDeviceSharding(topo.devices[0])
    pairs, k, n, dtype = CALLS[call]
    dtype = jnp.dtype(dtype)
    tm = gm.row_tile(pairs)
    rows = gm.buffer_rows(pairs, 8, tm)
    tiles = gm.plan(rows, k, n, tm, dtype.itemsize)
    assert tiles is not None and tiles.tm == tm == 512
    if call in TILES_1408:
        assert rows == 102400 and tiles == TILES_1408[call]
    for kernel in gm.KERNELS:
        assert gm.vmem_bytes(kernel, tm, getattr(tiles, kernel),
                             dtype.itemsize) <= gm._VMEM_BUDGET

    def struct(shape, dtype=dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def run(x, w, sizes):
        out, vjp = jax.vjp(lambda x_, w_: gm.grouped_matmul(
            x_, w_, sizes, tm, interpret=False), x, w)
        return out, vjp(out)

    text = jax.jit(run).lower(
        struct((rows, k)), struct((8, k, n)),
        struct((8,), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    for name in GMM:
        assert name in text, name
