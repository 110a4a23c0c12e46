"""Compile-only, for a described v5e:2x2 topology with no chip attached:
the `kimi-vl-a3b.t8192-b2` step program at full size (it has to fit
15.75 GiB and fill a quarter of it, hold no (T, T) score matrix, and carry
the rotary op's scope on every layer), the check's reference at the cell's
size (`reference.follow` holds 24 B a parameter beside one block's
`value_and_grad`: the peak has to stay under the chip's 15.75 GiB with all
16 heads, or the configuration must fall back to 8), and the grouped-matmul
kernels at an expert width of 1408 = 11 x 128. The flash kernels at D 192 /
Dv 128 are `test_compile_kimilinear.py`'s. As `test_compile_lfm2moe.py`: the
topology is described inside `test_compile_fullsize.py`'s fixture, so only
the worker that is given this file loads the TPU's library (where another
worker already holds it, the fixture skips)."""
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (REPO, os.path.dirname(os.path.abspath(__file__))):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_compile_fullsize import (device_bytes, lower_step,  # noqa: E402
                                   no_compile_cache, topo)    # noqa: F401

CHIP_BYTES = 16909336064        # bytes_limit a v5e reports: 15.75 GiB
CELL = "kimi-vl-a3b.t8192-b2"
SHAPE = re.compile(r"(?:f32|bf16|s32|pred|u32|s8|u8)\[([0-9,]+)\]")
FLASH = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")
GMM = ("moe_gmm_fwd", "moe_gmm_dx", "moe_gmm_dw")


def _shapes(text):
    return {tuple(int(d) for d in m.group(1).split(",") if d)
            for m in SHAPE.finditer(text)}


def test_step_compiles_for_v5e_fits_and_holds_no_scores(
        topo, no_compile_cache, monkeypatch):     # noqa: F811
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "0")
    compiled = lower_step(CELL, topo.devices[:1])
    need = device_bytes(compiled)
    print("%s: %.2f GiB on the chip by memory_analysis()"
          % (CELL, need / 2.0 ** 30))
    # a quarter of the chip is 3.94 GiB; the issue asks for 4
    assert 4 * 2 ** 30 < need < CHIP_BYTES
    text = compiled.as_text()
    for name in FLASH + GMM:
        assert name in text, name
    # 5 layers x (forward, replayed forward, dK/dV, dQ) flash calls and
    # 4 x 2 matrices x (forward, replayed forward, dX, dW) grouped matmuls
    assert text.count("tpu_custom_call") >= 20 + 32
    # every layer turns its rotary part under the op's own scope: forward,
    # replayed and pulled back (the reader of `kvl_rope_ms` goes by it)
    for scope in ("forward/remat_block/jvp(forward/partial_rope)",
                  "rematted_computation/forward/partial_rope",
                  "jvp()/checkpoint/forward/partial_rope"):
        assert scope in text, scope
    assert not [s for s in _shapes(text) if s.count(8192) >= 2]


def test_the_checks_reference_fits_beside_its_copies_with_all_16_heads(
        topo, no_compile_cache):                  # noqa: F811
    """`reference.follow` keeps the start weights, both moments and the
    summed gradient of the blocks before (16 B a parameter) while one
    block's `value_and_grad` runs (its arguments, the working copy; its
    results, the new gradient; its temporaries): compiled for the described
    chip at the cell's own size, in float32, they stay under 15.75 GiB. 602-
    606M parameters are on record as not fitting; this is 568.5M."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding
    from benchmark import cells, reference
    one = SingleDeviceSharding(topo.devices[0])
    cell = cells.Cell(CELL)
    assert cell.config["num_attention_heads"] == 16
    specs = cell.family.param_specs(cell.config, cell.traffic)
    count = sum(int(np.prod(shape)) for shape, _d, _k in specs.values())
    params = {k: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)
              for k, (shape, _d, _k) in specs.items()}
    rows, t = cell.traffic["reference_block_rows"], cell.traffic["seq_len"]
    blk = {"tok": jax.ShapeDtypeStruct((rows, t), jnp.int32, sharding=one),
           "lbl": jax.ShapeDtypeStruct((rows, t), jnp.int32, sharding=one),
           "mask": jax.ShapeDtypeStruct((rows, t), jnp.float32,
                                        sharding=one)}
    mm = reference.matmul_at("float32")
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(jax.value_and_grad(
            lambda p, b: cell.family.reference_loss(
                p, b, cell.config, cell.traffic, mm))).lower(
                    params, blk).compile()
    peak = 16 * count + device_bytes(compiled)
    print("follow at %s: 24 B x %.1fM = %.2f GiB + %.2f GiB of a block's "
          "temporaries = %.2f GiB"
          % (CELL, count / 1e6, 24 * count / 2.0 ** 30,
             (peak - 24 * count) / 2.0 ** 30, peak / 2.0 ** 30))
    assert 24 * count < peak < CHIP_BYTES - 2 ** 30     # a GiB to spare


@pytest.mark.parametrize("k,n", [(2048, 2816), (1408, 2048)])
def test_grouped_matmul_kernels_compile_at_an_expert_width_of_1408(
        topo, no_compile_cache, k, n):            # noqa: F811
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    one = SingleDeviceSharding(topo.devices[0])
    pairs = 16384 * 6
    tm = gm.row_tile(pairs)
    rows = gm.buffer_rows(pairs, 8, tm)
    assert (tm, rows) == (512, 102400)
    # 1408 = 11 x 128 has no other multiple of 128 among its divisors, and
    # 2816 = 22 x 128 only 256: W2's dX runs 128-wide output tiles and its
    # dW 128-deep K tiles (a `perf_opt` issue's to mend: PERF.md section 7)
    tiles = gm.plan(rows, k, n, tm)
    assert tiles == ((512, (256, 2048), (512, 1408), (1024, 256))
                     if k == 2048 else
                     (512, (512, 1408), (128, 2048), (128, 512)))

    def struct(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def call(x, w, sizes):
        out, vjp = jax.vjp(lambda x_, w_: gm.grouped_matmul(
            x_, w_, sizes, tm, interpret=False), x, w)
        return out, vjp(out)

    text = jax.jit(call).lower(
        struct((rows, k)), struct((8, k, n)),
        struct((8,), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    for name in GMM:
        assert name in text, name
