"""Compile-only, for a described v5e:2x2 topology with no chip attached: the
flash kernels at the `sdar-30b-a3b.bd4-t8192-b1` cell's attention call under
the block-diffusion rule (32 query heads on 4 key heads of 128 over the
16,384 rows of an 8,192-token document's two copies, blocks of 4), which is
what Mosaic has to take: the rule's scalar arithmetic in the index maps and
the column / row block ids in the tile. The whole step and the check's
reference at full size (it has to fit 15.75 GiB, fill a quarter of it, and
hold no (2T, 2T) operand) are behind `slow`: a minute each of one worker.
As `test_compile_smallthinker.py`: the topology is described inside
`test_compile_fullsize.py`'s fixture, so only the worker that is given this
file loads the TPU's library."""
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
CELL = "sdar-30b-a3b.bd4-t8192-b1"
SHAPE = re.compile(r"(?:f32|bf16|s32|pred|u32|s8|u8)\[([0-9,]+)\]")
FLASH = ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq")


def _shapes(text):
    return {tuple(int(d) for d in m.group(1).split(",") if d)
            for m in SHAPE.finditer(text)}


@pytest.mark.parametrize("heads", [(32, 4), (4, 4)])
def test_flash_kernels_compile_under_the_block_diffusion_rule(
        topo, no_compile_cache, heads):           # noqa: F811
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.ops.pallas import flash_attention as fa
    one = SingleDeviceSharding(topo.devices[0])
    hq, hkv = heads
    q_shape, kv_shape, bd = (1, hq, 16384, 128), (1, hkv, 16384, 128), \
        (4, 8192)
    path = fa.attention_path(q_shape, kv_shape, kv_shape, jnp.bfloat16,
                             False, None, False, block_diffusion=bd)
    fused = hq == hkv
    assert path.path == "flash"
    assert path.backward == ("fused" if fused else "split: group")
    assert set(path.blocks) == {(1024, 1024)}

    def struct(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)

    def call(q, k, v):
        out, vjp = jax.vjp(lambda q_, k_, v_: fa.flash_attention(
            q_, k_, v_, scale=128 ** -0.5, interpret=False,
            block_diffusion=bd), q, k, v)
        return out, vjp(out)

    text = jax.jit(call).lower(struct(q_shape), struct(kv_shape),
                               struct(kv_shape)).compile().as_text()
    assert text.count("tpu_custom_call") == (2 if fused else 3)
    for name in (("flash_fwd", "flash_bwd") if fused else FLASH):
        assert name in text, name
    assert not [s for s in _shapes(text) if s.count(16384) >= 2]


@pytest.mark.slow
def test_step_compiles_for_v5e_fits_and_holds_no_square(
        topo, no_compile_cache, monkeypatch):     # noqa: F811
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "0")
    compiled = lower_step(CELL, topo.devices[:1])
    need = device_bytes(compiled)
    print("%s: %.2f GiB on the chip by memory_analysis()"
          % (CELL, need / 2.0 ** 30))
    assert 0.25 * CHIP_BYTES < need < CHIP_BYTES
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    # six layers x (forward, replayed forward, dK/dV, dQ), every one under
    # the scope a trace reader finds them by, and no other flash call
    scoped = [line for line in calls if "block_diffusion_attention" in line]
    assert len(scoped) == 6 * 4
    assert all(any(k in line for k in FLASH) for line in scoped)
    assert not [line for line in calls if any(k in line for k in FLASH)
                and line not in scoped]
    assert not [s for s in _shapes(text) if s.count(16384) >= 2]


@pytest.mark.slow
def test_the_checks_reference_fits_beside_its_copies(
        topo, no_compile_cache):                  # noqa: F811
    """`reference.follow` keeps 16 B a parameter beside one block's
    `value_and_grad` (its arguments, results and temporaries): compiled for
    the described chip at the cell's own size, in float32, with the
    reference walking one key head and its 8 query heads at a time."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding
    from benchmark import cells, reference
    one = SingleDeviceSharding(topo.devices[0])
    cell = cells.Cell(CELL)
    specs = cell.family.param_specs(cell.config, cell.traffic)
    count = sum(int(np.prod(shape)) for shape, _d, _k in specs.values())
    params = {k: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one)
              for k, (shape, _d, _k) in specs.items()}
    rows, t = cell.traffic["reference_block_rows"], cell.traffic["seq_len"]
    blk = {"noisy": jax.ShapeDtypeStruct((rows, t), jnp.int32, sharding=one),
           "tok": jax.ShapeDtypeStruct((rows, t), jnp.int32, sharding=one),
           "weight": jax.ShapeDtypeStruct((rows, t), jnp.float32,
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
    assert 24 * count < peak < CHIP_BYTES - 2 ** 31     # two GiB to spare
