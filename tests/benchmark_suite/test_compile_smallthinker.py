"""Compile-only, for a described v5e:2x2 topology with no chip attached:
the `smallthinker-21b-a3b.t16384-b2` step program at full size (it has to
fit 15.75 GiB and fill a quarter of it, and hold no (T, T) score matrix),
the flash kernels at its two attention calls (7 query heads a key head at
D 128, T 16,384: full causal, and a 4,096 window) and the grouped-matmul
kernels at its widths. As `test_compile_lfm2moe.py`: the topology is
described inside `test_compile_fullsize.py`'s fixture, so only the worker
that is given this file loads the TPU's library (where another worker
already holds it, the fixture skips)."""
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
CELL = "smallthinker-21b-a3b.t16384-b2"
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
    assert 0.25 * CHIP_BYTES < need < CHIP_BYTES
    text = compiled.as_text()
    for name in FLASH + GMM:
        assert name in text, name
    # 4 layers x (forward, replayed forward, dK/dV, dQ) flash calls and
    # 4 x 2 matrices x (forward, replayed forward, dX, dW) grouped matmuls
    assert text.count("tpu_custom_call") >= 16 + 32
    # the three window layers' calls, and theirs alone, carry the scope a
    # trace reader splits them by: forward, replay, and both backward kernels
    scoped = [line for line in text.splitlines()
              if "tpu_custom_call" in line and "window_attention" in line]
    assert len(scoped) == 3 * 4
    assert all(any(k in line for k in FLASH) for line in scoped)
    assert not [s for s in _shapes(text) if s.count(16384) >= 2]


@pytest.mark.parametrize("window", [None, 4096])
def test_flash_kernels_compile_at_group_7_width_128_t_16384(
        topo, no_compile_cache, window):          # noqa: F811
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.ops.pallas import flash_attention as fa
    one = SingleDeviceSharding(topo.devices[0])
    q_shape, kv_shape = (2, 28, 16384, 128), (2, 4, 16384, 128)
    path = fa.attention_path(q_shape, kv_shape, kv_shape, jnp.bfloat16, True,
                             window, False)
    assert path.path == "flash" and path.backward == "split: group"
    assert path.blocks == ((1024, 1024),) * 3
    # a window row walks 5 key blocks of 16, a key block 5 query blocks a
    # head of its group
    plan = fa.plan(q_shape, kv_shape, kv_shape, True, window, path.blocks,
                   path.backward)
    assert plan["group"] == 7
    assert plan["fwd"]["grid_inner"] == (16 if window is None else 5)
    assert plan["bwd_dkv"]["grid_inner"] == (16 if window is None else 5)
    assert plan["fwd"]["tiles_visited"] == (136 if window is None else 70)

    def struct(shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)

    def call(q, k, v):
        out, vjp = jax.vjp(lambda q_, k_, v_: fa.flash_attention(
            q_, k_, v_, scale=128 ** -0.5, causal=True, window=window,
            interpret=False), q, k, v)
        return out, vjp(out)

    text = jax.jit(call).lower(struct(q_shape), struct(kv_shape),
                               struct(kv_shape)).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    for name in FLASH:
        assert name in text, name
    assert not [s for s in _shapes(text) if s.count(16384) >= 2]


@pytest.mark.parametrize("k,n", [(2560, 1536), (768, 2560)])
def test_grouped_matmul_kernels_compile_at_the_cells_widths(
        topo, no_compile_cache, k, n):            # noqa: F811
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    one = SingleDeviceSharding(topo.devices[0])
    pairs = 32768 * 6
    tm = gm.row_tile(pairs)
    rows = gm.buffer_rows(pairs, 8, tm)
    assert (tm, rows) == (512, 200704)

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
