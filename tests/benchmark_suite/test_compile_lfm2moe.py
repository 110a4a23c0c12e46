"""Compile-only, for a described v5e:2x2 topology with no chip attached:
the `lfm2-8b-a1b.t8192-b2` step program at full size (it has to fit
15.75 GiB and fill a quarter of it) and the grouped-matmul kernels at the
cell's widths. As `test_compile_phi4flash.py`: the topology is described
inside `test_compile_fullsize.py`'s fixture, so only the worker that is
given this file loads the TPU's library (where another worker already holds
it, the fixture skips)."""
import os
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
CELL = "lfm2-8b-a1b.t8192-b2"


def test_step_compiles_for_v5e_fits_and_fills_a_quarter(
        topo, no_compile_cache, monkeypatch):     # noqa: F811
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "0")
    compiled = lower_step(CELL, topo.devices[:1])
    need = device_bytes(compiled)
    print("%s: %.2f GiB on the chip by memory_analysis()"
          % (CELL, need / 2.0 ** 30))
    assert 0.25 * CHIP_BYTES < need < CHIP_BYTES
    text = compiled.as_text()
    # 4 expert layers x 2 matrices x (forward, replayed forward, dX, dW)
    # and the attention layer's forward, replayed forward and backward
    assert text.count("tpu_custom_call") >= 35
    for name in ("moe_gmm_fwd", "moe_gmm_dx", "moe_gmm_dw", "flash_fwd"):
        assert name in text, name


@pytest.mark.parametrize("k,n", [(2048, 3584), (1792, 2048)])
def test_grouped_matmul_kernels_compile_at_the_cells_widths(
        topo, no_compile_cache, k, n):            # noqa: F811
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.ops.pallas import grouped_matmul as gm
    one = SingleDeviceSharding(topo.devices[0])
    tm = gm.row_tile(16384 * 4)
    rows = gm.buffer_rows(16384 * 4, 8, tm)

    def struct(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def call(x, w, sizes):
        out, vjp = jax.vjp(lambda x_, w_: gm.grouped_matmul(
            x_, w_, sizes, tm, interpret=False), x, w)
        return out, vjp(out)

    compiled = jax.jit(call).lower(
        struct((rows, k)), struct((8, k, n)),
        struct((8,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    for name in ("moe_gmm_fwd", "moe_gmm_dx", "moe_gmm_dw"):
        assert name in text, name
