"""Compile-only, for a described v5e:2x2 topology with no chip attached:
the new cell's step program at full size (it has to fit 15.75 GiB and fill
a quarter of it), and the new kernels at the cell's widths. As
`test_compile_fullsize.py`, whose `lower_step` this file borrows: the
topology is described inside a fixture, so only the worker that is given
this file loads the TPU's library (where another worker already holds it,
the fixture skips)."""
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
CELL = "phi4-mini-flash.t8192-b1"


def test_step_compiles_for_v5e_fits_and_fills_a_quarter(
        topo, no_compile_cache, monkeypatch):     # noqa: F811
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "0")
    compiled = lower_step(CELL, topo.devices[:1])
    need = device_bytes(compiled)
    print("%s: %.2f GiB on the chip by memory_analysis()"
          % (CELL, need / 2.0 ** 30))
    assert 0.25 * CHIP_BYTES < need < CHIP_BYTES
    text = compiled.as_text()
    # 3 attention layers x (forward, replayed forward, dK/dV, dQ) and
    # the Mamba layer's forward, replayed forward and backward
    assert text.count("tpu_custom_call") >= 15
    for name in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq",
                 "ssm_scan_fwd", "ssm_scan_bwd"):
        assert name in text, name


@pytest.mark.parametrize("window", [512, None])
def test_flash_kernels_compile_at_the_cells_widths(topo, no_compile_cache,
                                                   window):  # noqa: F811
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.ops.pallas import flash_attention as fa
    one = SingleDeviceSharding(topo.devices[0])

    def struct(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)

    def call(q, k, v):
        out, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
            q, k, v, scale=0.125, causal=True, window=window,
            interpret=False), q, k, v)
        return out, vjp(out)

    compiled = jax.jit(call).lower(
        struct(2, 20, 8192, 64), struct(2, 10, 8192, 64),
        struct(2, 10, 8192, 128)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 3


def test_scan_kernels_compile_at_the_cells_widths(topo,
                                                  no_compile_cache):  # noqa: F811
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.ops.pallas import selective_scan as ss
    one = SingleDeviceSharding(topo.devices[0])

    def struct(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def call(*args):
        out, vjp = jax.vjp(lambda *a: ss.selective_scan(*a, interpret=False),
                           *args)
        return out, vjp(out)

    b, t, e, n = 1, 8192, 5120, 16
    compiled = jax.jit(call).lower(
        struct((b, t, e)), struct((b, t, e)), struct((e, n), jnp.float32),
        struct((b, t, n)), struct((b, t, n)),
        struct((e,), jnp.float32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2
