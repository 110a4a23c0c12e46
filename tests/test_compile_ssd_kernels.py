"""Compile-only, for a described v5e:2x2 topology with no chip attached: the
`ssd_fwd` and `ssd_bwd` Pallas kernels alone at the
`nemotron-twotower-30b-a3b.t8192-b2` cell's call ((2, 8192, 64 x 64), 8
groups, state 128, bfloat16 with dt float32), each within the VMEM `plan`
reckons for it (Mosaic refuses a kernel that asks for more than its limit);
and, behind `slow`, the cell's whole step: it holds the Mamba-2 scan as those
kernels (`ssd_fwd` six times: three layers, forward and recompute's replay;
`ssd_bwd` three times), no (2, 64, 64, 128, 128) float32 decay scores and no
loop under a `mamba2_scan` scope (the XLA form's `lax.scan` over the chunks'
states is gone: the chunk axis is the kernels' grid), and it fits the chip.
The topology is described inside `tests/benchmark_suite/
test_compile_fullsize.py`'s fixture, which skips where it cannot be."""
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(HERE), os.path.join(HERE, "benchmark_suite")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_compile_fullsize import (device_bytes, lower_step,  # noqa: E402
                                   no_compile_cache, topo)    # noqa: F401
from test_compile_kda_kernels import CHIP_BYTES, _op_names  # noqa: E402

CELL = "nemotron-twotower-30b-a3b.t8192-b2"
B, T, H, P, G, N = 2, 8192, 64, 64, 8, 128


@pytest.mark.parametrize("kernel", ["ssd_fwd", "ssd_bwd"])
def test_the_kernels_compile_at_the_cells_call_within_their_vmem(
        topo, no_compile_cache, kernel):     # noqa: F811
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.ops.pallas import ssd
    plan = ssd.plan((B, T, H, P), G, N, ssd.CHUNK, 2)
    assert plan["heads_a_step"] == 8
    assert plan["vmem_fwd"] < plan["vmem_bwd"] < 32 * 2 ** 20
    assert "ssd_fwd" in plan["kernels"] and "ssd_bwd" in plan["kernels"]
    one = SingleDeviceSharding(topo.devices[0])
    chunks = T // ssd.CHUNK

    def struct(dtype, *last):
        return jax.ShapeDtypeStruct((B, chunks, ssd.CHUNK) + last, dtype,
                                    sharding=one)

    x, dy = struct(jnp.bfloat16, H, P), struct(jnp.float32, H, P)
    bc = struct(jnp.bfloat16, G, N)
    operands = (x, struct(jnp.float32, H),
                jax.ShapeDtypeStruct((H,), jnp.float32, sharding=one), bc, bc)
    if kernel == "ssd_fwd":
        fn, more = ssd._forward, ()
    else:
        fn = ssd._backward
        more = (jax.ShapeDtypeStruct((B, chunks, H // 8, N, 8 * P),
                                     jnp.float32, sharding=one), dy)
    text = jax.jit(lambda *xs: fn(*xs, interpret=False)).lower(
        *operands, *more).compile().as_text()
    calls = [n for n in _op_names(text, "custom-call") if "/ssd_" in n]
    assert len(calls) == 1 and "/%s/" % kernel in calls[0], calls


@pytest.mark.slow
def test_the_nemotron_step_holds_the_scan_as_kernels_and_no_loop_of_it(
        topo, no_compile_cache, monkeypatch):     # noqa: F811
    """Behind `slow`: `tests/benchmark_suite/test_compile_fullsize.py`
    compiles the same step in tier-1 (that it fits), 2-3 minutes a time."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "0")
    compiled = lower_step(CELL, topo.devices[:1])
    need = device_bytes(compiled)
    print("%s: %.2f GiB on the chip by memory_analysis()"
          % (CELL, need / 2.0 ** 30))
    assert need < CHIP_BYTES
    text = compiled.as_text()
    calls = [n for n in _op_names(text, "custom-call") if "/ssd_" in n]
    kernels = [re.search(r"/(ssd_[a-z_]+)/pallas_call", n).group(1)
               for n in calls]
    assert kernels.count("ssd_fwd") == 6, kernels
    assert kernels.count("ssd_bwd") == 3, kernels
    # every call lies under the op's scope, where `ssd_device_ms` reads it
    assert all("/mamba2_scan" in n for n in calls), calls
    assert "f32[2,64,64,128,128]" not in text
    assert "f32[2,64,8,8,128,128]" not in text
    loops = _op_names(text, "while")
    assert not [n for n in loops if "mamba2_scan" in n], loops
