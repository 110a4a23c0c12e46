"""Compile-only, for a described v5e:2x2 topology with no chip attached: the
fused flash backward at unequal widths. The latent-attention call of the
two Kimi cells, (2, 16, 8192, 192 | 128) bfloat16 causal, has to compile to
exactly two custom calls (`flash_fwd`, `flash_bwd`), hold no (T, T) array
and ask Mosaic for VMEM under the ceiling; the longest dQ row the rule
still keeps fused at 192 (Tq = 24,576) has to compile too; and the
`kimi-vl-a3b.t8192-b2` step at full size has to fit 15.75 GiB, fill a
quarter of it, hold `flash_bwd` in place of the split pair and no (T, T)
array (Kimi-Linear's one latent layer is the same call: no second
whole-step compile). What `tests/benchmark_suite/test_compile_kimilinear.py`
and `test_compile_kimivl.py` guarded while the call was "split: widths".
The topology is described inside `test_compile_fullsize.py`'s fixture,
which skips where it cannot be."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(HERE), os.path.join(HERE, "benchmark_suite")):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_compile_fullsize import (device_bytes, lower_step,  # noqa: E402
                                   no_compile_cache, topo)    # noqa: F401
from test_compile_kimivl import (CELL, CHIP_BYTES, GMM,       # noqa: E402
                                 _shapes)

#: (batch, heads, T, D, Dv): the cells' call, and the longest row kept fused
CALLS = {"kimi": (2, 16, 8192, 192, 128),
         "longest_row": (1, 1, 24576, 192, 128)}


@pytest.mark.parametrize("call", list(CALLS))
def test_the_call_compiles_to_flash_fwd_and_one_flash_bwd(
        topo, no_compile_cache, call):            # noqa: F811
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.ops.pallas import flash_attention as fa
    one = SingleDeviceSharding(topo.devices[0])
    b, h, t, d, dv = CALLS[call]
    shape = (b, h, t)
    path = fa.attention_path(shape + (d,), shape + (d,), shape + (dv,),
                             jnp.bfloat16, True, None, False)
    assert path == ("flash", ((1024, 1024), (1024, 1024)), None, "fused")
    # what the call asks Mosaic for: over its 16 MiB default, under 96 MiB
    params = fa._compiler_params("bwd", 1024, 1024, d, jnp.bfloat16, "none",
                                 dv, t)
    assert fa._VMEM_DEFAULT < params.vmem_limit_bytes <= fa._VMEM_CEILING
    assert params.vmem_limit_bytes == fa.vmem_bytes(
        "bwd", 1024, 1024, d, 2, "none", dv, t)

    def struct(width):
        return jax.ShapeDtypeStruct(shape + (width,), jnp.bfloat16,
                                    sharding=one)

    def run(q, k, v):
        out, vjp = jax.vjp(lambda q_, k_, v_: fa.flash_attention(
            q_, k_, v_, scale=d ** -0.5, causal=True, interpret=False),
            q, k, v)
        return out, vjp(out)

    text = jax.jit(run).lower(struct(d), struct(d),
                              struct(dv)).compile().as_text()
    assert text.count("tpu_custom_call") == 2
    for name, there in (("flash_fwd", True), ("flash_bwd", True),
                        ("flash_bwd_dkv", False), ("flash_bwd_dq", False)):
        assert (name in text) == there, name
    assert not [s for s in _shapes(text) if s.count(t) >= 2]


@pytest.mark.slow
def test_the_kimi_vl_step_compiles_fits_and_holds_the_fused_backward(
        topo, no_compile_cache, monkeypatch):     # noqa: F811
    """Behind `slow`: `tests/benchmark_suite/test_compile_kimivl.py::
    test_step_compiles_for_v5e_fits_and_holds_no_scores` compiles the same
    step; its re-pin (ROADMAP C1 (j)) brings this guard back into tier-1."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "0")
    compiled = lower_step(CELL, topo.devices[:1])
    need = device_bytes(compiled)
    print("%s: %.2f GiB on the chip by memory_analysis()"
          % (CELL, need / 2.0 ** 30))
    assert 0.25 * CHIP_BYTES < need < CHIP_BYTES
    text = compiled.as_text()
    for name in ("flash_fwd", "flash_bwd") + GMM:
        assert name in text, name
    for name in ("flash_bwd_dkv", "flash_bwd_dq"):
        assert name not in text, name
    # 5 layers x (forward, replayed forward, one backward) flash calls and
    # 4 x 2 matrices x (forward, replayed forward, dX, dW) grouped matmuls
    assert text.count("tpu_custom_call") >= 15 + 32
    assert not [s for s in _shapes(text) if s.count(8192) >= 2]
