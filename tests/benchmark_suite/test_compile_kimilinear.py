"""Compile-only, for a described v5e:2x2 topology with no chip attached:
the `kimi-linear-48b-a3b.t8192-b2` step program at full size (it has to fit
15.75 GiB and fill a quarter of it, hold no (T, T) score matrix and no
(T, K, V) state history) and the flash kernels at the latent-attention
layer's widths (D 192, Dv 128). As `test_compile_lfm2moe.py`: the topology
is described inside `test_compile_fullsize.py`'s fixture, so only the worker
that is given this file loads the TPU's library (where another worker
already holds it, the fixture skips)."""
import math
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (REPO, os.path.dirname(os.path.abspath(__file__))):
    if p not in sys.path:
        sys.path.insert(0, p)

from test_compile_fullsize import (device_bytes, lower_step,  # noqa: E402
                                   no_compile_cache, topo)    # noqa: F401

CHIP_BYTES = 16909336064        # bytes_limit a v5e reports: 15.75 GiB
CELL = "kimi-linear-48b-a3b.t8192-b2"
SHAPE = re.compile(r"(?:f32|bf16|s32|pred|u32|s8|u8)\[([0-9,]+)\]")


def _shapes(text):
    return {tuple(int(d) for d in m.group(1).split(",") if d)
            for m in SHAPE.finditer(text)}


def test_step_compiles_for_v5e_fits_and_holds_no_history(
        topo, no_compile_cache, monkeypatch):     # noqa: F811
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "0")
    compiled = lower_step(CELL, topo.devices[:1])
    need = device_bytes(compiled)
    print("%s: %.2f GiB on the chip by memory_analysis()"
          % (CELL, need / 2.0 ** 30))
    assert 0.25 * CHIP_BYTES < need < CHIP_BYTES
    text = compiled.as_text()
    # 4 expert layers x 2 matrices x (forward, replayed forward, dX, dW)
    # and the latent-attention layer's forward, replayed forward and its
    # split backward; the delta rule is XLA's (no `kda_*` kernel yet)
    for name in ("moe_gmm_fwd", "moe_gmm_dx", "moe_gmm_dw", "flash_fwd",
                 "flash_bwd_dkv", "flash_bwd_dq"):
        assert name in text, name
    assert text.count("tpu_custom_call") >= 36
    shapes = _shapes(text)
    # no (T, T) scores in any layout, and nothing the size of a state a
    # token: (B, T, H, K, V) would be 4.3e9 numbers; the largest array a
    # step holds is the expert layer's worst-case row buffer,
    # 135,168 x 2,304
    assert not [s for s in shapes if s.count(8192) >= 2]
    largest = max(shapes, key=math.prod)
    assert math.prod(largest) < 4e8, largest
    # the states the delta rule keeps: one (K, V) a head and 64-token chunk
    assert (8, 16, 2, 16, 128, 128) in shapes


def test_flash_kernels_compile_at_the_latent_layers_widths(
        topo, no_compile_cache):                  # noqa: F811
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.ops.pallas import flash_attention as fa
    one = SingleDeviceSharding(topo.devices[0])
    shape = (2, 16, 8192)
    path = fa.attention_path(shape + (192,), shape + (192,), shape + (128,),
                             jnp.bfloat16, True, None, False)
    assert path.path == "flash" and path.backward == "split: widths"

    def struct(width):
        return jax.ShapeDtypeStruct(shape + (width,), jnp.bfloat16,
                                    sharding=one)

    def call(q, k, v):
        out, vjp = jax.vjp(lambda q_, k_, v_: fa.flash_attention(
            q_, k_, v_, scale=192 ** -0.5, causal=True, interpret=False),
            q, k, v)
        return out, vjp(out)

    text = jax.jit(call).lower(struct(192), struct(192),
                               struct(128)).compile().as_text()
    assert text.count("tpu_custom_call") == 3
    for name in ("flash_fwd", "flash_bwd_dkv", "flash_bwd_dq"):
        assert name in text, name
    assert not [s for s in _shapes(text) if s.count(8192) >= 2]
