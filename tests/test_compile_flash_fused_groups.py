"""Compile-only, for a described v5e:2x2 topology with no chip attached: the
fused flash backward with grouped query heads. The five cells' calls whose
attention shares key/value heads (SDAR's under its block-diffusion rule,
SmallThinker's global layer, LFM2's, Nemotron's, Phi's full layer) each
have to compile to exactly two custom calls (`flash_fwd`, one `flash_bwd`
and no `flash_bwd_dkv` / `flash_bwd_dq`), hold no (T, T) array and ask
Mosaic for VMEM under the ceiling; the longest grouped call the rule keeps
fused (its tile halved) has to compile too, and a window keeps the pair.
The topology is described inside `test_compile_fullsize.py`'s fixture,
which skips where it cannot be."""
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
for p in (os.path.dirname(HERE), os.path.join(HERE, "benchmark_suite")):
    if p not in sys.path:
        sys.path.insert(0, p)

from _flash_cases import GROUPED_CELL_CALLS, call_shapes    # noqa: E402
from test_compile_fullsize import no_compile_cache, topo    # noqa: E402,F401

SHAPE = re.compile(r"(?:bf16|f32)\[([0-9,]+)\]")

#: the cells' calls but Phi's window layer (the benchmark suite compiles
#: it), and the longest grouped call the rule keeps fused
CALLS = dict({name: call for name, call in GROUPED_CELL_CALLS.items()
              if name != "phi4-mini-flash.t8192-b1/window"},
             longest_rows_kept_fused=(1, 8, 1, 32768, 128, 128, None, None))


def _shapes(text):
    return {tuple(int(d) for d in m.group(1).split(",") if d)
            for m in SHAPE.finditer(text)}


@pytest.mark.parametrize("call", list(CALLS))
def test_a_grouped_call_compiles_to_flash_fwd_and_one_flash_bwd(
        topo, no_compile_cache, call):            # noqa: F811
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from paddle_tpu.ops.pallas import flash_attention as fa
    one = SingleDeviceSharding(topo.devices[0])
    _b, hq, hkv, t, d, dv, window, rule = CALLS[call]
    shapes = call_shapes(CALLS[call])
    fused = window is None
    path = fa.attention_path(*shapes, jnp.bfloat16, rule is None, window,
                             False, block_diffusion=rule)
    assert path.path == "flash"
    assert path.backward == ("fused" if fused else "split: window")
    if fused:
        # what the call asks Mosaic for: over its 16 MiB default, under 96
        tile = path.blocks[1]
        assert tile == ((512, 1024) if t == 32768 else (1024, 1024))
        params = fa._compiler_params("bwd", *tile, d, jnp.bfloat16, "none",
                                     dv, **fa._bwd_rows(t, t, hq // hkv))
        assert fa._VMEM_DEFAULT < params.vmem_limit_bytes <= fa._VMEM_CEILING

    def run(q, k, v):
        out, vjp = jax.vjp(lambda q_, k_, v_: fa.flash_attention(
            q_, k_, v_, scale=d ** -0.5, causal=rule is None, window=window,
            interpret=False, block_diffusion=rule), q, k, v)
        return out, vjp(out)

    text = jax.jit(run).lower(*(
        jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one)
        for s in shapes)).compile().as_text()
    assert text.count("tpu_custom_call") == (2 if fused else 3)
    for name, there in (("flash_fwd", True), ("flash_bwd", True),
                        ("flash_bwd_dkv", not fused),
                        ("flash_bwd_dq", not fused)):
        assert (name in text) == there, name
    assert not [s for s in _shapes(text) if s.count(t) >= 2]
