"""The fused flash backward kernel (`flash_bwd`) against the split pair it
stands for at equal tiles, to the bit, at equal and unequal widths, in
float32 (bfloat16: `test_flash_fused_backward_bf16.py`); at unequal backward
tiles to float32 rounding; and which of the two a call differentiates
through."""
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa

from _flash_cases import (_flash_grads, _inputs, _worst,
                          fused_backward_against_the_split_kernels)

test_fused_backward_equals_the_split_kernels_to_the_bit = \
    fused_backward_against_the_split_kernels("float32")


def test_fused_backward_with_unequal_backward_tiles_matches_the_split():
    """The split kernels at their own (different) tiles sum in another
    order than the fused one at its tile: equal to float32 rounding, 1e-6
    of the gradient's range, not to the bit."""
    q, k, v, w = _inputs(1, 2, 2, 64, 64, 16, 16, seed=13)
    fused = _flash_grads(((16, 16), (32, 16)), q, k, v, w, None, True)
    split = _flash_grads(((16, 16), (16, 32), (8, 8)), q, k, v, w, None,
                         True)
    assert _worst(fused, split) < 1e-6


@pytest.mark.parametrize("hq,hkv,d,dv,window,fused", [
    (2, 2, 16, 16, None, True),
    (2, 2, 24, 16, None, True),         # Dv != D, either way round
    (2, 2, 16, 32, None, True),
    (4, 2, 16, 16, None, True),         # grouped heads (PR 49)
    (4, 2, 24, 16, None, True),
    (14, 2, 16, 16, None, True),
    (2, 2, 24, 16, 32, False),          # a window, whatever the heads
    (4, 2, 16, 16, 32, False)])
def test_which_backward_a_call_differentiates_through(hq, hkv, d, dv,
                                                      window, fused):
    q, k, v, _w = _inputs(1, hq, hkv, 64, 64, d, dv)
    text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        fa.flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                           window=window, interpret=True)),
        (0, 1, 2)))(q, k, v))
    assert text.count("pallas_call[") == (2 if fused else 3)
    assert ("name=flash_bwd\n" in text) == fused
    assert ("flash_bwd_dkv" in text) == (not fused)
    assert ("flash_bwd_dq" in text) == (not fused)
