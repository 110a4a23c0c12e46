"""What the flash kernels' test files share (`test_flash_modes.py`,
`test_flash_fused_backward.py`, `test_flash_fused_backward_bf16.py`,
`test_flash_fused_backward_groups.py`, `test_flash_lowering_pins.py`,
`test_compile_flash_fused_groups.py`): seeded q, k, v and a cotangent, the
worst relative gap of two lists of arrays, the fused backward against the
split pair to the bit, whose cases the second and third file run a dtype
each, and the attention calls of the cells whose query heads share
key/value heads."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa


#: the calls of the five cells with more than one query head a key/value
#: head (`benchmark/families/*.py:attention_calls`), bfloat16: (batch,
#: query heads, kv heads, T, D, Dv, window, block_diffusion); causal where
#: no rule stands in the last place
GROUPED_CELL_CALLS = {
    "sdar-30b-a3b.bd4-t8192-b1": (1, 32, 4, 16384, 128, 128, None,
                                  (4, 8192)),
    "smallthinker-21b-a3b.t16384-b2/global": (2, 28, 4, 16384, 128, 128,
                                              None, None),
    "smallthinker-21b-a3b.t16384-b2/window": (2, 28, 4, 16384, 128, 128,
                                              4096, None),
    "lfm2-8b-a1b.t8192-b2": (2, 32, 8, 8192, 64, 64, None, None),
    "nemotron-twotower-30b-a3b.t8192-b2": (2, 32, 2, 8192, 128, 128, None,
                                           None),
    "phi4-mini-flash.t8192-b1/full": (2, 20, 10, 8192, 64, 128, None, None),
    "phi4-mini-flash.t8192-b1/window": (2, 20, 10, 8192, 64, 128, 512,
                                        None),
}


def call_shapes(call):
    """The q, k and v shapes of one of GROUPED_CELL_CALLS' rows."""
    b, hq, hkv, t, d, dv = call[:6]
    return (b, hq, t, d), (b, hkv, t, d), (b, hkv, t, dv)


def _inputs(b, hq, hkv, tq, tk, d, dv, dtype=jnp.float32, seed=0):
    key = jax.random.PRNGKey(seed)
    shapes = [(b, hq, tq, d), (b, hkv, tk, d), (b, hkv, tk, dv),
              (b, hq, tq, dv)]
    q, k, v, w = (jax.random.normal(jax.random.fold_in(key, i), s,
                                    jnp.float32)
                  for i, s in enumerate(shapes))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), w


def _worst(got, want):
    return max(float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-9))
               for a, b in zip(got, want))


def _mask(mode, b, tq, tk, dtype):
    if mode == "key":
        m = np.zeros((b, 1, 1, tk), np.float32)
        m[..., 3 * tk // 4:] = -1e4
        return jnp.asarray(m, dtype)
    if mode == "qk":
        return (0.5 * jax.random.normal(jax.random.PRNGKey(9),
                                        (b, 1, tq, tk))).astype(dtype)
    return None


def _flash_grads(blocks, q, k, v, w, mask, causal):
    return jax.jit(jax.grad(lambda q, k, v: jnp.sum(fa._flash(
        q, k, v, mask, 0.25, causal, blocks, True, None).astype(jnp.float32)
        * w), (0, 1, 2)))(q, k, v)


def fused_backward_against_the_split_kernels(dtype):
    """`test_fused_backward_equals_the_split_kernels_to_the_bit` at one
    dtype: its cases are two files' (neither holds a worker for more than a
    sixth of a tier-1 run), its body and the other parameters are here."""
    @pytest.mark.parametrize("dtype", [dtype])
    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("mask_mode", ["none", "key", "qk"])
    @pytest.mark.parametrize("tq,tk,tile,d,dv", [
        (64, 64, (16, 16), 16, 16), (32, 64, (8, 16), 16, 16),
        (32, 64, (16, 8), 16, 16),
        # Dv != D: a small pair, and latent attention's decompressed heads
        (64, 64, (16, 16), 24, 16), (32, 64, (8, 16), 24, 16),
        (64, 64, (16, 16), 192, 128), (32, 64, (8, 16), 192, 128)])
    def test_fused_backward_equals_the_split_kernels_to_the_bit(
            dtype, causal, mask_mode, tq, tk, tile, d, dv):
        """Equal tiles: both run `_bwd_p_ds` and then the same dots, dK/dV
        summed over ascending q-blocks and dQ over ascending k-blocks in
        both, so every gradient is bit-equal, whatever the two widths; and
        within float32 rounding (bfloat16: its 1e-2) of the float32 XLA
        oracle."""
        q, k, v, w = _inputs(2, 2, 2, tq, tk, d, dv, jnp.dtype(dtype),
                             seed=11)
        mask = _mask(mask_mode, 2, tq, tk, jnp.dtype(dtype))
        fused = _flash_grads((tile, tile), q, k, v, w, mask, causal)
        split = _flash_grads((tile,) * 3, q, k, v, w, mask, causal)
        for a, b_ in zip(fused, split):
            assert a.dtype == b_.dtype == jnp.dtype(dtype)
            assert (np.asarray(a, np.float32)
                    == np.asarray(b_, np.float32)).all()
        f32 = [x.astype(jnp.float32) for x in (q, k, v)]
        oracle = jax.jit(jax.grad(lambda q, k, v: jnp.sum(fa._xla_attention(
            q, k, v, None if mask is None else mask.astype(jnp.float32), 0.25,
            causal) * w), (0, 1, 2)))(*f32)
        assert _worst([g.astype(jnp.float32) for g in fused], oracle) \
            < (1e-5 if dtype == "float32" else 1e-2)
    return test_fused_backward_equals_the_split_kernels_to_the_bit
