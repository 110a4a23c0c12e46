"""The `kda_fwd` / `kda_bwd` Pallas kernels (ops/pallas/delta_rule.py) in
interpret mode on the CPU, so tier-1 runs the kernel bodies: against the
token-by-token recurrence and against the XLA form `_kda` (the kernels'
oracle) in value and in all five gradients, over decay (typical, so strong
that a chunk's cumulative log-decay passes -100, none), T (whole chunks;
150, which is none), dtype (float32; bfloat16 inputs with g float32) and
two head counts (two heads a grid step; one); and the saved states' shape.
The path rule and the tool's `--kernels` mode are in
`test_kda_kernel_path.py`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import linear_attn_ops as la
from paddle_tpu.ops.pallas import delta_rule

from test_kda_op import inputs, recurrence

D = 128
SCALE = D ** -0.5


def wide_inputs(t, decay, heads, seed=0):
    return inputs(t, decay, seed=seed, b=1, h=heads, dk=D, dv=D)


def value_and_grads(fn, args, cot):
    out, pull = jax.vjp(fn, *args)
    return out, pull(cot.astype(out.dtype))


def close(got, want, rel, name):
    got, want = (jnp.asarray(x, jnp.float32) for x in (got, want))
    assert bool(jnp.all(jnp.isfinite(got))), name
    norm = float(jnp.linalg.norm(want))
    assert float(jnp.linalg.norm(got - want)) <= rel * norm + 1e-6, name


@pytest.mark.parametrize("heads", [2, 3])
@pytest.mark.parametrize("decay", ["typical", "strong", "none"])
@pytest.mark.parametrize("t", [192, 150])
def test_kernels_equal_the_recurrence_and_the_xla_form_in_float32(
        t, decay, heads):
    args = wide_inputs(t, decay, heads)
    if decay == "strong":
        assert float(jnp.sum(args[3][:, :la.CHUNK], axis=1).min()) < -100.0
    assert delta_rule.pick_heads(heads) == (2 if heads == 2 else 1)
    assert delta_rule.pick_heads(16) == 8 and delta_rule.pick_heads(12) == 4
    cot = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    got = value_and_grads(
        lambda *a: delta_rule.kda(*a, SCALE, True), args, cot)
    oracle = value_and_grads(lambda *a: la._kda(*a, SCALE), args, cot)
    plain = value_and_grads(lambda *a: recurrence(*a, SCALE), args, cot)
    np.testing.assert_allclose(got[0], plain[0], rtol=2e-4, atol=2e-5)
    close(got[0], oracle[0], 2e-5, "o")
    for name, mine, xla, ref in zip("q k v g beta".split(), got[1],
                                    oracle[1], plain[1]):
        close(mine, ref, 2e-4, "d" + name + " against the recurrence")
        close(mine, xla, 2e-5, "d" + name + " against the XLA form")


@pytest.mark.parametrize("heads", [2, 1])
@pytest.mark.parametrize("decay", ["typical", "strong", "none"])
def test_bfloat16_inputs_round_where_the_xla_form_rounds(decay, heads):
    """bfloat16 q, k, v, beta with g float32: the kernels send bfloat16
    operands to the MXU where `_mm(..., mxu)` does and nowhere else, so
    they stay as near the float32 recurrence as the XLA form does, and
    nearer the XLA form than either is to the recurrence."""
    full = wide_inputs(150, decay, heads, seed=5)
    args = tuple(x.astype(jnp.bfloat16) for x in full[:3]) \
        + (full[3], full[4].astype(jnp.bfloat16))
    exact = tuple(x.astype(jnp.float32) for x in args)
    cot = jax.random.normal(jax.random.PRNGKey(3), args[2].shape)
    got = value_and_grads(
        lambda *a: delta_rule.kda(*a, SCALE, True), args, cot)
    oracle = value_and_grads(lambda *a: la._kda(*a, SCALE), args, cot)
    plain = value_and_grads(lambda *a: recurrence(*a, SCALE), exact, cot)
    assert got[0].dtype == jnp.bfloat16
    assert [x.dtype for x in got[1]] == [x.dtype for x in args]
    close(got[0], plain[0], 0.02, "o")
    for name, mine, xla, ref in zip("q k v g beta".split(), got[1],
                                    oracle[1], plain[1]):
        ref = jnp.asarray(ref, jnp.float32)
        gap = float(jnp.linalg.norm(jnp.asarray(xla, jnp.float32) - ref))
        close(mine, ref, 0.03, "d" + name)
        assert float(jnp.linalg.norm(jnp.asarray(mine, jnp.float32) - ref)) \
            <= 1.5 * gap + 1e-6, name


def test_the_forward_saves_the_state_each_chunk_began_with():
    """(groups, chunks a group, B, H, V, K) float32: the XLA form's states
    transposed (the kernels hold S^T); 20 chunks are two groups of 16, of
    which the kernels write the first 20 states."""
    args = wide_inputs(20 * la.CHUNK - 7, "typical", 2, seed=2)
    _out, res = delta_rule._kda_fwd(*args, SCALE, True)
    _xla_out, xla_res = la._kda_fwd(*args, SCALE)
    states, want = res[-1], xla_res[-1]
    assert states.shape == want.shape == (2, 16, 1, 2, D, D)
    assert states.dtype == jnp.float32
    assert bool(jnp.all(states[0, 0] == 0.0))
    np.testing.assert_allclose(
        jnp.swapaxes(states.reshape((32,) + states.shape[2:])[:20], -1, -2),
        want.reshape((32,) + want.shape[2:])[:20], rtol=2e-4, atol=2e-5)
