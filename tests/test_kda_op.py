"""`kda_attention` (the chunked gated delta rule, ops/linear_attn_ops.py)
against the token-by-token recurrence in float32, in value and in all five
gradients: typical decay, a decay so strong that a chunk's cumulative
log-decay passes -100 (no exp of it may overflow, and a factored
e^{G_i} e^{-G_j} would), and no decay at all (the plain delta rule); a
sequence that is no whole number of chunks; the op through a Program; the
three elementwise ops of a KDA layer against `jax.numpy`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.ops import linear_attn_ops as la
from paddle_tpu.ops.registry import get_op, get_shape_rule
from paddle_tpu.ops.shape_rules import ShapeError, TensorMeta

HIGHEST = jax.lax.Precision.HIGHEST


def recurrence(q, k, v, g, beta, scale):
    """S' = Diag(e^g_t) S; S_t = S' - b_t k_t (k_t^T S') + b_t k_t v_t^T;
    o_t = S_t^T q_t scale. (B, T, H, .) in, (B, T, H, V) out, float32."""
    b, _t, h, dk = q.shape

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None] * s
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=HIGHEST)
        s = s + jnp.einsum("bhk,bhv->bhkv", k_t,
                           b_t[..., None] * (v_t - seen), precision=HIGHEST)
        return s, jnp.einsum("bhk,bhkv->bhv", q_t * scale, s,
                             precision=HIGHEST)

    _s, out = jax.lax.scan(
        step, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 1)


def _unit(x):
    return x / jnp.linalg.norm(x, axis=-1, keepdims=True)


def inputs(t, decay, seed=0, b=2, h=2, dk=32, dv=48):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = _unit(jax.random.normal(keys[0], (b, t, h, dk)))
    k = _unit(jax.random.normal(keys[1], (b, t, h, dk)))
    v = jax.random.normal(keys[2], (b, t, h, dv))
    beta = jax.nn.sigmoid(jax.random.normal(keys[3], (b, t, h)))
    raw = jax.random.normal(keys[4], (b, t, h, dk))
    if decay == "none":
        g = jnp.zeros_like(raw)
    elif decay == "strong":
        # half the channels lose e^-3..e^-5 a token: -190..-320 a chunk
        g = -jax.nn.softplus(raw) * jnp.where(jnp.arange(dk) % 2, 0.1, 1.0) \
            - jnp.where(jnp.arange(dk) % 2, 0.0, 3.0)
    else:
        g = -0.3 * jax.nn.softplus(raw)
    return q, k, v, g, beta


@pytest.mark.parametrize("decay", ["typical", "strong", "none"])
@pytest.mark.parametrize("t", [192, 256])
def test_chunked_form_equals_the_recurrence_in_value_and_gradients(t, decay):
    args = inputs(t, decay)
    scale = args[0].shape[-1] ** -0.5
    if decay == "strong":
        per_chunk = jnp.sum(args[3][:, :la.CHUNK], axis=1)
        assert float(per_chunk.min()) < -100.0
    cot = jax.random.normal(jax.random.PRNGKey(9), args[2].shape)
    want = recurrence(*args, scale)
    got = la.kda_attention(*args, scale=scale)
    assert bool(jnp.all(jnp.isfinite(got)))
    # float32 throughout; the solve and the 64-token sums reorder the
    # recurrence's additions, nothing else
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    mine = jax.grad(lambda *a: jnp.sum(la.kda_attention(*a, scale=scale)
                                       * cot), range(5))(*args)
    ref = jax.grad(lambda *a: jnp.sum(recurrence(*a, scale) * cot),
                   range(5))(*args)
    for name, a, b in zip("q k v g beta".split(), mine, ref):
        assert bool(jnp.all(jnp.isfinite(a))), name
        norm = float(jnp.linalg.norm(b))
        assert float(jnp.linalg.norm(a - b)) <= 2e-4 * norm + 1e-6, name


def test_a_sequence_that_is_no_whole_number_of_chunks():
    args = inputs(150, "typical", seed=3)
    np.testing.assert_allclose(la.kda_attention(*args),
                               recurrence(*args, 32 ** -0.5),
                               rtol=2e-4, atol=2e-5)


def test_bfloat16_inputs_stay_near_the_float32_recurrence():
    args = inputs(128, "typical", seed=5)
    want = recurrence(*args, 32 ** -0.5)
    low = [a.astype(jnp.bfloat16) for a in args[:3]] + [args[3], args[4]]
    got = la.kda_attention(*low)
    assert got.dtype == jnp.bfloat16
    err = jnp.linalg.norm(got.astype(jnp.float32) - want) \
        / jnp.linalg.norm(want)
    assert float(err) < 0.02


def test_plan_says_what_a_call_will_do():
    plan = la.plan((2, 8192, 16, 128))
    assert plan["chunks"] == 128 and plan["chunk"] == 64
    assert plan["sub_block"] == 16 and plan["padded"] == 0
    assert la.plan((1, 150, 2, 32))["padded"] == 42


def test_shape_rules():
    rule = get_shape_rule("kda_attention")
    good = {"Q": [TensorMeta((2, 64, 4, 32), "bfloat16")],
            "K": [TensorMeta((2, 64, 4, 32), "bfloat16")],
            "V": [TensorMeta((2, 64, 4, 48), "bfloat16")],
            "G": [TensorMeta((2, 64, 4, 32), "float32")],
            "Beta": [TensorMeta((2, 64, 4), "bfloat16")]}
    out = rule(None, good, {})["Out"][0]
    assert out.shape == (2, 64, 4, 48) and out.dtype == "bfloat16"
    with pytest.raises(ShapeError):
        rule(None, dict(good, Beta=[TensorMeta((2, 64, 5), "bfloat16")]), {})
    out = get_shape_rule("head_l2_norm")(
        None, {"X": [TensorMeta((2, 64, 128), "bfloat16")]},
        {"head_dim": 32})["Out"][0]
    assert out.shape == (2, 64, 4, 32)
    out = get_shape_rule("kda_gate")(
        None, {"X": [TensorMeta((2, 64, 128), "bfloat16")]},
        {"head_dim": 32})["Out"][0]
    assert out.shape == (2, 64, 4, 32) and out.dtype == "float32"
    with pytest.raises(ShapeError):
        get_shape_rule("kda_out_norm")(
            None, {"X": [TensorMeta((2, 64, 4, 32), "bfloat16")],
                   "Gate": [TensorMeta((2, 64, 96), "bfloat16")]}, {})


def test_the_elementwise_ops_of_a_kda_layer():
    keys = jax.random.split(jax.random.PRNGKey(1), 5)
    x = jax.random.normal(keys[0], (2, 8, 4 * 16))
    got = get_op("head_l2_norm").fn(None, {"X": [x]}, {"head_dim": 16})["Out"]
    xh = x.reshape(2, 8, 4, 16)
    np.testing.assert_allclose(
        got, xh / jnp.sqrt(jnp.sum(xh * xh, -1, keepdims=True) + 1e-6),
        rtol=1e-5, atol=1e-6)
    a_log = jax.random.normal(keys[1], (4,))
    dt_bias = jax.random.normal(keys[2], (64,))
    g = get_op("kda_gate").fn(None, {"X": [x], "ALog": [a_log],
                                     "DtBias": [dt_bias]},
                              {"head_dim": 16})["Out"]
    want = -jnp.exp(a_log)[None, None, :, None] * jax.nn.softplus(
        (x + dt_bias).reshape(2, 8, 4, 16))
    np.testing.assert_allclose(g, want, rtol=1e-5, atol=1e-6)
    assert float(g.max()) <= 0.0
    o = jax.random.normal(keys[3], (2, 8, 4, 16))
    scale = 1.0 + 0.1 * jax.random.normal(keys[4], (16,))
    got = get_op("kda_out_norm").fn(
        None, {"X": [o], "Gate": [x], "Scale": [scale]},
        {"epsilon": 1e-5})["Out"]
    want = (o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5)
            * scale).reshape(2, 8, 64) * jax.nn.sigmoid(x)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_the_op_through_a_program_trains_its_inputs_projections():
    """`layers.kda_attention` in a Program: the gradient reaches every
    parameter of the layer and a few Adam steps lower a regression loss."""
    from paddle_tpu import optimizer
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [2, 64, 32], dtype="float32",
                        append_batch_size=False)
        y = layers.data("y", [2, 64, 32], dtype="float32",
                        append_batch_size=False)
        out = layers.kda_attention(x, num_heads=2, head_dim=16,
                                   gate_rank=8, name="kda")
        loss = layers.mean(layers.square(layers.elementwise_sub(out, y)))
        optimizer.Adam(1e-2).minimize(loss)
    names = {p.name for p in main.global_block().all_parameters()}
    assert {"kda_qkv.w_0", "kda_qkv_conv.w_0", "kda_f_a.w_0", "kda_f_b.w_0",
            "kda_A_log", "kda_dt_bias", "kda_beta.w_0", "kda_g_a.w_0",
            "kda_g_b.w_0", "kda_o_norm_s", "kda_out.w_0"} == names
    exe = pt.Executor()
    exe.run(startup)
    rng = np.random.default_rng(0)
    feed = {"x": rng.standard_normal((2, 64, 32)).astype("float32"),
            "y": rng.standard_normal((2, 64, 32)).astype("float32")}
    losses = [float(exe.run(main, feed=feed,
                            fetch_list=[loss])[0].reshape(-1)[0])
              for _ in range(12)]
    assert np.isfinite(losses).all() and losses[-1] < 0.9 * losses[0]
