"""`kda_attention` (the chunked gated delta rule, ops/linear_attn_ops.py)
against the token-by-token recurrence in float32, in value and in all five
gradients: typical decay, a decay so strong that a chunk's cumulative
log-decay passes -100 (no exp of it may overflow, and a factored
e^{G_i} e^{-G_j} would), and no decay at all (the plain delta rule); a
sequence that is no whole number of chunks; the op through a Program; the
three elementwise ops of a KDA layer against `jax.numpy`. The two pieces of
the chunk math with a hand-written backward, each alone: the unit-lower
solve against `solve_triangular` and against jax's pullback of the plain
row-by-row spelling, the in-block decay products against `jax.grad` of the
plain products (both spellings live here as the references); no `scatter`
in the op's gradient; `tools/mb_kda_intra.py` starts."""
import os
import subprocess
import sys

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


def plain_inverse(low):
    """(I + low)^-1 the plain way, for jax to pull back: row i is e_i -
    low_i X, written into the array (PR 33's spelling of every row of a
    sub-block; here of the whole chunk)."""
    r = low.shape[-1]
    eye = jnp.eye(r, dtype=jnp.float32)
    x = jnp.broadcast_to(eye, low.shape)
    for i in range(1, r):
        row = eye[i] - jnp.sum(low[..., i, :, None] * x, axis=-2)
        x = x.at[..., i, :].set(row)
    return x


def plain_products(q_b, k_b, cum_b, scale):
    """kk_rs = sum_c k_rc k_sc e^{G_rc - G_sc} (r > s) and qk_rs = scale
    sum_c q_rc k_sc e^{G_rc - G_sc} (r >= s), for jax to pull back."""
    sub = q_b.shape[-2]
    within = jnp.tril(jnp.ones((sub, sub), bool))
    decay = jnp.exp(jnp.where(
        within[..., None], cum_b[..., :, None, :] - cum_b[..., None, :, :],
        -jnp.inf))
    kk = jnp.sum(k_b[..., :, None, :] * k_b[..., None, :, :] * decay,
                 axis=-1) * jnp.tril(jnp.ones((sub, sub), jnp.float32), -1)
    qk = jnp.sum(q_b[..., :, None, :] * k_b[..., None, :, :] * decay,
                 axis=-1) * scale
    return kk, qk


def chunk_blocks(decay, seed=0):
    """q, k and the cumulative decay of `inputs`' first chunks as (B, H,
    chunks, sub-blocks, SUB, K), and beta (B, H, chunks, CHUNK)."""
    q, k, _v, g, beta = inputs(2 * la.CHUNK, decay, seed=seed)

    def heads_first(x):
        x = jnp.moveaxis(x, 2, 1)                       # (B, H, T, ..)
        return x.reshape(x.shape[:2] + (2, la.CHUNK) + x.shape[3:])

    q, k, g, beta = (heads_first(x) for x in (q, k, g, beta))
    cum = jnp.cumsum(g, axis=-2)

    def blocks(x):
        return x.reshape(x.shape[:3] + (la.CHUNK // la.SUB, la.SUB,
                                        x.shape[-1]))

    return blocks(q), blocks(k), blocks(cum), beta


def lower_system(kind):
    """`low` (B, H, chunks, CHUNK, CHUNK), strictly lower: random numbers,
    or Diag(beta) A of the strong-decay inputs with every difference of
    the cumulative decay taken directly."""
    if kind == "random":
        raw = jax.random.normal(jax.random.PRNGKey(4),
                                (2, 2, 2, la.CHUNK, la.CHUNK))
        return 0.3 * jnp.tril(raw, -1)
    _q, k_b, cum_b, beta = chunk_blocks("strong")
    k, cum = (x.reshape(x.shape[:3] + (la.CHUNK, -1)) for x in (k_b, cum_b))
    kk, _qk = plain_products(k, k, cum, 1.0)
    return beta[..., None] * kk


@pytest.mark.parametrize("kind", ["random", "strong"])
def test_the_solve_equals_solve_triangular(kind):
    low = lower_system(kind)
    eye = jnp.broadcast_to(jnp.eye(la.CHUNK), low.shape)
    want = jax.scipy.linalg.solve_triangular(
        eye + low, eye, lower=True, unit_diagonal=True)
    got = la._unit_lower_inverse(low)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # upper triangle zero, diagonal one: to the bit
    assert bool(jnp.all(jnp.triu(got, 1) == 0.0))
    assert bool(jnp.all(jnp.diagonal(got, axis1=-2, axis2=-1) == 1.0))


@pytest.mark.parametrize("kind", ["random", "strong"])
def test_the_solves_pullback_reads_x_alone_and_equals_jaxs(kind):
    """d low = -strictly_lower(X^T dX X^T) against jax's pullback through
    the row updates of the plain spelling."""
    low = lower_system(kind)
    cot = jax.random.normal(jax.random.PRNGKey(8), low.shape)

    def loss(fn):
        return lambda l: jnp.sum(fn(jnp.tril(l, -1)) * cot)

    mine = jax.grad(loss(la._unit_lower_inverse))(low)
    ref = jax.grad(loss(plain_inverse))(low)
    assert bool(jnp.all(jnp.isfinite(mine)))
    assert bool(jnp.all(jnp.triu(mine) == 0.0))
    norm = float(jnp.linalg.norm(ref))
    assert float(jnp.linalg.norm(mine - ref)) <= 2e-4 * norm + 1e-6


@pytest.mark.parametrize("decay", ["typical", "strong", "none"])
def test_the_decay_products_backward_equals_jaxs_of_the_plain_products(
        decay):
    q_b, k_b, cum_b, _beta = chunk_blocks(decay, seed=2)
    scale = q_b.shape[-1] ** -0.5
    if decay == "strong":
        assert float((cum_b[..., -1, :] - cum_b[..., 0, :]).min()) < -40.0
    want = plain_products(q_b, k_b, cum_b, scale)
    got = la._decay_products(q_b, k_b, cum_b, scale)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6)
    cots = [jax.random.normal(key, want[0].shape)
            for key in jax.random.split(jax.random.PRNGKey(6))]

    def loss(fn):
        return lambda *a: sum(jnp.sum(o * c)
                              for o, c in zip(fn(*a, scale), cots))

    mine = jax.grad(loss(la._decay_products), range(3))(q_b, k_b, cum_b)
    ref = jax.grad(loss(plain_products), range(3))(q_b, k_b, cum_b)
    for name, a, b in zip("q k cum".split(), mine, ref):
        assert bool(jnp.all(jnp.isfinite(a))), name
        norm = float(jnp.linalg.norm(b))
        assert float(jnp.linalg.norm(a - b)) <= 2e-4 * norm + 1e-6, name


def _primitives(jaxpr, seen):
    for eqn in jaxpr.eqns:
        seen.add(eqn.primitive.name)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, seen)
    return seen


def test_the_ops_gradient_holds_no_scatter_at_any_depth():
    """Neither the forward nor the backward writes rows into an array or
    pulls back an integer index: the jaxpr of the gradient, through every
    scan body, custom_vjp rule and closed call, has no scatter (and no
    gather, whose pullback would be one)."""
    args = inputs(150, "typical")
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(la.kda_attention(*a)), range(5)))(*args)
    seen = _primitives(jaxpr.jaxpr, set())
    assert "scan" in seen and "dot_general" in seen     # it walked inside
    assert not {p for p in seen if "scatter" in p or "gather" in p}, seen


def test_the_microbenchmark_of_the_sub_block_math_starts():
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "mb_kda_intra.py")
    done = subprocess.run(
        [sys.executable, tool, "--help"], capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]
    assert "--walk-through" in done.stdout


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


@pytest.mark.parametrize("path", ["xla", "pallas"])
def test_plan_names_the_solve_and_the_two_hand_written_backwards(path):
    """Both plans: the XLA form's line (off the TPU, or shapes the kernels
    do not tile) and the `kda_*` kernels' (`delta_rule.plan`)."""
    from paddle_tpu.ops.pallas import delta_rule
    found = delta_rule.plan((2, 8192, 16, 128), 128, 2) \
        if path == "pallas" else None
    plan = la.plan((2, 8192, 16, 128), found)
    kernels = plan["kernels"]
    assert "solve: " in kernels and "X^T dX X^T" in kernels
    assert "products: " in kernels and "by hand" in kernels
    if path == "xla":
        assert kernels.startswith("xla: ")
        assert "decay products: " in kernels and "vmem_bwd" not in plan
        assert la.plan((2, 8192, 16, 128)) == plan
        assert la.plan((2, 8192, 16, 32), delta_rule.plan(
            (2, 8192, 16, 32), 32, 2))["kernels"] == kernels
    else:
        assert kernels.startswith("pallas: kda_fwd, kda_bwd; ")
        assert plan["heads_a_step"] == 8 and plan["levels"] == 6
        assert plan["chunk"] == 64 and plan["sub_block"] == 16
        assert plan["vmem_fwd"] < plan["vmem_bwd"] < 64 * 2 ** 20


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
