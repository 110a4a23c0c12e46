"""The ops the `nemotron_h` stack brought, at a tiny size: the `mamba2_scan`
op against the step-by-step recurrence (forward and every gradient; T no
multiple of the chunk, across a chunk boundary, shorter than a chunk;
bfloat16 in), `mamba2_gate_norm` against its formula, the grouped-matmul
kernels in interpret mode at a width that is a multiple of 64 and not of
128, and non-gated relu^2 experts through `moe_ffn`. The model through the
normal path is in `test_nemotron_h.py`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.framework import obs
from paddle_tpu.framework.scope import Scope
from paddle_tpu.ops import moe_ops, ssm_ops
from paddle_tpu.ops.pallas import grouped_matmul as gm
from paddle_tpu.ops.registry import get_op


# ---------------------------------------------------------------------------
# the scan op
# ---------------------------------------------------------------------------

def _recurrent(x, dt, dt_bias, a_log, b, c, d):
    """S_t = a_t S_{t-1} + dt_t B_t x_t^T, y_t = S_t^T C_t + D x_t, one
    token at a time (no chunk anywhere)."""
    bsz, _t, h, p = x.shape
    g, n = b.shape[2:]
    step = jax.nn.softplus(dt + dt_bias)
    a = jnp.exp(-step * jnp.exp(a_log))
    bh, ch = (jnp.repeat(m, h // g, axis=2) for m in (b, c))

    def f(s, now):
        x_t, dt_t, a_t, b_t, c_t = now
        s = a_t[..., None, None] * s \
            + (dt_t[..., None] * b_t)[..., :, None] * x_t[..., None, :]
        return s, jnp.sum(s * c_t[..., :, None], axis=-2)

    _s, ys = jax.lax.scan(f, jnp.zeros((bsz, h, n, p)), tuple(
        m.swapaxes(0, 1) for m in (x, step, a, bh, ch)))
    return ys.swapaxes(0, 1) + d[None, None, :, None] * x


def _scan_inputs(t, seed=0, h=4, p=8, g=2, n=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    return (jax.random.normal(k[0], (2, t, h, p)),
            jax.random.normal(k[1], (2, t, h)),
            0.5 * jax.random.normal(k[2], (h,)),
            0.5 * jax.random.normal(k[3], (h,)),
            jax.random.normal(k[4], (2, t, g, n)),
            jax.random.normal(k[5], (2, t, g, n)),
            jax.random.normal(k[6], (h,))), \
        jax.random.normal(k[7], (2, t, h, p))


@pytest.mark.parametrize("t,chunk", [(40, 16), (37, 16), (32, 16), (5, 16),
                                     (130, 128)])
def test_the_chunked_scan_is_the_step_by_step_recurrence(t, chunk):
    """Forward and the gradient of every input, T across chunk boundaries
    and no multiple of the chunk: the chunked form and its hand-written
    backward over the chunks' states against jax's own pullback of the
    recurrence, to float32 rounding."""
    args, weight = _scan_inputs(t, seed=t)
    got = jax.jit(lambda *a: ssm_ops.mamba2_scan(*a, chunk=chunk))(*args)
    want = jax.jit(_recurrent)(*args)
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) \
        <= 1e-5 * float(jnp.max(jnp.abs(want)))
    mine = jax.jit(jax.grad(lambda *a: jnp.sum(
        ssm_ops.mamba2_scan(*a, chunk=chunk) * weight), range(7)))(*args)
    theirs = jax.jit(jax.grad(lambda *a: jnp.sum(_recurrent(*a) * weight),
                              range(7)))(*args)
    for name, m, w in zip(("x", "dt", "dt_bias", "A_log", "B", "C", "D"),
                          mine, theirs):
        assert float(jnp.max(jnp.abs(m - w))) \
            <= 2e-4 * float(jnp.max(jnp.abs(w))), name


def test_a_long_decay_does_not_leave_float32():
    """A chunk whose cumulative log-decay passes -100: every decay is
    e^{L_i - L_j} with i >= j, so nothing overflows and the result is still
    the recurrence's."""
    args, _w = _scan_inputs(48, seed=3)
    args = list(args)
    args[1] = args[1] + 4.0             # dt ~ 4 a token
    args[3] = jnp.full((4,), 1.0)       # A = -e
    got = ssm_ops.mamba2_scan(*args, chunk=16)
    want = _recurrent(*args)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert float(jnp.max(jnp.abs(got - want))) \
        <= 1e-5 * float(jnp.max(jnp.abs(want)))


def test_bfloat16_in_gives_bfloat16_out_near_the_float32_result():
    args, _w = _scan_inputs(40, seed=5)
    low = [a.astype(jnp.bfloat16) if a.ndim == 4 else a for a in args]
    got = ssm_ops.mamba2_scan(*low, chunk=16)
    assert got.dtype == jnp.bfloat16
    want = _recurrent(*[a.astype(jnp.float32) for a in low])
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) \
        <= 0.03 * float(jnp.max(jnp.abs(want)))


def test_the_ops_are_registered_with_shape_rules_and_a_plan_record():
    from paddle_tpu.ops.shape_rules import ShapeError, TensorMeta
    from paddle_tpu.ops.registry import get_shape_rule
    rule = get_shape_rule("mamba2_scan")

    def ins(h=4, g=2):
        m = lambda *s: [TensorMeta(s, "float32")]
        return {"X": m(2, 40, h, 8), "Dt": m(2, 40, h), "DtBias": m(h),
                "ALog": m(h), "D": m(h), "B": m(2, 40, g, 16),
                "C": m(2, 40, g, 16)}

    out = rule(None, ins(), {})["Out"][0]
    assert tuple(out.shape) == (2, 40, 4, 8)
    with pytest.raises(ShapeError, match="multiple of G"):
        rule(None, ins(g=3), {})
    args, _w = _scan_inputs(40)
    obs.clear()
    obs.enable()
    try:
        get_op("mamba2_scan").fn(None, {
            "X": [args[0]], "Dt": [args[1]], "DtBias": [args[2]],
            "ALog": [args[3]], "B": [args[4]], "C": [args[5]],
            "D": [args[6]]}, {"chunk_size": 16})
        plans = obs.spans(name="ssd.plan")
    finally:
        obs.disable()
        obs.clear()
    assert len(plans) == 1
    assert {k: plans[0]["labels"][k] for k in (
        "batch", "seq", "heads", "head_dim", "groups", "state", "chunk",
        "chunks", "padded")} == {
            "batch": 2, "seq": 40, "heads": 4, "head_dim": 8, "groups": 2,
            "state": 16, "chunk": 16, "chunks": 3, "padded": 8}
    from paddle_tpu import profiler
    assert "ssd.plan" in profiler.PLAN_RECORDS


def test_the_plan_record_names_the_kernels_where_a_tpu_call_takes_them(
        monkeypatch):
    """Traced as a TPU process would trace it (nothing is lowered): at a
    shape the kernels tile, `ssd.plan` keeps its keys and gains the
    kernels' line, the heads a grid step holds and its VMEM bytes; off the
    TPU the same call records the XLA form."""
    args, _w = _scan_inputs(300, h=8, p=64, g=2, n=128)
    low = [a.astype(jnp.bfloat16) if a.ndim == 4 else a for a in args]

    def plan_of():
        obs.clear()
        obs.enable()
        try:
            out = jax.eval_shape(      # a fresh function: traced anew
                lambda *a: ssm_ops.mamba2_scan(*a), *low)
            return out, obs.spans(name="ssd.plan")[0]["labels"]
        finally:
            obs.disable()
            obs.clear()

    out, off = plan_of()
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "0")
    same, on = plan_of()
    assert out == same and out.shape == (2, 300, 8, 64)
    assert off["kernels"].startswith("xla: ")
    assert on["kernels"].startswith("pallas: ssd_fwd, ssd_bwd; ")
    assert {k: v for k, v in on.items() if k in off and k != "kernels"} \
        == {k: v for k, v in off.items() if k != "kernels"}
    assert (on["chunks"], on["padded"], on["state_bytes_kept"]) \
        == (3, 84, 4 * 2 * 3 * 8 * 128 * 64)
    assert on["heads_a_step"] == 4
    assert 0 < on["vmem_fwd"] < on["vmem_bwd"] < 2 ** 27
    assert set(on) - set(off) == {"heads_a_step", "vmem_fwd", "vmem_bwd"}


def test_the_gate_norm_gates_first_then_norms_each_group():
    rng = np.random.default_rng(1)
    x, z = (rng.standard_normal((2, 5, 32)).astype(np.float32)
            for _ in range(2))
    scale = rng.standard_normal(32).astype(np.float32)
    got = get_op("mamba2_gate_norm").fn(
        None, {"X": [jnp.asarray(x)], "Z": [jnp.asarray(z)],
               "Scale": [jnp.asarray(scale)]},
        {"groups": 4, "epsilon": 1e-5})["Y"]
    y = (x * (z / (1.0 + np.exp(-z)))).reshape(2, 5, 4, 8)
    y = y / np.sqrt((y ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(np.asarray(got), y.reshape(2, 5, 32) * scale,
                               rtol=2e-5, atol=2e-6)
    # one group of 32 is another result: the groups are live
    whole = get_op("mamba2_gate_norm").fn(
        None, {"X": [jnp.asarray(x)], "Z": [jnp.asarray(z)],
               "Scale": [jnp.asarray(scale)]}, {"groups": 1})["Y"]
    assert float(jnp.max(jnp.abs(whole - got))) > 1e-2


# ---------------------------------------------------------------------------
# the grouped matmuls off the 128-lane grid, and the non-gated experts
# ---------------------------------------------------------------------------

def test_plan_gives_a_width_off_the_lane_grid_its_whole_width_tile():
    """1856 = 14.5 x 128 has no multiple of 128 among its divisors: its one
    tile is the whole width, in all three kernels and on either side of the
    matmul, within the VMEM budget by the lanes the block really takes
    (15 x 128); the widths on the grid plan as they did."""
    rows = gm.buffer_rows(16384 * 6, 8, 512)
    assert rows == 102400
    w1 = gm.plan(rows, 2688, 1856, 512)
    w2 = gm.plan(rows, 1856, 2688, 512)
    assert w1 == (512, (1856, 896), (896, 1856), (896, 1856))
    assert w2 == (512, (896, 1856), (1856, 896), (1856, 896))
    for what in (w1, w2):
        for kernel in gm.KERNELS:
            assert gm.vmem_bytes(kernel, 512, getattr(what, kernel), 2) \
                <= gm._VMEM_BUDGET
    assert gm.vmem_bytes("fwd", 512, (1856, 896), 2) \
        == gm.vmem_bytes("fwd", 512, (1920, 896), 2)
    assert gm._divisors(1856) == [1856] and gm._divisors(256) == [128, 256]
    assert gm.plan(rows, 2048, 2816, 512) == (
        512, (1408, 2048), (1024, 2816), (2048, 1408))    # Kimi-VL's
    assert gm.plan(rows, 2688, 1857, 512) is None   # no multiple of 8


@pytest.mark.parametrize("k,n", [(128, 192), (192, 128), (64, 320)])
def test_the_kernels_at_a_width_of_64s_equal_the_xla_form(k, n):
    """Interpret mode, a width that is a multiple of 64 and not of 128, with
    an empty group and ragged group ends: forward, dX and dW against
    `grouped_matmul_xla` and its own pullback."""
    tm = 16
    sizes = jnp.asarray([20, 0, 7, 33], jnp.int32)
    rows = gm.buffer_rows(60, 4, tm)
    rng = np.random.default_rng(k + n)
    x = jnp.asarray(rng.standard_normal((rows, k)), jnp.float32)
    w = jnp.asarray(0.3 * rng.standard_normal((4, k, n)), jnp.float32)
    what = gm.plan(rows, k, n, tm, 4)
    assert what is not None and (n in what.fwd or k in what.fwd)
    lay = gm.layout(sizes, rows, tm)
    row = jnp.arange(rows)
    inside = ((row % tm) < lay["tile_end"][row // tm])[:, None]

    def kernels(x_, w_):
        return jnp.where(inside, gm.grouped_matmul(x_, w_, sizes, tm,
                                                   interpret=True), 0.0)

    def xla(x_, w_):
        return jnp.where(inside, gm.grouped_matmul_xla(x_, w_, sizes, tm),
                         0.0)

    got, pull = jax.vjp(kernels, x, w)
    want, pull_xla = jax.vjp(xla, x, w)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    dy = jnp.asarray(rng.standard_normal(got.shape), jnp.float32)
    (dx, dw), (dx_w, dw_w) = pull(dy), pull_xla(dy)
    np.testing.assert_allclose(jnp.where(inside, dx, 0.0),
                               jnp.where(inside, dx_w, 0.0),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dw, dw_w, rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(dw[1]))) == 0.0   # the empty group's


def test_with_obs_on_the_plan_record_shows_the_kernel_path():
    """`moe_gmm.plan` at the cell's two calls (K 2688 / N 1856 and the other
    way round), traced for their shapes alone."""
    obs.clear()
    obs.enable()
    try:
        for k, n in ((2688, 1856), (1856, 2688)):
            jax.eval_shape(
                lambda x, w, s: gm.grouped_matmul(x, w, s, 512,
                                                  interpret=True),
                jax.ShapeDtypeStruct((102400, k), jnp.bfloat16),
                jax.ShapeDtypeStruct((8, k, n), jnp.bfloat16),
                jax.ShapeDtypeStruct((8,), jnp.int32))
        plans = [p["labels"] for p in obs.spans(name="moe_gmm.plan")]
    finally:
        obs.disable()
        obs.clear()
    assert [(p["k"], p["n"], p["fwd_tiles"], p["dx_tiles"], p["dw_tiles"])
            for p in plans] == [
        (2688, 1856, "1856x896", "896x1856", "896x1856"),
        (1856, 2688, "896x1856", "1856x896", "1856x896")]
    assert all(p["fwd_reread"] < 3.0 for p in plans)


def _dense_relu2(x, w_r, w1, w2, k, held, scaling=2.5):
    """sum over the picks held of w_e W2_e relu(W1_e x)^2, densely."""
    first, count = held
    scores = jax.nn.sigmoid(jnp.dot(x, w_r, precision="highest"))
    _top, picks = jax.lax.top_k(scores, k)
    weights = jnp.take_along_axis(scores, picks, axis=1)
    weights = weights / (jnp.sum(weights, axis=1, keepdims=True) + 1e-6) \
        * scaling
    out = 0.0
    for e in range(count):
        gate = jnp.sum(jnp.where(picks == first + e, weights, 0.0), axis=1)
        act = jnp.square(jax.nn.relu(jnp.dot(x, w1[e], precision="highest")))
        out = out + gate[:, None] * jnp.dot(act, w2[e], precision="highest")
    return out


def test_moe_ffn_with_relu2_is_the_non_gated_layer_forward_and_backward():
    """One (count, d, F) leaf named `<name>_experts_up`, a `gate` attr of
    "relu2" on `moe_experts`, and the result and both gradients of the dense
    masked sum; F = 24 is no multiple of 16."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((48, 16)), jnp.float32)
    w_r = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    w1 = jnp.asarray(0.3 * rng.standard_normal((4, 16, 24)), jnp.float32)
    w2 = jnp.asarray(0.3 * rng.standard_normal((4, 24, 16)), jnp.float32)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        xv = layers.data("x", [48, 16], append_batch_size=False)
        xv.stop_gradient = False
        out, _load = layers.moe_ffn(xv, 8, 2, 24, experts_held=(2, 4),
                                    routed_scaling_factor=2.5, name="e",
                                    gate="relu2")
        loss = layers.reduce_sum(layers.square(out))
        grads = pt.gradients([loss], [xv])
    params = {p.name: tuple(p.shape)
              for p in main.global_block().all_parameters()}
    assert params == {"e_router.w_0": (16, 8), "e_experts_up": (4, 16, 24),
                      "e_experts_down": (4, 24, 16)}
    assert [op.attrs.get("gate") for op in main.global_block().ops
            if op.type == "moe_experts"] == ["relu2"]
    scope, exe = Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    for name, value in (("e_router.w_0", w_r), ("e_experts_up", w1),
                        ("e_experts_down", w2)):
        scope.set_var(name, jnp.copy(value))
    got = exe.run(main, feed={"x": np.asarray(x)},
                  fetch_list=[out] + list(grads), scope=scope)
    want = _dense_relu2(x, w_r, w1, w2, 2, (2, 4))
    np.testing.assert_allclose(got[0], want, rtol=1e-4, atol=1e-5)
    dx = jax.grad(lambda a: jnp.sum(_dense_relu2(a, w_r, w1, w2, 2,
                                                 (2, 4)) ** 2))(x)
    np.testing.assert_allclose(got[1], dx, rtol=1e-4, atol=1e-4)
    with pt.program_guard(pt.Program(), pt.Program()):
        xv = layers.data("x", [48, 16], append_batch_size=False)
        with pytest.raises(ValueError, match="gate"):
            layers.moe_ffn(xv, 8, 2, 24, gate="relu3")
    assert set(moe_ops.PLAIN) == {"relu2"} and not (
        set(moe_ops.PLAIN) & set(moe_ops.GATES))
