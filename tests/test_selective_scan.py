"""The selective-scan kernels (interpret mode) against the chunked XLA scan
and a float64 step-by-step oracle; the `selective_scan`, `causal_conv1d` and
`rms_norm` ops through a Program with their gradients; the chunk rule and
`ssm.plan`; and the cotangents of a value that several rematerialised
segments read (the memory M and the shared K*, V* of a cross-decoder)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.framework import obs
from paddle_tpu.framework.backward import append_backward
from paddle_tpu.ops.pallas import selective_scan as ss


def _inputs(b, t, e, n, dtype=jnp.float32, seed=0):
    key = jax.random.PRNGKey(seed)

    def normal(i, shape):
        return jax.random.normal(jax.random.fold_in(key, i), shape,
                                 jnp.float32)

    args = (normal(1, (b, t, e)).astype(dtype),
            jax.nn.softplus(normal(2, (b, t, e))).astype(dtype),
            -jnp.exp(0.5 * normal(3, (e, n))),
            normal(4, (b, t, n)).astype(dtype),
            normal(5, (b, t, n)).astype(dtype), normal(6, (e,)))
    return args, normal(7, (b, t, e))


def _value_and_grads(fn, args, w):
    return jax.jit(jax.value_and_grad(
        lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
        tuple(range(6))))(*args)


def _oracle(x, delta, a, bm, c, d):
    """The recurrence one step at a time in float64 numpy."""
    x, delta, a, bm, c, d = (np.asarray(z, np.float64)
                             for z in (x, delta, a, bm, c, d))
    b, t, e = x.shape
    h = np.zeros((b, e, a.shape[1]))
    y = np.zeros((b, t, e))
    for i in range(t):
        h = np.exp(delta[:, i, :, None] * a) * h \
            + (delta[:, i] * x[:, i])[..., None] * bm[:, i, None, :]
        y[:, i] = (h * c[:, i, None, :]).sum(-1) + d * x[:, i]
    return y


# T a multiple of the chunk, not a multiple (padded), one chunk, and the
# chunk the rule picks; N = 8 and 16; one and two channel blocks
@pytest.mark.parametrize("b,t,e,n,chunk", [
    (2, 32, 128, 8, 16), (1, 40, 256, 16, 16), (2, 32, 128, 8, 32),
    (1, 24, 128, 8, None), (1, 72, 640, 16, 8)])
def test_kernels_equal_the_xla_scan_forward_and_every_gradient(b, t, e, n,
                                                               chunk):
    args, w = _inputs(b, t, e, n)
    got = _value_and_grads(lambda *a: ss.selective_scan(
        *a, chunk=chunk, interpret=True), args, w)
    want = _value_and_grads(lambda *a: ss.scan_xla(*a, chunk=8), args, w)
    assert abs(float(got[0] - want[0])) <= 1e-5 * abs(float(want[0]))
    for name, g, r in zip("x delta A B C D".split(), got[1], want[1]):
        err = float(jnp.max(jnp.abs(g - r)) / (jnp.max(jnp.abs(r)) + 1e-9))
        assert err < 1e-5, (name, err)


def test_bfloat16_inputs_keep_a_float32_state():
    args, _w = _inputs(1, 48, 128, 8, jnp.bfloat16, seed=2)
    got = ss.selective_scan(*args, chunk=16, interpret=True)
    assert got.dtype == jnp.bfloat16
    want = _oracle(*(np.asarray(z.astype(jnp.float32)) for z in args))
    # the state never rounds to bfloat16: only the output does
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)), want,
                               rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("chunk", [4, 64])
def test_the_xla_scan_equals_the_step_by_step_oracle(chunk):
    args, _w = _inputs(2, 20, 12, 3, seed=1)
    np.testing.assert_allclose(ss.scan_xla(*args, chunk=chunk),
                               _oracle(*args), rtol=2e-5, atol=2e-5)


def test_off_the_tpu_the_entry_takes_the_xla_scan():
    args, _w = _inputs(1, 16, 128, 8)
    text = str(jax.make_jaxpr(lambda *a: ss.selective_scan(*a))(*args))
    assert "pallas_call" not in text and "scan" in text
    with pytest.raises(ValueError, match="do not fit"):
        ss.selective_scan(args[0], args[1][:, :8], *args[2:])


def test_the_chunk_rule_and_the_plan():
    # the benchmark's shape: 128 steps a chunk fit 24 MiB, 256 do not
    assert ss.pick_channel_block(5120) == 512
    assert ss.pick_chunk(8192, 512, 16, 2) == 128
    assert ss.vmem_bytes("bwd", 256, 512, 16, 2) > ss._VMEM_BUDGET \
        >= ss.vmem_bytes("bwd", 128, 512, 16, 2)
    assert ss.pick_chunk(32, 128, 8, 4) == 32      # no longer than T
    assert ss.pick_chunk(40, 128, 8, 4) == 64
    plan = ss.plan((1, 8192, 5120), 16, 2)
    assert (plan["chunk"], plan["chunks"], plan["channel_block"]) \
        == (128, 64, 512)
    assert plan["vmem_bwd"] > plan["vmem_fwd"]
    assert ss.plan((1, 64, 100), 16, 4) is None     # E % 128: XLA scan
    assert ss.plan((1, 64, 128), 4, 4) is None      # N % 8: XLA scan


def test_a_lowering_records_one_ssm_plan_while_obs_is_on():
    args, _w = _inputs(1, 32, 128, 8)
    obs.clear()
    obs.enable()
    try:
        jax.make_jaxpr(lambda *a: ss.selective_scan(*a, interpret=True))(
            *args)
        plans = obs.spans(name="ssm.plan")
    finally:
        obs.disable()
        obs.clear()
    assert len(plans) == 1
    assert plans[0]["labels"]["chunk"] == 32
    assert plans[0]["labels"]["chunks"] == 1


# ---------------------------------------------------------------------------
# the ops through a Program
# ---------------------------------------------------------------------------

def _run(build, feed):
    """Build under a fresh program pair, run once; returns {name: value}
    of what `build` returned."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        fetch = build()
    exe = pt.Executor()
    exe.run(startup)
    names = sorted(fetch)
    out = exe.run(main, feed=feed, fetch_list=[fetch[n] for n in names])
    return dict(zip(names, out))


def test_the_selective_scan_op_and_its_gradients():
    args, _w = _inputs(2, 16, 8, 4, seed=3)
    names = ["x", "delta", "a", "b", "c", "d"]

    def build():
        ins = [layers.data(n, list(z.shape), dtype="float32",
                           append_batch_size=False)
               for n, z in zip(names, args)]
        for v in ins:
            v.stop_gradient = False
        y = layers.selective_scan(*ins)
        loss = layers.reduce_sum(layers.elementwise_mul(y, y))
        grads = pt.gradients([loss], ins)
        return dict({"y": y}, **{"d" + n: g for n, g in zip(names, grads)})

    got = _run(build, {n: np.asarray(z) for n, z in zip(names, args)})
    np.testing.assert_allclose(got["y"], _oracle(*args), rtol=2e-5,
                               atol=2e-5)
    want = jax.grad(lambda *a: jnp.sum(ss.scan_xla(*a) ** 2),
                    tuple(range(6)))(*args)
    for n, g in zip(names, want):
        np.testing.assert_allclose(got["d" + n], g, rtol=1e-4, atol=1e-5)


def test_causal_conv1d_reads_only_the_past():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 10, 6).astype(np.float32)

    def build():
        xv = layers.data("x", [2, 10, 6], dtype="float32",
                         append_batch_size=False)
        xv.stop_gradient = False
        out = layers.causal_conv1d(
            xv, 4, param_attr=pt.ParamAttr(name="cw"),
            bias_attr=pt.ParamAttr(name="cb"))
        w = pt.default_main_program().global_block().var("cw")
        loss = layers.reduce_sum(layers.elementwise_mul(out, out))
        dx, dw = pt.gradients([loss], [xv, w])
        return {"out": out, "w": w, "dx": dx, "dw": dw}

    got = _run(build, {"x": x})
    w = np.asarray(got["w"])
    padded = np.pad(x, ((0, 0), (3, 0), (0, 0)))
    want = sum(padded[:, i:i + 10] * w[i] for i in range(4))
    np.testing.assert_allclose(got["out"], want, rtol=1e-5, atol=1e-6)
    # out[:, t] does not move with x[:, t+1:]
    later = x.copy()
    later[:, 6:] += 1.0
    np.testing.assert_allclose(
        sum(np.pad(later, ((0, 0), (3, 0), (0, 0)))[:, i:i + 10] * w[i]
            for i in range(4))[:, :6], want[:, :6])

    def f(x, w):
        p = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
        return jnp.sum(sum(p[:, i:i + 10] * w[i] for i in range(4)) ** 2)

    dx, dw = jax.grad(f, (0, 1))(x, w)
    np.testing.assert_allclose(got["dx"], dx, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got["dw"], dw, rtol=1e-4, atol=1e-5)


def test_rms_norm_scales_by_the_root_mean_square():
    rng = np.random.RandomState(1)
    x = rng.randn(3, 5, 8).astype(np.float32)

    def build():
        xv = layers.data("x", [3, 5, 8], dtype="float32",
                         append_batch_size=False)
        return {"y": layers.rms_norm(xv, epsilon=1e-5,
                                     param_attr=pt.ParamAttr(name="g")),
                "plain": layers.rms_norm(xv, param_attr=False)}

    got = _run(build, {"x": x})
    want = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got["y"], want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(got["plain"], want, rtol=1e-5, atol=1e-6)


def test_shape_rules_name_a_wrong_operand():
    from paddle_tpu.ops.registry import get_shape_rule
    from paddle_tpu.ops.shape_rules import ShapeError, TensorMeta

    def metas(**shapes):
        return {k: [TensorMeta(v, "float32")] for k, v in shapes.items()}

    scan = get_shape_rule("selective_scan")
    good = metas(X=(2, 16, 8), Delta=(2, 16, 8), A=(8, 4), B=(2, 16, 4),
                 C=(2, 16, 4), D=(8,))
    assert scan(None, good, {})["Out"][0].shape == (2, 16, 8)
    with pytest.raises(ShapeError):
        scan(None, dict(good, A=[TensorMeta((8, 5), "float32")]), {})
    conv = get_shape_rule("causal_conv1d")
    assert conv(None, metas(X=(2, 16, 8), W=(4, 8)), {})["Out"][0].shape \
        == (2, 16, 8)
    with pytest.raises(ShapeError):
        conv(None, metas(X=(2, 16, 8), W=(4, 9)), {})
    assert get_shape_rule("rms_norm")(None, metas(X=(2, 8)), {})[
        "Y"][0].shape == (2, 8)


# ---------------------------------------------------------------------------
# one value read by several rematerialised segments
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("consumers", [2, 3])
def test_cotangents_of_a_shared_segment_output_add_up(consumers):
    """A first segment makes (h, m); every later segment reads the running
    h AND the same m, as the cross-decoder reads M and K*, V*. The
    program's parameter gradients equal jax.grad of the same function."""
    rng = np.random.RandomState(2)
    x = rng.randn(4, 6).astype(np.float32)
    names = ["w_first", "w_mem"] + ["w_%d" % i for i in range(consumers)]

    def build():
        xv = layers.data("x", [4, 6], dtype="float32",
                         append_batch_size=False)

        def first(h):
            return [layers.fc(h, 6, param_attr=pt.ParamAttr(name="w_first"),
                              bias_attr=False, act="tanh"),
                    layers.fc(h, 6, param_attr=pt.ParamAttr(name="w_mem"),
                              bias_attr=False, act="sigmoid")]

        h, m = layers.recompute_segment(first, [xv])
        for i in range(consumers):
            h = layers.recompute_segment(
                lambda h_, m_, i=i: layers.elementwise_mul(
                    layers.fc(h_, 6, param_attr=pt.ParamAttr(
                        name="w_%d" % i), bias_attr=False, act="tanh"), m_),
                [h, m])
        loss = layers.reduce_sum(layers.elementwise_mul(h, h))
        grads = dict((p.name, g) for p, g in append_backward(loss))
        block = pt.default_main_program().global_block()
        out = {"loss": loss}
        for n in names:
            out[n] = block.var(n)
            out["d" + n] = grads[n]
        return out

    got = _run(build, {"x": x})

    def f(ws):
        h, m = jnp.tanh(x @ ws["w_first"]), jax.nn.sigmoid(x @ ws["w_mem"])
        for i in range(consumers):
            h = jnp.tanh(h @ ws["w_%d" % i]) * m
        return jnp.sum(h * h)

    ws = {n: jnp.asarray(got[n]) for n in names}
    np.testing.assert_allclose(got["loss"].reshape(()), f(ws), rtol=1e-5)
    want = jax.grad(f)(ws)
    for n in names:
        np.testing.assert_allclose(got["d" + n], want[n], rtol=1e-4,
                                   atol=1e-6, err_msg=n)
