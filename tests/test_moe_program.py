"""`layers.moe_ffn` through a Program against the dense masked sum, with its
gradients; what it refuses; `moe_balance` and the loss-free bias update."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.ops.registry import get_op

from _moe_cases import _run, moe_dense


@pytest.mark.parametrize("held", [None, (4, 4)])
def test_moe_ffn_through_a_program_with_its_gradients(held):
    x = np.random.RandomState(0).randn(24, 16).astype(np.float32)
    first, count = held or (0, 8)

    def build():
        xv = layers.data("x", [24, 16], dtype="float32",
                         append_batch_size=False)
        xv.stop_gradient = False
        out, load = layers.moe_ffn(xv, 8, 2, 8, experts_held=held,
                                   name="moe")
        layers.moe_balance(load, "moe", held)
        block = pt.default_main_program().global_block()
        params = [block.var(n) for n in ("moe_router.w_0",
                                         "moe_experts_gate_up",
                                         "moe_experts_down")]
        loss = layers.reduce_sum(layers.elementwise_mul(out, out))
        grads = pt.gradients([loss], [xv] + params)
        return dict({"out": out, "load": block.var("moe_expert_load"),
                     "bias": block.var("moe_expert_bias")},
                    **{"w%d" % i: v for i, v in enumerate(params)},
                    **{"g%d" % i: g for i, g in enumerate(grads)})

    got, main = _run(build, {"x": x})
    names = {p.name for p in main.global_block().all_parameters()}
    assert names == {"moe_router.w_0", "moe_experts_gate_up",
                     "moe_experts_down"}        # the bias is no Parameter
    assert got["w1"].shape == (count, 16, 16) and got["w2"].shape \
        == (count, 8, 16) and got["w0"].dtype == np.float32
    assert (got["bias"] == 0).all() and got["bias"].shape == (8,)
    args = (jnp.asarray(x), got["w0"], got["bias"], got["w1"], got["w2"])
    want = moe_dense(*args, 2, (first, count))
    np.testing.assert_allclose(got["out"], want, rtol=1e-4, atol=1e-5)
    picks = jax.lax.top_k(jax.nn.sigmoid(x @ got["w0"]), 2)[1]
    assert list(got["load"]) == [int((picks == e).sum()) for e in range(8)]
    ref = jax.grad(lambda *a: jnp.sum(moe_dense(*a, 2, (first, count))
                                      ** 2), (0, 1, 3, 4))(*args)
    for i, r in enumerate(ref):
        np.testing.assert_allclose(got["g%d" % i], r, rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("kw,match", [
    (dict(experts_held=(6, 4)), "no range"),
    (dict(experts_held=(0, 0)), "no range"),
    (dict(top_k=9), "top_k 9")])
def test_moe_ffn_refuses_what_it_cannot_hold(kw, match):
    with pt.program_guard(pt.Program(), pt.Program()):
        xv = layers.data("x", [8, 16], dtype="float32",
                         append_batch_size=False)
        with pytest.raises(ValueError, match=match):
            layers.moe_ffn(xv, 8, kw.pop("top_k", 2), 8, **kw)


def test_moe_balance_inside_a_segment_is_refused_by_name():
    with pt.program_guard(pt.Program(), pt.Program()):
        xv = layers.data("x", [8, 16], dtype="float32",
                         append_batch_size=False)

        def segment(h):
            out, load = layers.moe_ffn(h, 4, 2, 8, name="seg")
            layers.moe_balance(load, "seg")
            return out

        with pytest.raises(ValueError, match="where the segment's results"):
            layers.recompute_segment(segment, [xv])


def test_moe_bias_update_is_the_loss_free_balance_step():
    """+ rate under the mean load, - rate over it, nothing at it; the op
    takes no gradient."""
    from paddle_tpu.ops.registry import get_op
    op = get_op("moe_bias_update")
    assert not op.differentiable
    bias = jnp.asarray([0.0, 0.5, -0.25, 0.125], jnp.float32)
    load = jnp.asarray([10, 2, 6, 6], jnp.int32)        # mean 6
    out = op.fn(None, {"Bias": [bias], "Load": [load]}, {"rate": 0.01})
    np.testing.assert_allclose(out["Out"],
                               [-0.01, 0.51, -0.25, 0.125], atol=1e-7)


@pytest.mark.parametrize("recompute", [False, True])
def test_moe_balance_moves_the_bias_once_a_step_and_the_picks_follow(
        recompute):
    """A router that sends everything to experts 0 and 1: with the update
    on, the bias of the two falls and the others' rises a step, the
    forward pass (and its replay under recompute) reads the bias the step
    began with, and after enough steps the picks spread."""
    from paddle_tpu import optimizer
    from paddle_tpu.framework.scope import Scope
    x = np.abs(np.random.RandomState(1).randn(32, 16)).astype(np.float32)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        xv = layers.data("x", [32, 16], dtype="float32",
                         append_batch_size=False)

        def segment(h):
            return list(layers.moe_ffn(h, 8, 2, 8, experts_held=(0, 4),
                                       name="moe"))

        out, load = layers.recompute_segment(segment, [xv]) if recompute \
            else segment(xv)
        layers.moe_balance(load, "moe", (0, 4), bias_update_rate=0.05)
        loss = layers.reduce_mean(layers.elementwise_mul(out, out))
        optimizer.SGD(0.0).minimize(loss)       # the weights stay
    scope, exe = Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    router = np.zeros((16, 8), np.float32)
    router[:, :2] = 0.05                        # x > 0: experts 0, 1 win
    scope.set_var("moe_router.w_0", jnp.asarray(router))
    loads, biases = [], []
    for _ in range(12):
        exe.run(main, feed={"x": x}, fetch_list=[loss], scope=scope)
        loads.append(np.asarray(scope.find_var("moe_expert_load")))
        biases.append(np.asarray(scope.find_var("moe_expert_bias")))
    assert exe.cache_misses == 1                # one compiled step
    assert list(loads[0]) == [32, 32, 0, 0, 0, 0, 0, 0]
    np.testing.assert_allclose(biases[0], [-0.05] * 2 + [0.05] * 6,
                               atol=1e-7)
    # the step that wrote biases[0] routed with the zeros it began with
    assert list(loads[1]) != list(loads[0]) or biases[1][0] < biases[0][0]
    assert loads[-1].sum() == 64 and loads[-1].max() < 32
    assert (loads[-1] > 0).sum() > 2
