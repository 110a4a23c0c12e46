"""Core IR + executor tests (reference test model: tests/unittests/
test_program.py, test_executor_*.py)."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers


def test_program_build():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        y = layers.fc(x, size=3)
    assert x.shape == (-1, 4)
    assert y.shape == (-1, 3)
    types = [op.type for op in main.global_block().ops]
    assert "mul" in types and "elementwise_add" in types
    # params created in both programs, init ops in startup
    assert len(main.all_parameters()) == 2
    assert len(startup.global_block().ops) == 2


def test_program_clone_and_serialize():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        h = layers.fc(x, size=3, act="relu")
        d = layers.dropout(h, 0.5)
    test_prog = main.clone(for_test=True)
    drop_ops = [op for op in test_prog.global_block().ops
                if op.type == "dropout"]
    assert drop_ops[0].attrs["is_test"] is True
    # round trip
    js = main.to_json()
    restored = pt.Program.from_json(js)
    assert [o.type for o in restored.global_block().ops] == \
        [o.type for o in main.global_block().ops]
    assert len(restored.all_parameters()) == len(main.all_parameters())


def test_executor_feed_fetch():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [3], dtype="float32")
        y = layers.scale(x, scale=2.0, bias=1.0)
    exe = pt.Executor()
    xv = np.arange(6, dtype=np.float32).reshape(2, 3)
    out, = exe.run(main, feed={"x": xv}, fetch_list=[y])
    np.testing.assert_allclose(out, xv * 2 + 1, rtol=1e-6)


def test_executor_compile_cache():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [3], dtype="float32")
        y = layers.scale(x, scale=3.0)
    exe = pt.Executor()
    xv = np.ones((2, 3), np.float32)
    exe.run(main, feed={"x": xv}, fetch_list=[y])
    assert len(exe._cache) == 1
    exe.run(main, feed={"x": xv * 2}, fetch_list=[y])
    assert len(exe._cache) == 1            # same signature -> cached
    exe.run(main, feed={"x": np.ones((4, 3), np.float32)}, fetch_list=[y])
    assert len(exe._cache) == 2            # new batch size -> new entry


def test_persistable_state_updates():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        counter = layers.create_global_var([1], 0.0, "float32",
                                           persistable=True)
        layers.increment(counter, value=1.0)
        out = layers.scale(counter, scale=1.0)
    exe = pt.Executor()
    exe.run(startup)
    for i in range(3):
        val, = exe.run(main, feed={}, fetch_list=[out])
    assert float(val[0]) == 3.0


def test_startup_initializers():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        layers.fc(x, size=8,
                  param_attr=pt.ParamAttr(
                      name="w_init_test",
                      initializer=pt.initializer.Constant(0.5)))
    exe = pt.Executor()
    exe.run(startup)
    w = pt.global_scope().get_numpy("w_init_test")
    assert w.shape == (4, 8)
    np.testing.assert_allclose(w, 0.5)


def test_scope_guard_isolation():
    from paddle_tpu.framework.scope import Scope, scope_guard
    s1 = Scope()
    with scope_guard(s1):
        pt.global_scope().set_var("a", 1)
    assert s1.find_var("a") == 1
    assert pt.global_scope().find_var("a") is None


def test_prune():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        h = layers.fc(x, size=3)
        y = layers.softmax(h)
        z = layers.scale(h, scale=5.0)  # not needed for y
    pruned = main._prune(["x"], [y.name])
    types = [op.type for op in pruned.global_block().ops]
    assert "softmax" in types and "scale" not in types


def _tiny_train_hlo(recompute):
    import re
    from paddle_tpu import optimizer
    from paddle_tpu.framework.scope import Scope, scope_guard
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [8, 16], "float32", append_batch_size=False)
        y = layers.data("y", [8, 1], "float32", append_batch_size=False)
        h = layers.fc(x, 32, act="relu")
        if recompute:
            h = layers.recompute_segment(
                lambda t: layers.fc(t, 32, act="tanh"), [h])
        h = layers.layer_norm(h)
        loss = layers.reduce_mean(layers.square(layers.fc(h, 1) - y))
        optimizer.Adam(1e-3).minimize(loss)
    with scope_guard(Scope()):
        exe = pt.Executor()
        exe.run(startup)
        feed = {"x": np.ones((8, 16), np.float32),
                "y": np.ones((8, 1), np.float32)}
        text = exe.dump_hlo(main, feed=feed, fetch_list=[loss],
                            include_compiled=True)["compiled"]
    return set(re.findall(r'op_name="([^"]+)"', text))


@pytest.mark.parametrize("recompute", [False, True])
def test_compiled_step_names_every_op_by_role_and_type(recompute):
    """trace_op lowers every op under jax.named_scope("<role>/<op type>"):
    the first path component after jit(step)/ is the role, the second the
    Fluid op type, whatever JAX appends; a grad_of op is backward/<fwd
    type>; a recompute segment's replayed forward sits under backward,
    its body's ops nested with their own scopes."""
    names = _tiny_train_hlo(recompute)
    scoped = [n for n in names if n.startswith("jit(step)/")]
    assert scoped
    roles = {n.split("/")[1] for n in scoped}
    assert roles == {"forward", "backward", "optimize"}, roles
    for want in ("jit(step)/forward/mul/", "jit(step)/backward/mul/",
                 "jit(step)/forward/layer_norm/",
                 "jit(step)/backward/layer_norm/",
                 "jit(step)/optimize/adam/"):
        assert any(n.startswith(want) for n in scoped), (want, scoped)
    if recompute:
        replayed = [n for n in scoped
                    if n.startswith("jit(step)/backward/remat_block/")
                    and "rematted_computation" in n]
        assert replayed and all("forward/" in n.split("/", 3)[3]
                                for n in replayed), replayed
        assert any(n.startswith("jit(step)/forward/remat_block/")
                   and "forward/tanh" in n for n in scoped)


def test_op_scope_of_roles_and_grad_ops():
    from paddle_tpu import optimizer
    from paddle_tpu.framework.trace import op_scope
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        loss = layers.reduce_mean(layers.fc(x, 1))
        optimizer.SGD(layers.exponential_decay(0.1, 10, 0.9)).minimize(loss)
    scopes = {op_scope(op) for op in main.global_block().ops}
    assert {"forward/mul", "backward/mul", "optimize/sgd"} <= scopes
    assert any(s.startswith("lr_sched/") for s in scopes), scopes
    assert not any(s.endswith("/grad_of") for s in scopes)
