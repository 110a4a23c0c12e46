"""Sharding / collective tests on the 8-virtual-device CPU mesh
(reference test model: tests/unittests/test_dist_* + collective tests,
re-expressed as mesh shardings instead of pserver/NCCL processes). Ring
and Ulysses attention are in `test_sequence_parallel.py`."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import layers, optimizer
from paddle_tpu.framework.compiler import CompiledProgram, BuildStrategy

pytestmark = pytest.mark.skipif(len(jax.devices()) < 8,
                                reason="needs 8 virtual devices")


def _build_mlp_train(seed=0, minimize_fn=None):
    main, startup = pt.Program(), pt.Program()
    main.random_seed = seed
    startup.random_seed = seed
    with pt.program_guard(main, startup):
        x = layers.data("x", [16], dtype="float32")
        y = layers.data("y", [1], dtype="int64")
        h = layers.fc(x, size=32, act="relu",
                      param_attr=pt.ParamAttr(name="w1"),
                      bias_attr=pt.ParamAttr(name="b1"))
        logits = layers.fc(h, size=4, param_attr=pt.ParamAttr(name="w2"),
                           bias_attr=pt.ParamAttr(name="b2"))
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
        if minimize_fn is None:
            optimizer.SGD(0.1).minimize(loss)
        else:
            minimize_fn(loss)
    return main, startup, loss


def test_data_parallel_matches_single_device():
    rng = np.random.RandomState(0)
    xv = rng.rand(16, 16).astype(np.float32)
    yv = rng.randint(0, 4, (16, 1)).astype(np.int64)

    # single device
    main, startup, loss = _build_mlp_train()
    exe = pt.Executor()
    exe.run(startup)
    single = [float(exe.run(main, feed={"x": xv, "y": yv},
                            fetch_list=[loss])[0][0]) for _ in range(3)]
    w_single = pt.global_scope().get_numpy("w1")

    # fresh scope, dp over 8 devices
    from paddle_tpu.framework.scope import Scope, scope_guard
    with scope_guard(Scope()):
        main2, startup2, loss2 = _build_mlp_train()
        exe2 = pt.Executor()
        exe2.run(startup2)
        compiled = CompiledProgram(main2).with_data_parallel(
            loss_name=loss2.name)
        dp = [float(exe2.run(compiled, feed={"x": xv, "y": yv},
                             fetch_list=[loss2])[0][0]) for _ in range(3)]
        w_dp = pt.global_scope().get_numpy("w1")

    np.testing.assert_allclose(single, dp, rtol=1e-4)
    np.testing.assert_allclose(w_single, w_dp, rtol=1e-4, atol=1e-6)


def test_tensor_parallel_fc():
    """Column-parallel fc over mp axis must equal dense result."""
    from paddle_tpu.distributed import column_parallel_attr
    rng = np.random.RandomState(1)
    xv = rng.rand(4, 8).astype(np.float32)

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [8], dtype="float32")
        attr = column_parallel_attr(name="w_mp")
        attr.initializer = pt.initializer.Constant(0.1)
        y = layers.fc(x, size=16, param_attr=attr, bias_attr=False)
    exe = pt.Executor()
    exe.run(startup)

    bs = BuildStrategy()
    bs.mesh_axes = {"dp": 2, "mp": 4}
    compiled = CompiledProgram(main, bs)
    out, = exe.run(compiled, feed={"x": xv}, fetch_list=[y])
    np.testing.assert_allclose(out, xv @ np.full((8, 16), 0.1, np.float32),
                               rtol=1e-5)


def test_full_train_step_dp_mp_mesh():
    """fc stack with mp-sharded weights + dp-sharded batch; one SGD step."""
    from paddle_tpu.distributed import column_parallel_attr, \
        row_parallel_attr
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [32], dtype="float32")
        y = layers.data("y", [1], dtype="int64")
        h = layers.fc(x, size=64, act="gelu",
                      param_attr=column_parallel_attr(name="mp_w1"),
                      bias_attr=pt.ParamAttr(name="mp_b1"))
        h2 = layers.fc(h, size=32,
                       param_attr=row_parallel_attr(name="mp_w2"),
                       bias_attr=pt.ParamAttr(name="mp_b2"))
        logits = layers.fc(h2, size=8)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
        optimizer.Adam(1e-3).minimize(loss)
    exe = pt.Executor()
    exe.run(startup)
    bs = BuildStrategy()
    bs.mesh_axes = {"dp": 2, "mp": 4}
    compiled = CompiledProgram(main, bs)
    rng = np.random.RandomState(2)
    feed = {"x": rng.rand(8, 32).astype(np.float32),
            "y": rng.randint(0, 8, (8, 1)).astype(np.int64)}
    l1 = exe.run(compiled, feed=feed, fetch_list=[loss])[0]
    for _ in range(5):
        l2 = exe.run(compiled, feed=feed, fetch_list=[loss])[0]
    assert float(l2[0]) < float(l1[0])


def test_collective_ops_shardmap():
    """c_allreduce_sum / c_allgather kernels inside shard_map."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    from paddle_tpu.ops.registry import get_op

    devs = np.array(jax.devices()[:8])
    mesh = Mesh(devs, ("dp",))

    class Ctx:
        bound_axes = ("dp",)

        def rng(self):
            return jax.random.PRNGKey(0)

    def body(x):
        out = get_op("c_allreduce_sum").fn(Ctx(), {"X": [x]},
                                           {"axis_name": "dp"})
        return out["Out"]

    x = jnp.arange(8.0)
    f = shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    res = f(x)
    np.testing.assert_allclose(np.asarray(res), np.full(8, 28.0))


def test_fleet_api():
    from paddle_tpu.distributed import fleet, DistributedStrategy
    strategy = DistributedStrategy()
    strategy.mesh_axes = {"dp": 8}
    fleet.init(strategy=strategy)
    assert fleet.worker_num() == 1  # single host in tests
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        loss = layers.mean(layers.fc(x, size=2))
        opt = fleet.distributed_optimizer(optimizer.SGD(0.1))
        opt.minimize(loss)
    exe = pt.Executor()
    exe.run(startup)
    compiled = fleet.main_program_compiled(main)
    out, = exe.run(compiled,
                   feed={"x": np.ones((8, 4), np.float32)},
                   fetch_list=[loss])
    assert np.isfinite(out).all()


def test_pipeline_forward_matches_serial():
    """8-stage GPipe ring over 8 devices == serial composition."""
    from paddle_tpu.distributed.pipeline import (pipeline_forward,
                                                 stack_stage_params)
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:8])
    mesh = Mesh(devs, ("pp",))
    rng = np.random.RandomState(0)
    n_stage, n_micro, mb, d = 8, 4, 2, 16
    ws = [rng.randn(d, d).astype(np.float32) * 0.3 for _ in range(n_stage)]
    params = stack_stage_params([{"w": w} for w in ws])
    x = rng.randn(n_micro, mb, d).astype(np.float32)

    def stage(p, h):
        return jnp.tanh(h @ p["w"])

    out = np.asarray(pipeline_forward(stage, params, x, mesh))
    ref = x.copy()
    for w in ws:
        ref = np.tanh(ref @ w)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


def test_pipeline_grads():
    from paddle_tpu.distributed.pipeline import (pipeline_loss_and_grads,
                                                 stack_stage_params)
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("pp",))
    rng = np.random.RandomState(1)
    n_stage, n_micro, mb, d = 4, 2, 2, 8
    ws = [rng.randn(d, d).astype(np.float32) * 0.3 for _ in range(n_stage)]
    params = stack_stage_params([{"w": w} for w in ws])
    x = rng.randn(n_micro, mb, d).astype(np.float32)
    y = rng.randn(n_micro, mb, d).astype(np.float32)

    def stage(p, h):
        return jnp.tanh(h @ p["w"])

    def loss_fn(out, y):
        return jnp.mean((out - y) ** 2)

    loss, grads = pipeline_loss_and_grads(stage, loss_fn, params, x, y,
                                          mesh)
    # reference grads via serial composition
    def serial_loss(ws_stacked):
        h = x
        for i in range(n_stage):
            h = jnp.tanh(h @ ws_stacked["w"][i])
        return jnp.mean((h - y) ** 2)

    ref_loss, ref_grads = jax.value_and_grad(serial_loss)(params)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grads["w"]),
                               np.asarray(ref_grads["w"]),
                               rtol=1e-3, atol=1e-5)


def test_pipeline_1f1b_matches_serial_and_gpipe():
    """1F1B schedule must be numerically exact vs serial composition (and
    therefore vs the GPipe path) for loss AND per-stage grads."""
    from paddle_tpu.distributed.pipeline import (pipeline_1f1b_step,
                                                 stack_stage_params)
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("pp",))
    rng = np.random.RandomState(2)
    n_stage, n_micro, mb, d = 4, 6, 2, 8
    ws = [rng.randn(d, d).astype(np.float32) * 0.3 for _ in range(n_stage)]
    bs = [rng.randn(d).astype(np.float32) * 0.1 for _ in range(n_stage)]
    params = stack_stage_params([{"w": w, "b": b} for w, b in zip(ws, bs)])
    x = rng.randn(n_micro, mb, d).astype(np.float32)
    y = rng.randn(n_micro, mb, d).astype(np.float32)

    def stage(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    def micro_loss(h_out, y_m):
        return jnp.mean((h_out - y_m) ** 2)

    loss, grads = pipeline_1f1b_step(stage, micro_loss, params, x, y, mesh)

    def serial_loss(ps):
        h = x
        for i in range(n_stage):
            h = jnp.tanh(h @ ps["w"][i] + ps["b"][i])
        return jnp.mean(jnp.mean((h - y) ** 2, axis=tuple(range(1, h.ndim))))

    ref_loss, ref_grads = jax.value_and_grad(serial_loss)(params)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grads["w"]),
                               np.asarray(ref_grads["w"]),
                               rtol=1e-3, atol=1e-5)
    np.testing.assert_allclose(np.asarray(grads["b"]),
                               np.asarray(ref_grads["b"]),
                               rtol=1e-3, atol=1e-5)


def test_pipeline_1f1b_odd_micro_counts():
    """Schedule edges: n_micro < n_stage and n_micro not divisible."""
    from paddle_tpu.distributed.pipeline import (pipeline_1f1b_step,
                                                 stack_stage_params)
    from jax.sharding import Mesh
    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("pp",))
    rng = np.random.RandomState(3)
    n_stage, d = 4, 4
    for n_micro in (1, 3, 5):
        ws = [rng.randn(d, d).astype(np.float32) * 0.5
              for _ in range(n_stage)]
        params = stack_stage_params([{"w": w} for w in ws])
        x = rng.randn(n_micro, 2, d).astype(np.float32)
        y = rng.randn(n_micro, 2, d).astype(np.float32)

        def stage(p, h):
            return jnp.tanh(h @ p["w"])

        def micro_loss(h_out, y_m):
            return jnp.mean((h_out - y_m) ** 2)

        loss, grads = pipeline_1f1b_step(stage, micro_loss, params, x, y,
                                         mesh)

        def serial_loss(ps):
            h = x
            for i in range(n_stage):
                h = jnp.tanh(h @ ps["w"][i])
            return jnp.mean((h - y) ** 2)

        ref_loss, ref_grads = jax.value_and_grad(serial_loss)(params)
        np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(grads["w"]),
                                   np.asarray(ref_grads["w"]),
                                   rtol=1e-3, atol=1e-5)


def test_sharded_embedding_matches_dense():
    """Row-sharded lookup over 8 shards == dense table gather; grads are
    the scatter-add restricted to owner shards."""
    from paddle_tpu.distributed.sharded_embedding import (
        sharded_embedding_lookup)
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:8]), ("mp",))
    rng = np.random.RandomState(0)
    v, d = 64, 16
    table = jnp.asarray(rng.randn(v, d).astype(np.float32))
    ids = jnp.asarray(rng.randint(0, v, size=(4, 7)))

    out = sharded_embedding_lookup(table, ids, mesh)
    np.testing.assert_allclose(np.asarray(out), np.asarray(table)[ids],
                               rtol=1e-6)

    def loss_sharded(t):
        return jnp.sum(sharded_embedding_lookup(t, ids, mesh) ** 2)

    def loss_dense(t):
        return jnp.sum(t[ids] ** 2)

    g1 = jax.grad(loss_sharded)(table)
    g2 = jax.grad(loss_dense)(table)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-5)


def test_sharded_embedding_class_trains():
    from paddle_tpu.distributed import ShardedEmbedding
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:4]), ("mp",))
    emb = ShardedEmbedding(32, 8, mesh)
    ids = jnp.asarray(np.array([1, 5, 17, 31]))
    target = jnp.ones((4, 8))

    def loss(table):
        from paddle_tpu.distributed.sharded_embedding import (
            sharded_embedding_lookup)
        out = sharded_embedding_lookup(table, ids, mesh)
        return jnp.mean((out - target) ** 2)

    l0 = float(loss(emb.table))
    grad = jax.jit(jax.grad(loss))      # traced once, not once an update
    for _ in range(40):
        emb.apply_row_sparse_grad(grad(emb.table), lr=1.0)
    assert float(loss(emb.table)) < 0.1 * l0


def test_lazy_adam_skips_untouched_rows():
    """Adam(lazy_mode=True): embedding rows absent from the batch keep
    params AND moments frozen (reference sparse adam semantics)."""
    import paddle_tpu as pt
    from paddle_tpu import layers, optimizer
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        ids = layers.data("ids", [1], "int64")
        emb = layers.embedding(ids, size=(10, 4))
        loss = layers.reduce_mean(layers.square(emb))
        optimizer.Adam(0.5, lazy_mode=True).minimize(loss)
    exe = pt.Executor()
    exe.run(startup)
    from paddle_tpu.framework.scope import global_scope
    wname = main.all_parameters()[0].name
    before = np.asarray(global_scope().find_var(wname)).copy()
    feed = {"ids": np.array([[1], [3]], np.int64)}
    for _ in range(3):
        exe.run(main, feed=feed, fetch_list=[loss])
    after = np.asarray(global_scope().find_var(wname))
    touched = np.zeros(10, bool)
    touched[[1, 3]] = True
    assert not np.allclose(after[touched], before[touched])
    np.testing.assert_allclose(after[~touched], before[~touched])
    m1 = np.asarray(global_scope().find_var(wname + "_moment1_0"))
    assert np.all(m1[~touched] == 0) and not np.all(m1[touched] == 0)


@pytest.mark.parametrize("schedule", ["1f1b", "gpipe"])
def test_fleet_pipeline_dp_x_pp_matches_serial(schedule):
    """fleet.distributed_optimizer(opt, strategy with pipeline=True) must
    run GPipe/1F1B on a stage-partitioned Program over a dp x pp mesh and
    match full-batch serial SGD training exactly (VERDICT r2 next #5)."""
    import jax.numpy as jnp
    from paddle_tpu.distributed import fleet, init_mesh, DistributedStrategy
    from paddle_tpu.distributed.pipeline_program import pp_stage_guard

    n_stage, dm, batch, lr = 4, 8, 8, 0.2
    init_mesh({"dp": 2, "pp": n_stage})
    strategy = DistributedStrategy()
    strategy.mesh_axes = {"dp": 2, "pp": n_stage}
    strategy.pipeline = True
    strategy.pp_schedule = schedule
    strategy.pp_num_micro = 4

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("pp_x", [batch, dm], "float32",
                        append_batch_size=False)
        h = x
        for s in range(n_stage):
            with pp_stage_guard(s):
                h = layers.fc(h, size=dm, act="tanh")
        y = layers.data("pp_y", [batch, dm], "float32",
                        append_batch_size=False)
        loss = layers.reduce_mean(layers.square(h - y))
        opt = fleet.distributed_optimizer(optimizer.SGD(lr), strategy)
        opt.minimize(loss)

    exe = pt.Executor()
    exe.run(startup)
    # snapshot the initial stage params for the serial oracle
    pnames = [p.name for p in main.all_parameters()]
    init_params = {n: np.asarray(pt.global_scope().find_var(n))
                   for n in pnames}

    rng = np.random.RandomState(0)
    xs = [rng.randn(batch, dm).astype(np.float32) for _ in range(3)]
    ys = [rng.randn(batch, dm).astype(np.float32) for _ in range(3)]
    losses = []
    for xv, yv in zip(xs, ys):
        lv, = exe.run(main, feed={"pp_x": xv, "pp_y": yv},
                      fetch_list=[loss])
        losses.append(float(np.asarray(lv).reshape(-1)[0]))

    # serial full-batch oracle with identical init
    ws = [jnp.asarray(init_params["fc_%d.w_0_0" % s]) for s in range(n_stage)]
    bs = [jnp.asarray(init_params["fc_%d.b_0_0" % s]) for s in range(n_stage)]

    def serial_loss(params, xv, yv):
        hh = jnp.asarray(xv)
        for W, b in zip(params[0], params[1]):
            hh = jnp.tanh(hh @ W + b)
        return jnp.mean((hh - jnp.asarray(yv)) ** 2)

    params = (ws, bs)
    for i, (xv, yv) in enumerate(zip(xs, ys)):
        lv, grads = jax.value_and_grad(serial_loss)(params, xv, yv)
        np.testing.assert_allclose(losses[i], float(lv), rtol=1e-4,
                                   atol=1e-5)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)

    # trained params written back per stage
    for s in range(n_stage):
        np.testing.assert_allclose(
            np.asarray(pt.global_scope().find_var("fc_%d.w_0_0" % s)),
            np.asarray(params[0][s]), rtol=1e-4, atol=1e-5)


def test_place_feed_local_shard_path():
    """The multi-host feed assembler (make_array_from_process_local_data)
    must agree with plain sharded device_put in the 1-process case, so
    the multi-host path is exercised by construction."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.framework.compiler import _place_feed, make_mesh
    mesh = make_mesh({"dp": 4})
    s = NamedSharding(mesh, P("dp"))
    x = np.arange(32, dtype=np.float32).reshape(8, 4)
    via_dp = jax.device_put(x, s)
    via_local = jax.make_array_from_process_local_data(s, x)
    np.testing.assert_array_equal(np.asarray(via_dp),
                                  np.asarray(via_local))
    out = _place_feed(x, s)   # 1-process: device_put branch
    np.testing.assert_array_equal(np.asarray(out), x)
    rep = _place_feed(x, NamedSharding(mesh, P()))
    np.testing.assert_array_equal(np.asarray(rep), x)


def _run_workers(tmp_path, script, base_port, n=2, extra_env=None):
    """Launch n worker processes through launch.start_procs (the
    PADDLE_TRAINER env contract) and return their combined logs; asserts
    every worker exits 0."""
    import os
    import textwrap

    worker = tmp_path / "worker.py"
    worker.write_text(textwrap.dedent(script))
    from paddle_tpu.distributed import launch
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in (env.get("PYTHONPATH"),
                     os.path.dirname(os.path.dirname(
                         os.path.abspath(__file__)))) if p])
    env.pop("XLA_FLAGS", None)  # workers use 1 CPU device each
    env.update(extra_env or {})
    log_dir = str(tmp_path / "logs")
    procs = launch.start_procs(n, str(worker), log_dir=log_dir,
                               base_port=base_port, env=env)
    rcs = [p.wait() for p in procs]
    logs = "\n".join(
        open(os.path.join(log_dir, "workerlog.%d" % i)).read()
        for i in range(n))
    assert rcs == [0] * n, logs
    return logs


def test_multiprocess_jax_distributed_e2e(tmp_path):
    """REAL multi-host validation: 2 OS processes form a jax.distributed
    job through launch.start_procs + init_on_pod (the PADDLE_TRAINER env
    contract), build one global mesh over both processes' devices, feed
    process-local shards, and agree on a collective sum — the exact
    code path a TPU pod runs, minus the ICI."""
    logs = _run_workers(tmp_path, """
        import jax
        import numpy as np
        from paddle_tpu.distributed import launch
        pid, n = launch.init_on_pod()
        assert n == 2, n
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        mesh = Mesh(np.array(jax.devices()), ("dp",))
        local = np.full((4, 2), float(pid + 1), np.float32)
        sh = NamedSharding(mesh, P("dp"))
        garr = jax.make_array_from_process_local_data(sh, local)
        total = jax.jit(lambda x: jnp.sum(x),
                        out_shardings=NamedSharding(mesh, P()))(garr)
        assert abs(float(np.asarray(total)) - 24.0) < 1e-6
        print("OK", pid, flush=True)
    """, base_port=8520)
    assert "OK 0" in logs and "OK 1" in logs


def test_multiprocess_sharded_checkpoint_e2e(tmp_path):
    """REAL multi-host checkpoint contract: 2 OS processes in one
    jax.distributed job save a dp-sharded array — each process writes
    ONLY its own shard file, process 0 commits the manifest — then
    restore straight onto the mesh (shardings= path) and verify every
    local shard.  The fs-visible analogue of the reference's
    per-pserver _save_distributed_persistables."""
    logs = _run_workers(tmp_path, """
        import jax
        import json
        import os
        import numpy as np
        from paddle_tpu.distributed import launch
        pid, n = launch.init_on_pod()
        assert n == 2, n
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from paddle_tpu.io import save_checkpoint, load_checkpoint
        from paddle_tpu.framework.scope import Scope, scope_guard

        ckpt = os.environ["CKPT_DIR"]
        mesh = Mesh(np.array(jax.devices()), ("dp",))
        sh = NamedSharding(mesh, P("dp"))
        full = np.arange(16, dtype=np.float32).reshape(8, 2)
        garr = jax.make_array_from_process_local_data(
            sh, full[pid * 4:(pid + 1) * 4])
        sc = Scope()
        with scope_guard(sc):
            sc.set_var("w_mh", garr)
            sc.set_var("step_counter", np.int64(11))
            save_checkpoint(None, ckpt, step=2)

        man = json.load(open(os.path.join(ckpt, "step_2",
                                          "manifest.json")))
        files = {s["file"] for s in man["vars"]["w_mh"]["shards"]}
        assert files == {"shards_p0.npz", "shards_p1.npz"}, files
        own = np.load(os.path.join(ckpt, "step_2",
                                   "shards_p%d.npz" % pid))
        # pid 0 additionally owns the replicated counter
        assert len(own.files) == (2 if pid == 0 else 1), own.files

        sc2 = Scope()
        with scope_guard(sc2):
            step = load_checkpoint(None, ckpt, shardings={"w_mh": sh})
            assert step == 2
            got = sc2.find_var("w_mh")
            assert got.sharding == sh
            for s in got.addressable_shards:
                np.testing.assert_allclose(np.asarray(s.data),
                                           full[s.index])
            assert int(np.asarray(sc2.find_var("step_counter"))) == 11
        print("CKPT OK", pid, flush=True)
    """, base_port=8532, extra_env={"CKPT_DIR": str(tmp_path / "ckpt")})
    assert "CKPT OK 0" in logs and "CKPT OK 1" in logs


def test_zero1_optimizer_state_sharding_matches_unsharded():
    """fleet DistributedStrategy.sharding_optimizer_state (ZeRO-1):
    Adam moments annotated for dp sharding must train identically to
    the replicated run, and the moment arrays must actually land
    dp-sharded on the mesh."""
    from paddle_tpu.distributed import fleet, DistributedStrategy
    from paddle_tpu.framework.scope import Scope, scope_guard

    rng = np.random.RandomState(0)
    xv = rng.rand(16, 16).astype(np.float32)
    yv = rng.randint(0, 4, (16, 1)).astype(np.int64)

    def build(sharded):
        strategy = DistributedStrategy()
        strategy.mesh_axes = {"dp": 8}
        strategy.sharding_optimizer_state = sharded
        main, startup, loss = _build_mlp_train(
            minimize_fn=lambda l: fleet.distributed_optimizer(
                optimizer.Adam(0.05), strategy).minimize(l))
        return main, startup, loss, strategy

    results = {}
    for sharded in (False, True):
        with scope_guard(Scope()):
            main, startup, loss, strategy = build(sharded)
            exe = pt.Executor()
            exe.run(startup)
            bs = BuildStrategy()
            bs.mesh_axes = strategy.mesh_axes
            compiled = CompiledProgram(main, bs)
            losses = [float(np.asarray(
                exe.run(compiled, feed={"x": xv, "y": yv},
                        fetch_list=[loss])[0]).reshape(-1)[0])
                for _ in range(4)]
            w = pt.global_scope().get_numpy("w1")
            if sharded:
                # a (32,)-row moment of w1 must be split over dp
                moments = [n for n in pt.global_scope().keys()
                           if "w1" in n and ("moment" in n.lower()
                                             or "_m" in n)]
                assert moments, "no Adam moment vars found for w1"
                arr = pt.global_scope().find_var(moments[0])
                shard_axes = {
                    a for axes in getattr(arr.sharding, "spec", [])
                    or [] for a in (axes if isinstance(axes, tuple)
                                    else [axes]) if a}
                assert "dp" in shard_axes, (
                    moments[0], getattr(arr, "sharding", None))
            results[sharded] = (losses, w)

    np.testing.assert_allclose(results[False][0], results[True][0],
                               rtol=1e-4)
    np.testing.assert_allclose(results[False][1], results[True][1],
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("schedule", ["1f1b", "gpipe"])
def test_fleet_pipeline_multifeed_multifetch_matches_serial(schedule):
    """Pipeline v2 (VERDICT r4 next #7): dp2 x pp2 program whose loss
    section consumes TWO extra feeds (labels + per-sample weights) with
    THREE fetches (loss, per-sample error, unweighted mse) — all exact
    vs the serial oracle; then the same program through run_steps as one
    fused window."""
    import jax.numpy as jnp
    from paddle_tpu.distributed import fleet, init_mesh, DistributedStrategy
    from paddle_tpu.distributed.pipeline_program import pp_stage_guard

    n_stage, dm, batch, lr = 2, 8, 8, 0.2
    init_mesh({"dp": 2, "pp": n_stage})
    strategy = DistributedStrategy()
    strategy.mesh_axes = {"dp": 2, "pp": n_stage}
    strategy.pipeline = True
    strategy.pp_schedule = schedule
    strategy.pp_num_micro = 2

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("pp_x", [batch, dm], "float32",
                        append_batch_size=False)
        h = x
        for s in range(n_stage):
            with pp_stage_guard(s):
                h = layers.fc(h, size=dm, act="tanh")
        y = layers.data("pp_y", [batch, dm], "float32",
                        append_batch_size=False)
        w = layers.data("pp_w", [batch, 1], "float32",
                        append_batch_size=False)
        err = layers.reduce_mean(layers.square(h - y), dim=1,
                                 keep_dim=True)          # (batch, 1)
        mse = layers.reduce_mean(err)                     # unweighted
        loss = layers.reduce_mean(err * w)                # weighted loss
        opt = fleet.distributed_optimizer(optimizer.SGD(lr), strategy)
        opt.minimize(loss)

    exe = pt.Executor()
    exe.run(startup)
    pnames = [p.name for p in main.all_parameters()]
    init_params = {n: np.asarray(pt.global_scope().find_var(n))
                   for n in pnames}

    rng = np.random.RandomState(1)
    feeds = [{"pp_x": rng.randn(batch, dm).astype(np.float32),
              "pp_y": rng.randn(batch, dm).astype(np.float32),
              "pp_w": rng.rand(batch, 1).astype(np.float32)}
             for _ in range(3)]
    got = [exe.run(main, feed=f, fetch_list=[loss, err, mse])
           for f in feeds]

    # serial oracle with identical init
    ws = [jnp.asarray(init_params["fc_%d.w_0_0" % s])
          for s in range(n_stage)]
    bs = [jnp.asarray(init_params["fc_%d.b_0_0" % s])
          for s in range(n_stage)]

    def fwd(params, xv):
        hh = jnp.asarray(xv)
        for W, b in zip(params[0], params[1]):
            hh = jnp.tanh(hh @ W + b)
        return hh

    def weighted_loss(params, f):
        hh = fwd(params, f["pp_x"])
        e = jnp.mean((hh - jnp.asarray(f["pp_y"])) ** 2, axis=1,
                     keepdims=True)
        return jnp.mean(e * jnp.asarray(f["pp_w"])), e

    params = (ws, bs)
    for i, f in enumerate(feeds):
        (lv, e), grads = jax.value_and_grad(
            lambda p: weighted_loss(p, f), has_aux=True)(params)
        np.testing.assert_allclose(got[i][0].reshape(()), float(lv),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(got[i][1], np.asarray(e),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(got[i][2].reshape(()),
                                   float(jnp.mean(e)), rtol=1e-4,
                                   atol=1e-5)
        params = jax.tree.map(lambda p, g: p - lr * g, params, grads)


@pytest.mark.parametrize("schedule", ["1f1b"])
def test_fleet_pipeline_run_steps_matches_per_step(schedule):
    """run_steps x pipeline: a W-step fused window must produce the same
    per-step losses and final params as W sequential run() calls."""
    import jax.numpy as jnp
    from paddle_tpu.distributed import fleet, init_mesh, DistributedStrategy
    from paddle_tpu.distributed.pipeline_program import pp_stage_guard
    from paddle_tpu.framework.scope import Scope, scope_guard

    n_stage, dm, batch, lr, W = 2, 8, 8, 0.2, 3
    rng = np.random.RandomState(2)
    xs = rng.randn(W, batch, dm).astype(np.float32)
    ys = rng.randn(W, batch, dm).astype(np.float32)

    def build():
        init_mesh({"dp": 2, "pp": n_stage})
        strategy = DistributedStrategy()
        strategy.mesh_axes = {"dp": 2, "pp": n_stage}
        strategy.pipeline = True
        strategy.pp_schedule = schedule
        strategy.pp_num_micro = 2
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("pp_x", [batch, dm], "float32",
                            append_batch_size=False)
            h = x
            for s in range(n_stage):
                with pp_stage_guard(s):
                    h = layers.fc(h, size=dm, act="tanh")
            y = layers.data("pp_y", [batch, dm], "float32",
                            append_batch_size=False)
            loss = layers.reduce_mean(layers.square(h - y))
            fleet.distributed_optimizer(optimizer.SGD(lr),
                                        strategy).minimize(loss)
        return main, startup, loss

    main, startup, loss = build()
    pnames = [p.name for p in main.all_parameters()]
    with scope_guard(Scope()) as _:
        exe = pt.Executor()
        exe.run(startup)
        serial = [float(np.asarray(exe.run(
            main, feed={"pp_x": xs[i], "pp_y": ys[i]},
            fetch_list=[loss])[0]).reshape(()))
            for i in range(W)]
        serial_params = {n: np.asarray(pt.global_scope().find_var(n))
                         for n in pnames}

    main2, startup2, loss2 = build()
    pnames2 = [p.name for p in main2.all_parameters()]
    with scope_guard(Scope()):
        exe2 = pt.Executor()
        exe2.run(startup2)
        stacked, = exe2.run_steps(main2, feed={"pp_x": xs, "pp_y": ys},
                                  fetch_list=[loss2])
        win_params = {n: np.asarray(pt.global_scope().find_var(n))
                      for n in pnames2}
    np.testing.assert_allclose(np.asarray(stacked).reshape(W), serial,
                               rtol=1e-5, atol=1e-6)
    # param names differ between the two program builds (unique_name
    # keeps counting); align by position
    for n1, n2 in zip(pnames, pnames2):
        np.testing.assert_allclose(win_params[n2], serial_params[n1],
                                   rtol=1e-5, atol=1e-6)
