"""Executor trainer-loop tests (train_from_dataset / prefetch)."""
import numpy as np
import pytest


def test_train_from_dataset_runs_all_batches():
    """Executor.train_from_dataset: prefetch loop drives the jitted step
    over a Dataset (trainer_factory/device_worker equivalent)."""
    import paddle_tpu as pt
    from paddle_tpu import layers, optimizer

    class ListDataset(object):
        def __init__(self, batches):
            self._batches = batches

        def __iter__(self):
            return iter(self._batches)

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4], "float32")
        y = layers.fc(x, size=1)
        lbl = layers.data("y", [1], "float32")
        loss = layers.reduce_mean(layers.square_error_cost(y, lbl))
        optimizer.SGD(0.1).minimize(loss)
    exe = pt.Executor()
    exe.run(startup)
    rng = np.random.RandomState(0)
    batches = [{"x": rng.rand(8, 4).astype(np.float32),
                "y": rng.rand(8, 1).astype(np.float32)} for _ in range(7)]
    steps, last = exe.train_from_dataset(main, ListDataset(batches),
                                         fetch_list=[loss])
    assert steps == 7
    assert np.isfinite(np.asarray(last[0])).all()
    # loss decreased over the pass
    l_again = exe.run(main, feed=batches[0], fetch_list=[loss])[0]
    assert np.isfinite(l_again).all()


def test_train_from_dataset_windowed_matches_per_step():
    """steps_per_dispatch=3: same dataset pass (windows + tail) produces
    the same final parameters as the per-step loop."""
    import paddle_tpu as pt
    from paddle_tpu import layers, optimizer
    from paddle_tpu.framework.scope import Scope, scope_guard

    def build():
        main, startup = pt.Program(), pt.Program()
        with pt.unique_name.guard(), pt.program_guard(main, startup):
            x = layers.data("x", [4], "float32")
            y = layers.fc(x, size=1, name="wfc")
            lbl = layers.data("y", [1], "float32")
            loss = layers.reduce_mean(layers.square_error_cost(y, lbl))
            optimizer.SGD(0.1).minimize(loss)
        return main, startup, loss

    rng = np.random.RandomState(1)
    batches = [{"x": rng.rand(8, 4).astype(np.float32),
                "y": rng.rand(8, 1).astype(np.float32)} for _ in range(7)]

    results = []
    for w in (1, 3):
        main, startup, loss = build()
        sc = Scope()
        with scope_guard(sc):
            exe = pt.Executor()
            exe.run(startup)
            steps, last = exe.train_from_dataset(
                main, batches, fetch_list=[loss], steps_per_dispatch=w)
            assert steps == 7
            results.append({n: np.asarray(v) for n, v in sc.items()
                            if v is not None and
                            np.asarray(v).dtype.kind == "f"})
    for n, ref in results[0].items():
        np.testing.assert_allclose(results[1][n], ref, rtol=1e-6,
                                   atol=1e-6, err_msg=n)


def test_train_from_dataset_windowed_handles_ragged_batches():
    """A ragged batch (remainder / bucketed length) inside a window must
    degrade to per-step execution, not crash the epoch."""
    import paddle_tpu as pt
    from paddle_tpu import layers, optimizer
    from paddle_tpu.framework.scope import Scope, scope_guard

    main, startup = pt.Program(), pt.Program()
    with pt.unique_name.guard(), pt.program_guard(main, startup):
        x = layers.data("x", [4], "float32")
        y = layers.fc(x, size=1)
        lbl = layers.data("y", [1], "float32")
        loss = layers.reduce_mean(layers.square_error_cost(y, lbl))
        optimizer.SGD(0.1).minimize(loss)

    rng = np.random.RandomState(2)

    def mk(n):
        return {"x": rng.rand(n, 4).astype(np.float32),
                "y": rng.rand(n, 1).astype(np.float32)}

    batches = [mk(8), mk(8), mk(4), mk(8), mk(8)]   # ragged mid-window
    with scope_guard(Scope()):
        exe = pt.Executor()
        exe.run(startup)
        steps, last = exe.train_from_dataset(
            main, batches, fetch_list=[loss], steps_per_dispatch=3)
    assert steps == 5
    assert np.isfinite(np.asarray(last[0])).all()


def test_prefetch_iterator_propagates_errors():
    from paddle_tpu.trainer_factory import PrefetchIterator

    def gen():
        yield 1
        raise RuntimeError("boom")

    it = PrefetchIterator(gen())
    assert next(it) == 1
    import pytest
    with pytest.raises(RuntimeError):
        for _ in it:
            pass


def test_wrong_rank_feed_named_error():
    """A wrong-rank feed must fail at the feed boundary with the var's
    name, not as a jax shape error deep inside the trace."""
    import pytest
    import paddle_tpu as pt
    from paddle_tpu import layers
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("rank_x", [4], dtype="float32")
        y = layers.scale(x, scale=2.0)
    exe = pt.Executor()
    exe.run(startup)
    with pytest.raises(ValueError, match="rank_x.*rank"):
        exe.run(main, feed={"rank_x": np.ones(4, np.float32)},  # rank 1
                fetch_list=[y])                                 # wants 2


def test_infer_from_dataset_rejects_training_program():
    """infer_from_dataset must refuse a program with parameter-update ops
    (reference executor.py:1061 disables gradient push; ours validates) —
    and accept the for_test clone of the same model."""
    import pytest
    import paddle_tpu as pt
    from paddle_tpu import layers, optimizer

    class ListDataset(object):
        def __init__(self, batches):
            self._batches = batches

        def __iter__(self):
            return iter(self._batches)

    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4], "float32")
        y = layers.fc(x, size=1)
        lbl = layers.data("y", [1], "float32")
        loss = layers.reduce_mean(layers.square_error_cost(y, lbl))
        test_prog = main.clone(for_test=True)
        optimizer.SGD(0.1).minimize(loss)
    exe = pt.Executor()
    exe.run(startup)
    rng = np.random.RandomState(0)
    batches = [{"x": rng.rand(8, 4).astype(np.float32),
                "y": rng.rand(8, 1).astype(np.float32)} for _ in range(3)]
    with pytest.raises(ValueError, match="parameter-update ops"):
        exe.infer_from_dataset(main, ListDataset(batches),
                               fetch_list=[loss])
    steps, last = exe.infer_from_dataset(test_prog, ListDataset(batches),
                                         fetch_list=[loss])
    assert steps == 3
    assert np.isfinite(np.asarray(last[0])).all()


def test_train_from_dataset_windows_pipeline_program():
    """steps_per_dispatch on a fleet pipeline program routes through
    Executor._run_pipeline_steps (one fused scan per window) and matches
    the per-step loop exactly."""
    import paddle_tpu as pt
    from paddle_tpu import layers, optimizer
    from paddle_tpu.distributed import fleet, init_mesh, DistributedStrategy
    from paddle_tpu.distributed.pipeline_program import pp_stage_guard
    from paddle_tpu.framework.scope import Scope, scope_guard

    class ListDataset(object):
        def __init__(self, batches):
            self._batches = batches

        def __iter__(self):
            return iter(self._batches)

    n_stage, dm, batch, W = 2, 8, 8, 4
    rng = np.random.RandomState(3)
    batches = [{"pp_x": rng.randn(batch, dm).astype(np.float32),
                "pp_y": rng.randn(batch, dm).astype(np.float32)}
               for _ in range(W)]

    def build():
        init_mesh({"dp": 2, "pp": n_stage})
        strategy = DistributedStrategy()
        strategy.mesh_axes = {"dp": 2, "pp": n_stage}
        strategy.pipeline = True
        strategy.pp_schedule = "1f1b"
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
            fleet.distributed_optimizer(optimizer.SGD(0.1),
                                        strategy).minimize(loss)
        return main, startup, loss

    def run(steps_per_dispatch):
        main, startup, loss = build()
        with scope_guard(Scope()):
            exe = pt.Executor()
            exe.run(startup)
            steps, last = exe.train_from_dataset(
                main, ListDataset(batches), fetch_list=[loss],
                steps_per_dispatch=steps_per_dispatch)
        return steps, float(np.asarray(last[0]).reshape(-1)[-1])

    s1, l1 = run(1)
    s2, l2 = run(2)
    assert s1 == s2 == W
    np.testing.assert_allclose(l1, l2, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("mesh", [None, {"dp": 8}], ids=["plain", "dp8"])
@pytest.mark.parametrize("entry", ["run", "run_steps"])
def test_repeated_steps_compile_once_and_hit_after(entry, mesh):
    """Twelve dispatches of one program at one feed signature (fresh
    arrays each time, as a training loop feeds them): exactly 1 miss and
    11 hits through `run` and through `run_steps`, on one device and as a
    CompiledProgram over a dp mesh. A key that churned with the feed's
    identity would recompile every step."""
    import paddle_tpu as pt
    from paddle_tpu import layers, optimizer
    from paddle_tpu.framework.compiler import BuildStrategy, CompiledProgram
    from paddle_tpu.framework.scope import Scope, scope_guard
    n, window = 12, 3
    rng = np.random.RandomState(0)

    def batch(*lead):
        return {"x": rng.rand(*lead, 16, 64).astype(np.float32),
                "y": rng.randint(0, 8, lead + (16, 1)).astype(np.int64)}

    with scope_guard(Scope()):
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", [64], dtype="float32")
            y = layers.data("y", [1], dtype="int64")
            logits = layers.fc(layers.fc(x, size=128, act="relu"), size=8)
            loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
            optimizer.SGD(0.1).minimize(loss)
        exe = pt.Executor()
        exe.run(startup)
        assert (exe.cache_hits, exe.cache_misses) == (0, 0)   # eager
        target = main
        if mesh is not None:
            strategy = BuildStrategy()
            strategy.mesh_axes = mesh
            target = CompiledProgram(main, strategy)
        for _ in range(n):
            if entry == "run":
                out = exe.run(target, feed=batch(), fetch_list=[loss])
            else:
                out = exe.run_steps(target, feed=batch(window),
                                    fetch_list=[loss])
            assert np.isfinite(np.asarray(out[0])).all()
        assert (exe.cache_misses, exe.cache_hits) == (1, n - 1)
