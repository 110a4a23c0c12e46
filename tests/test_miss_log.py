"""The miss log (`framework/executor.py`): where a step-cache miss's time
went, read from JAX's own compile events without touching what the miss
executes. One entry a miss whether obs is on or off; with obs on the four
stages become children of the step's `exec.execute`; a hit writes nothing
and fires no listener; the always-on histogram and the straggler event
take their compile seconds from the same entry."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, optimizer
from paddle_tpu.framework import executor, obs, resilience, watchdog
from paddle_tpu.framework.compiler import BuildStrategy, CompiledProgram
from paddle_tpu.framework.scope import Scope, scope_guard
from paddle_tpu.ops.registry import get_op

pytestmark = pytest.mark.obs

STAGES = ("trace_s", "lower_s", "backend_s", "first_run_s", "builder_s")
KEYS = {"entry", "program", "version", "t0", "t1", "cache",
        "cache_requests", "cache_hits", "retrieval_s", "compile_s"} \
    | set(STAGES)
EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")


def _deep_program(depth=12):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [8, 32], "float32", append_batch_size=False)
        h = x
        for _ in range(depth):
            h = layers.fc(h, 32, act="relu")
        loss = layers.reduce_mean(layers.square(layers.fc(h, 1)))
        optimizer.Adam(1e-3).minimize(loss)
    return main, startup, {"x": np.ones((8, 32), np.float32)}, loss


@pytest.fixture
def fresh():
    """An empty log, obs off and cleared, and both back as they were."""
    obs.disable()
    obs.clear()
    executor._misses.clear()
    with scope_guard(Scope()):
        yield
    obs.disable()
    obs.clear()
    executor._misses.clear()


@pytest.fixture
def heard():
    """Every jax.monitoring callback the process fires while the test
    runs, as (kind, event, ...) tuples."""
    seen = []

    def span(event, start, end, **kw):
        seen.append(("span", event, start, end))

    def event(event, **kw):
        seen.append(("event", event))

    def duration(event, seconds, **kw):
        seen.append(("duration", event, seconds))

    jax.monitoring.register_event_time_span_listener(span)
    jax.monitoring.register_event_listener(event)
    jax.monitoring.register_event_duration_secs_listener(duration)
    yield seen
    jax.monitoring.unregister_event_time_span_listener(span)
    jax.monitoring.unregister_event_listener(event)
    jax.monitoring.unregister_event_duration_listener(duration)


def _run_once(exe, main, feed, loss):
    out = exe.run(main, feed=feed, fetch_list=[loss])
    assert np.isfinite(out[0]).all()


@pytest.mark.parametrize("on", [False, True], ids=["obs-off", "obs-on"])
def test_a_miss_writes_one_entry_and_with_obs_on_its_four_children(
        fresh, on):
    main, startup, feed, loss = _deep_program()
    exe = pt.Executor()
    exe.run(startup)
    assert executor.miss_log() == []            # the eager startup: no miss
    if on:
        obs.enable("exec")
    _run_once(exe, main, feed, loss)
    log = executor.miss_log()
    assert len(log) == 1 and set(log[0]) == KEYS
    miss = log[0]
    assert miss["entry"] == "run"
    assert (miss["program"], miss["version"]) == (id(main), main._version)
    assert all(miss[k] > 0 for k in STAGES) and miss["t1"] > miss["t0"]
    assert miss["compile_s"] == pytest.approx(
        sum(miss[k] for k in STAGES) - miss["first_run_s"])
    # the stages and the builder account for the miss: what is left is
    # the Python between JAX's stages
    assert sum(miss[k] for k in STAGES) == pytest.approx(
        miss["t1"] - miss["t0"], rel=0.05)
    children = [s for s in obs.spans() if s["name"] in (
        "exec.trace", "exec.lower", "exec.backend", "exec.first_run")]
    if not on:
        assert obs.spans() == []
        return
    assert [s["name"] for s in sorted(children, key=lambda s: s["t0"])] \
        == ["exec.trace", "exec.lower", "exec.backend", "exec.first_run"]
    execute, = [s for s in obs.spans(name="exec.execute")]
    compile_, = obs.spans(name="exec.compile")
    assert {s["parent"] for s in children} == {execute["id"]}
    assert all(execute["t0"] - 1e-3 <= s["t0"] <= s["t1"]
               <= execute["t1"] + 1e-3 for s in children)
    covered = sum(s["t1"] - s["t0"] for s in children)
    assert covered == pytest.approx(execute["t1"] - execute["t0"], rel=0.05)
    by_name = {s["name"]: s for s in children}
    assert by_name["exec.trace"]["labels"]["seconds"] == miss["trace_s"]
    assert by_name["exec.backend"]["labels"]["cache"] == miss["cache"]
    assert "retrieval_s" in by_name["exec.backend"]["labels"]
    # the phase boundaries stay where they were: exec.compile is the
    # builder, and the log's t0, t1 are its start and exec.execute's end
    assert compile_["t1"] - compile_["t0"] == pytest.approx(
        miss["builder_s"])
    assert (compile_["t0"], execute["t1"]) == (miss["t0"], miss["t1"])


def test_hits_write_nothing_and_fire_no_listener(fresh, heard):
    main, startup, feed, loss = _deep_program(depth=2)
    exe = pt.Executor()
    exe.run(startup)
    _run_once(exe, main, feed, loss)
    assert any(kind == "span" for kind, *_ in heard)    # the miss was heard
    log = executor.miss_log()
    del heard[:]
    for _ in range(5):
        _run_once(exe, main, feed, loss)
    assert (exe.cache_misses, exe.cache_hits) == (1, 5)
    assert heard == []              # a steady step fires no JAX event
    assert executor.miss_log() == log
    assert getattr(executor._miss_tls, "open", None) is None


def test_events_outside_a_miss_are_dropped(fresh):
    main, startup, feed, loss = _deep_program(depth=2)
    exe = pt.Executor()
    exe.run(startup)
    _run_once(exe, main, feed, loss)            # the listeners are in
    log = executor.miss_log()
    jax.jit(lambda v: v * 3.0 + 1.0)(np.ones(5, np.float32))  # compiles
    assert executor.miss_log() == log


def test_a_jitted_function_inside_the_step_is_counted_once(
        fresh, heard, monkeypatch):
    """A nested jit fires its own trace event inside the outer one's: the
    stage is the union of the intervals, not their sum."""
    relu = get_op("relu")
    inner = relu.fn

    def with_nested_jit(*a, **kw):
        nested = jax.jit(lambda v: v + 0.0)     # fresh: traced every call
        return jax.tree_util.tree_map(nested, inner(*a, **kw))

    monkeypatch.setattr(relu, "fn", with_nested_jit)
    main, startup, feed, loss = _deep_program(depth=4)
    exe = pt.Executor()
    exe.run(startup)
    del heard[:]
    _run_once(exe, main, feed, loss)
    traces = [(s, e) for kind, event, s, e in
              (h for h in heard if h[0] == "span") if event == EVENTS[0]]
    outer = max(traces, key=lambda se: se[1] - se[0])
    nested = [se for se in traces if se != outer
              and outer[0] <= se[0] and se[1] <= outer[1]]
    assert len(nested) >= 4                     # the forward's four relus
    miss, = executor.miss_log()
    assert miss["trace_s"] < sum(e - s for s, e in traces)
    assert miss["trace_s"] == pytest.approx(outer[1] - outer[0], rel=0.02)


def test_stages_are_disjoint_unions():
    merged = executor._merged([(0, 2), (1, 3), (5, 6), (5.5, 5.8)])
    assert merged == [[0, 3], [5, 6]]
    assert executor._without(merged, [[2, 5.5]]) == [[0, 2], [5.5, 6]]
    assert executor._without(merged, [[-1, 10]]) == []
    assert executor._without(merged, []) == merged
    assert executor._without([[0, 10]], [[1, 2], [3, 4]]) \
        == [[0, 1], [2, 3], [4, 10]]


def test_the_histogram_and_the_straggler_event_hold_the_stages(
        fresh, monkeypatch):
    """On a miss `kind="compile"` and `compile_s` are the builder and the
    three compile stages, `execute_s` the rest of that call; the phases
    still add up to the latency."""
    seen = []
    det = watchdog.enable_straggler_detection(warmup=1000)
    monkeypatch.setattr(
        det, "observe", lambda seconds, what="step", phases=None:
        seen.append((seconds, phases)))
    resilience.clear_exec()
    try:
        main, startup, feed, loss = _deep_program()
        exe = pt.Executor()
        exe.run(startup)
        _run_once(exe, main, feed, loss)
        _run_once(exe, main, feed, loss)
    finally:
        watchdog.disable_straggler_detection()
    miss, = executor.miss_log()
    (latency, phases), (_hit_latency, hit_phases) = seen
    assert phases["compile_s"] == miss["compile_s"] > phases["execute_s"]
    assert phases["compile_s"] > 10 * miss["builder_s"]
    assert phases["execute_s"] >= miss["first_run_s"] - 1e-6
    assert sum(phases.values()) == pytest.approx(latency, rel=1e-6)
    assert "compile_s" not in hit_phases
    totals = resilience.executor_step_totals()
    assert totals["compile"]["count"] == 1
    assert totals["compile"]["sum"] == pytest.approx(miss["compile_s"])
    assert totals["execute"]["count"] == 2
    assert totals["execute"]["sum"] == pytest.approx(
        phases["execute_s"] + hit_phases["execute_s"])


def test_run_steps_and_a_compiled_program_log_their_entry(fresh):
    main, startup, feed, loss = _deep_program(depth=2)
    exe = pt.Executor()
    exe.run(startup)
    stacked = {k: np.stack([v] * 3) for k, v in feed.items()}
    exe.run_steps(main, feed=stacked, fetch_list=[loss])
    exe.run_steps(main, feed=stacked, fetch_list=[loss])        # a hit
    strategy = BuildStrategy()
    strategy.mesh_axes = {"dp": 2}
    compiled = CompiledProgram(main, strategy)
    exe.run(compiled, feed=feed, fetch_list=[loss])
    exe.run_steps(compiled, feed=stacked, fetch_list=[loss])
    log = executor.miss_log()
    assert [m["entry"] for m in log] \
        == ["run_steps", "compiled", "compiled"]
    assert all(m["trace_s"] > 0 and m["backend_s"] > 0 for m in log)
    assert exe.cache_misses == 3


def test_the_log_keeps_the_last_32_misses_oldest_first(fresh):
    main, startup, feed, loss = _deep_program(depth=1)
    exe = pt.Executor()
    exe.run(startup)
    for _ in range(3):
        exe.run(main, feed=feed, fetch_list=[loss], use_program_cache=False)
    log = executor.miss_log()
    assert len(log) == 3 and exe.cache_misses == 3
    assert [m["t0"] for m in log] == sorted(m["t0"] for m in log)
    assert executor._misses.maxlen == 32
    log[0]["entry"] = "mine"                    # a copy: the log's is safe
    assert executor.miss_log()[0]["entry"] == "run"


def test_a_miss_that_raises_leaves_no_mark(fresh):
    main, startup, feed, loss = _deep_program(depth=1)
    exe = pt.Executor()
    exe.run(startup)
    with pytest.raises(Exception):
        exe.run(main, feed={"x": np.ones((8, 32), np.float32),
                            "nobody": np.ones(3, np.float32)},
                fetch_list=["no_such_var"])
    assert getattr(executor._miss_tls, "open", None) is None
    assert executor.miss_log() == []
    _run_once(exe, main, feed, loss)
    assert len(executor.miss_log()) == 1


_CHILD = """
import json, sys
import numpy as np
import jax
import paddle_tpu as pt
from paddle_tpu import layers, optimizer
from paddle_tpu.framework import executor
main, startup = pt.Program(), pt.Program()
with pt.program_guard(main, startup):
    x = layers.data("x", [8, 16], "float32", append_batch_size=False)
    loss = layers.reduce_mean(layers.square(layers.fc(x, 4)))
    optimizer.SGD(0.1).minimize(loss)
exe = pt.Executor()
exe.run(startup)
exe.run(main, feed={"x": np.ones((8, 16), np.float32)}, fetch_list=[loss])
print(json.dumps(executor.miss_log()[-1]))
"""


def _child(cache_dir):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    out = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_cache_reads_miss_then_hit_over_two_processes_and_off_without(
        tmp_path):
    first = _child(tmp_path / "cache")
    second = _child(tmp_path / "cache")
    without = _child(None)
    assert (first["cache"], first["cache_hits"]) == ("miss", 0)
    assert first["cache_requests"] >= 1
    assert second["cache"] == "hit"
    assert second["cache_hits"] == second["cache_requests"] >= 1
    assert second["retrieval_s"] > 0 and first["retrieval_s"] == 0
    assert (without["cache"], without["cache_requests"],
            without["cache_hits"]) == ("off", 0, 0)
    assert without["backend_s"] > 0
