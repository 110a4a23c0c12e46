"""Observability battery: the obs spans engine + the end-to-end
distributed-tracing chain (ISSUE 12 tentpole).

Four tiers, every wait hard-bounded:

  * engine units — nesting/parentage, ring bound + dropped counter,
    disabled-is-free, header round trip, Chrome export validity,
    clock-offset probe against a live CoordServer;
  * executor — per-step phase spans with cache hit/miss annotation
    and the executor_step_seconds{kind=} histogram on the resilience
    metrics surface;
  * the propagation chain — one request through 2 routers + 2
    replicas (in-process fleet): a single trace_id spans
    client -> router -> replica with parentage intact, including a
    retry-on-sibling hop as two dispatch spans under one parent;
  * the REAL-process timeline proof — servingsvc router + replica
    processes with PADDLE_TPU_TRACE=1, spans pulled via /admin/trace,
    merged by tools/traceview.py into one valid Chrome-trace JSON in
    which one client request is visible across >= 3 processes with
    consistent parentage and clock-aligned timestamps.
"""
import contextlib
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.framework import obs, resilience
from paddle_tpu.framework.transport import CoordServer
from paddle_tpu.serving_fleet import (FleetClient, FleetRouter,
                                      ReplicaMember, http_json)

pytestmark = [pytest.mark.obs, pytest.mark.fleet]

WAIT_S = 20.0
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))


@pytest.fixture(autouse=True)
def _clean_obs():
    resilience.install(None)
    resilience.clear_events()
    obs.disable()
    obs.clear()
    obs.set_clock_offset(0.0)
    yield
    obs.disable()
    obs.clear()
    obs.set_clock_offset(0.0)
    resilience.install(None)
    resilience.clear_events()


def _wait(cond, what, timeout_s=WAIT_S):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.02)
    raise AssertionError("timed out waiting for %s" % what)


def _export_artifact(dirname, features=6, classes=3):
    from paddle_tpu.framework.scope import Scope, scope_guard
    with scope_guard(Scope()):
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", [features], dtype="float32")
            y = layers.softmax(layers.fc(x, classes))
        exe = pt.Executor()
        exe.run(startup)
        pt.save_inference_model(str(dirname), ["x"], [y], exe,
                                main_program=main, format="stablehlo",
                                batch_sizes=(1, 8))
    return str(dirname)


@pytest.fixture(scope="module")
def artifact(tmp_path_factory):
    return _export_artifact(tmp_path_factory.mktemp("obs_artifact"))


# ---------------------------------------------------------------------------
# engine units
# ---------------------------------------------------------------------------

def test_span_nesting_parentage_and_labels():
    obs.enable("unit")
    with obs.span("outer", k=1) as outer:
        assert obs.current() == (outer.trace, outer.id)
        with obs.span("inner") as inner:
            inner.set(extra="x")
        with pytest.raises(RuntimeError):
            with obs.span("failing"):
                raise RuntimeError("boom")
    got = {s["name"]: s for s in obs.spans()}
    assert set(got) == {"outer", "inner", "failing"}
    assert got["inner"]["parent"] == got["outer"]["id"]
    assert got["failing"]["parent"] == got["outer"]["id"]
    assert got["inner"]["trace"] == got["outer"]["trace"]
    assert got["outer"]["parent"] is None
    assert got["inner"]["labels"]["extra"] == "x"
    # an exception annotates the span instead of losing it
    assert got["failing"]["labels"]["error"] == "RuntimeError"
    for s in got.values():
        assert s["t1"] >= s["t0"]
    # inner nests temporally inside outer
    assert got["outer"]["t0"] <= got["inner"]["t0"]
    assert got["inner"]["t1"] <= got["outer"]["t1"]


def test_disabled_records_nothing_and_is_the_shared_noop():
    assert not obs.enabled()
    a = obs.span("x")
    b = obs.span("y", label=1)
    assert a is b                       # the no-op singleton
    with a:
        assert obs.current() is None
        assert obs.record("z", 0.0, 1.0) is None
    assert obs.spans() == []


def test_ring_bound_evicts_and_counts_dropped(monkeypatch):
    obs.enable("ring")
    # shrink the ring in place (capacity is fixed at import time)
    import collections
    monkeypatch.setattr(obs, "_ring", collections.deque(maxlen=8))
    for i in range(12):
        with obs.span("s%d" % i):
            pass
    assert len(obs.spans()) == 8
    assert obs.dropped_total() == 4
    # the overflow is loud on the resilience metrics surface
    text = resilience.metrics_text()
    assert "trace_spans_dropped_total 4" in text
    obs.clear()
    assert obs.dropped_total() == 0


def test_header_round_trip_and_malformed():
    obs.enable("hdr")
    with obs.span("root") as sp:
        h = obs.header()
        assert h == "%s:%s" % (sp.trace, sp.id)
    assert obs.parse_header(h) == (sp.trace, sp.id)
    for bad in (None, "", "nocolon", "a:b:c", 42):
        assert obs.parse_header(bad) == (None, None)
    assert obs.header() is None         # nothing open


def test_chrome_trace_merge_is_valid_and_multi_process():
    obs.enable("merge")
    with obs.span("a"):
        pass
    mine = obs.dump_dict()
    other = {"format": "paddle_tpu_trace", "version": 1,
             "service": "other", "pid": 99999, "clock_offset_s": 1.5,
             "dropped": 0,
             "spans": [{"trace": "t1", "id": "s1", "parent": None,
                        "name": "remote", "t0": 10.0, "t1": 11.0,
                        "labels": {}, "tid": "main"}]}
    trace = obs.chrome_trace([mine, other])
    json.dumps(trace)                   # valid JSON end to end
    evs = trace["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["pid"] for e in xs} == {os.getpid(), 99999}
    remote = [e for e in xs if e["name"] == "remote"][0]
    # the clock offset shifts exported timestamps (us)
    assert remote["ts"] == pytest.approx((10.0 + 1.5) * 1e6)
    assert remote["dur"] == pytest.approx(1e6)
    names = [e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"]
    assert "other" in names
    # every X event carries its trace context for viewer-side filters
    assert all("trace_id" in e["args"] and "span_id" in e["args"]
               for e in xs)


def test_clock_offset_probe_against_live_coordserver():
    with CoordServer(1) as srv:
        srv.start()
        from paddle_tpu.framework.transport import CoordClient
        client = CoordClient(srv.address, host_id=0)
        try:
            off = obs.probe_clock_offset(
                lambda cmd: client.call(cmd))
        finally:
            client.close()
    # same process, same clock: the offset is sub-second noise
    assert abs(off) < 1.0
    assert obs.clock_offset() == off


class _FakeAnnotation(object):
    """Stands in for jax.profiler.TraceAnnotation: records the order."""
    log = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))
        return False


def test_an_enabled_span_is_mirrored_as_a_trace_annotation(monkeypatch):
    """While obs is on, an open span enters an annotation of the same name
    (so a running profiler session sees it on its own clock), properly
    nested; retroactive records stay obs-only; the disabled path is still
    the shared no-op and builds no annotation."""
    monkeypatch.setattr(obs, "_annotation", _FakeAnnotation)
    monkeypatch.setattr(_FakeAnnotation, "log", [])
    assert obs.span("off") is obs.span("off2")
    with obs.span("off"):
        pass
    assert _FakeAnnotation.log == []
    obs.enable("unit")
    with obs.span("exec.step"):
        with obs.span("exec.feed"):
            pass
        obs.record("exec.compile", obs.now(), obs.now())
    assert _FakeAnnotation.log == [
        ("enter", "exec.step"), ("enter", "exec.feed"),
        ("exit", "exec.feed"), ("exit", "exec.step")]
    assert {s["name"] for s in obs.spans()} == {
        "exec.step", "exec.feed", "exec.compile"}


def test_the_mirror_is_jax_own_annotation_once_jax_is_imported(
        monkeypatch):
    import jax
    monkeypatch.setattr(obs, "_annotation", None)
    assert obs._annotation_class() is jax.profiler.TraceAnnotation
    obs.enable("unit")
    with obs.span("real") as sp:
        assert isinstance(sp.mirror, jax.profiler.TraceAnnotation)
    assert [s["name"] for s in obs.spans()] == ["real"]


def test_importing_obs_alone_does_not_import_jax():
    """Coordination servers and routers use obs without JAX: the module
    picks the annotation class up only if jax is already imported."""
    code = (
        "import importlib.util, sys\n"
        "spec = importlib.util.spec_from_file_location('obs_alone', %r)\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        "m.enable('alone')\n"
        "with m.span('a') as sp:\n"
        "    assert sp.mirror is None\n"
        "assert [s['name'] for s in m.spans()] == ['a']\n"
        "assert 'jax' not in sys.modules, 'obs imported jax'\n"
        "print('ok')\n" % os.path.join(ROOT, "paddle_tpu", "framework",
                                       "obs.py"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


# ---------------------------------------------------------------------------
# executor phases
# ---------------------------------------------------------------------------

def test_executor_phase_spans_and_step_histogram():
    from paddle_tpu import optimizer
    from paddle_tpu.framework.scope import Scope, scope_guard
    obs.enable("exec")
    with scope_guard(Scope()):
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", [4], dtype="float32")
            yv = layers.data("y", [1], dtype="int64")
            loss = layers.mean(layers.softmax_with_cross_entropy(
                layers.fc(x, 3), yv))
            optimizer.SGD(0.1).minimize(loss)
        exe = pt.Executor()
        exe.run(startup)
        feed = {"x": np.random.rand(4, 4).astype(np.float32),
                "y": np.zeros((4, 1), np.int64)}
        exe.run(main, feed=feed, fetch_list=[loss])
        exe.run(main, feed=feed, fetch_list=[loss])
    steps = obs.spans(name="exec.step")
    assert [s["labels"]["cache"] for s in steps] == ["miss", "hit"]
    compiles = obs.spans(name="exec.compile")
    assert len(compiles) == 1          # only the miss compiles
    assert compiles[0]["parent"] == steps[0]["id"]
    for name in ("exec.execute", "exec.writeback"):
        kids = obs.spans(name=name)
        assert len(kids) == 2
        assert {k["parent"] for k in kids} == {s["id"] for s in steps}
    # the histogram joins the resilience metrics surface
    tot = resilience.executor_step_totals()
    assert tot["total"]["count"] == 2
    assert tot["compile"]["count"] == 1
    assert tot["execute"]["count"] == 2
    text = resilience.metrics_text()
    assert 'executor_step_seconds_bucket{kind="execute"' in text
    assert 'executor_step_seconds_count{kind="total"} 2' in text


def test_run_steps_phases_share_one_exec_step_parent():
    """run_steps gets the same one-window-one-tree grouping as run():
    with NO ambient span open around the caller, the window's
    compile/execute/writeback spans still parent under a single
    exec.step root — not three unrelated root traces."""
    from paddle_tpu import optimizer
    from paddle_tpu.framework.scope import Scope, scope_guard
    obs.enable("exec")
    with scope_guard(Scope()):
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("x", [2, 4], "float32",
                            append_batch_size=False)
            y = layers.data("y", [2, 1], "float32",
                            append_batch_size=False)
            loss = layers.reduce_mean(layers.square(
                layers.fc(x, 1) - y))
            optimizer.SGD(0.1).minimize(loss)
        exe = pt.Executor()
        exe.run(startup)
        feed = {"x": np.random.rand(3, 2, 4).astype(np.float32),
                "y": np.zeros((3, 2, 1), np.float32)}
        exe.run_steps(main, feed=feed, fetch_list=[loss])
        exe.run_steps(main, feed=feed, fetch_list=[loss])
    steps = obs.spans(name="exec.step")
    assert [s["labels"]["cache"] for s in steps] == ["miss", "hit"]
    assert all(s["labels"]["entry"] == "run_steps" for s in steps)
    compiles = obs.spans(name="exec.compile")
    assert len(compiles) == 1          # only the miss compiles
    assert compiles[0]["parent"] == steps[0]["id"]
    for name in ("exec.execute", "exec.writeback"):
        kids = obs.spans(name=name)
        assert len(kids) == 2
        assert {k["parent"] for k in kids} == {s["id"] for s in steps}
    # one window = ONE trace id across all of its phases
    for s in steps:
        tree = [sp for sp in obs.spans(trace_id=s["trace"])]
        assert {sp["name"] for sp in tree} >= {
            "exec.step", "exec.execute", "exec.writeback"}


def _mlp_train_program(width=1024, depth=6, batch=256):
    from paddle_tpu import optimizer
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [batch, width], "float32",
                        append_batch_size=False)
        y = layers.data("y", [batch, 1], "float32",
                        append_batch_size=False)
        h = x
        for _ in range(depth):
            h = layers.fc(h, width, act="relu")
        loss = layers.reduce_mean(layers.square(layers.fc(h, 1) - y))
        optimizer.Adam(1e-3).minimize(loss)
    feed = {"x": np.random.rand(batch, width).astype(np.float32),
            "y": np.zeros((batch, 1), np.float32)}
    return main, startup, feed, loss


PHASES = ["exec.prepare", "exec.feed", "exec.prepare", "exec.execute",
          "exec.writeback", "exec.release"]


@pytest.mark.parametrize("records", [False, True],
                         ids=["", "step_records"])
@pytest.mark.parametrize("entry", ["run", "run_steps"])
def test_exec_step_children_tile_the_step(entry, records):
    """exec.step covers the call, preamble and frame exit included, and
    its phases (prepare, feed, prepare, compile, execute, writeback,
    release, and records where a layer registered a counter) tile it:
    each boundary is one reading of the clock, so what they leave out is
    the span's own open and close, under 1% of a step that does some
    milliseconds of work. exec.fetch sits inside exec.writeback, so that
    the scope writes are what is left; release and records are its
    siblings, never nested."""
    from paddle_tpu.framework.scope import Scope, scope_guard
    calls = []
    with scope_guard(Scope()):
        main, startup, feed, loss = _mlp_train_program()
        if records:
            weight = main.global_block().all_parameters()[0].name
            main.record_step_state("test.weight", weight, {"layer": 0},
                                   lambda w: {"size": int(w.size)})
        exe = pt.Executor()
        exe.run(startup)
        if entry == "run_steps":
            feed = {k: np.stack([v] * 3) for k, v in feed.items()}
        call = getattr(exe, entry)
        call(main, feed=feed, fetch_list=[loss])        # compiles
        obs.enable("exec")
        for _ in range(5):
            t_call = obs.now()
            call(main, feed=feed, fetch_list=[loss])
            calls.append((t_call, obs.now()))
    steps = obs.spans(name="exec.step")
    assert len(steps) == 5
    by_id = {}
    for s in obs.spans():
        by_id.setdefault(s["parent"], []).append(s)
    want = PHASES + (["exec.records"] if records else [])
    covers, of_call = [], []
    for step, (t_call, t_return) in zip(steps, calls):
        leaves = sorted(by_id[step["id"]], key=lambda k: k["t0"])
        assert [k["name"] for k in leaves] == want
        # one reading a boundary: a phase opens where the last one closed
        assert all(a["t1"] == b["t0"] for a, b in zip(leaves, leaves[1:]))
        assert leaves[-1]["t1"] == (step["t1"] if records
                                    else pytest.approx(step["t1"], abs=1e-3))
        covers.append(sum(k["t1"] - k["t0"] for k in leaves)
                      / (step["t1"] - step["t0"]))
        assert t_call <= step["t0"] and step["t1"] <= t_return
        of_call.append((step["t1"] - step["t0"]) / (t_return - t_call))
        wb = next(k for k in leaves if k["name"] == "exec.writeback")
        assert [k["name"] for k in by_id[wb["id"]]] == ["exec.fetch"]
        release = next(k for k in leaves if k["name"] == "exec.release")
        assert release["id"] not in by_id       # nothing nests in it
        if records:
            # the counters' spans, and nothing else, under exec.records
            mine = by_id[leaves[-1]["id"]]
            assert [(k["name"], k["labels"]) for k in mine] \
                == [("test.weight", {"layer": 0, "size": 1024 * 1024})]
            assert leaves[-1]["t0"] <= mine[0]["t0"] <= leaves[-1]["t1"]
    # the median step: one preempted step must not fail the suite
    assert sorted(covers)[len(covers) // 2] >= 0.99, covers
    assert sorted(of_call)[len(of_call) // 2] >= 0.99, of_call


def _counted_program():
    """A tiny train program whose two parameters are registered as step
    records."""
    from paddle_tpu import optimizer
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        loss = layers.reduce_mean(layers.square(layers.fc(x, 3)))
        optimizer.SGD(0.1).minimize(loss)
    for i, p in enumerate(main.global_block().all_parameters()):
        main.record_step_state("test.param", p.name, {"layer": i},
                               lambda v: {"size": int(v.size)})
    assert len(main.step_records) == 2
    return main, startup, {"x": np.ones((2, 4), np.float32)}, loss


def test_obs_off_opens_no_span_and_reads_no_step_record(monkeypatch):
    """With obs off the step's phases are the shared no-op: no _Span is
    built, and the registered counters are not fetched."""
    import jax
    from paddle_tpu.framework.scope import Scope, scope_guard

    def never(*a, **kw):
        raise AssertionError("reached with obs off")
    with scope_guard(Scope()):
        main, startup, feed, loss = _counted_program()
        exe = pt.Executor()
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])     # compiles
        monkeypatch.setattr(obs._Span, "__init__", never)
        monkeypatch.setattr(jax, "device_get", never)
        before = resilience.executor_step_totals()["total"]["count"]
        out = exe.run(main, feed=feed, fetch_list=[loss])
        stacked = {k: np.stack([v] * 2) for k, v in feed.items()}
        exe.run_steps(main, feed=stacked, fetch_list=[loss])
    assert np.isfinite(out[0]).all()
    assert obs.spans() == []
    # the always-on histograms still tick, from the same boundaries
    tot = resilience.executor_step_totals()
    assert tot["total"]["count"] == before + 2
    assert tot["execute"]["count"] == tot["writeback"]["count"] \
        == tot["total"]["count"]


def test_step_records_cost_one_device_get_a_step(monkeypatch):
    """Two registered counters, one host round trip: the observer fetches
    every registered array in ONE jax.device_get, under exec.records."""
    import jax
    from paddle_tpu.framework.scope import Scope, scope_guard
    fetched = []
    real = jax.device_get

    def counting(x):
        fetched.append(len(x))
        return real(x)
    with scope_guard(Scope()):
        main, startup, feed, loss = _counted_program()
        exe = pt.Executor()
        exe.run(startup)
        exe.run(main, feed=feed, fetch_list=[loss])     # compiles
        obs.enable("exec")
        monkeypatch.setattr(jax, "device_get", counting)
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[loss])
    assert fetched == [2, 2, 2]
    records = obs.spans(name="exec.records")
    assert len(records) == 3
    mine = obs.spans(name="test.param")
    assert sorted((s["labels"]["layer"], s["labels"]["size"])
                  for s in mine) == sorted([(0, 12), (1, 3)] * 3)
    assert {s["parent"] for s in mine} == {r["id"] for r in records}


def test_a_phase_closes_with_the_next_one_and_with_its_span():
    """_Span.phase: children that tile their parent, each boundary one
    reading (handed in or read once); an exception closes the open phase
    with its parent and labels both."""
    obs.enable("alone")
    with obs.span("whole") as sp:
        sp.phase("a", 10.0)
        with obs.span("inside_a"):
            pass
        sp.phase("b", 12.5)
        sp.phase(None, 13.0)
        assert obs.current()[1] == sp.id
        sp.phase("c")
    got = {s["name"]: s for s in obs.spans()}
    assert (got["a"]["t0"], got["a"]["t1"]) == (10.0, 12.5)
    assert (got["b"]["t0"], got["b"]["t1"]) == (12.5, 13.0)
    assert got["c"]["t1"] == got["whole"]["t1"]
    assert got["inside_a"]["parent"] == got["a"]["id"]
    assert {got[n]["parent"] for n in "abc"} == {got["whole"]["id"]}
    obs.clear()
    with pytest.raises(KeyError):
        with obs.span("whole") as sp:
            sp.phase("a")
            raise KeyError("x")
    assert obs.current() is None
    assert [(s["name"], s["labels"].get("error")) for s in obs.spans()] \
        == [("a", "KeyError"), ("whole", "KeyError")]
    obs.disable()
    obs.clear()
    obs.span("whole").phase("a", 1.0)       # the shared no-op's: nothing
    assert obs.spans() == [] and obs.current() is None


def test_the_straggler_detector_gets_the_steps_phases(monkeypatch):
    """An armed detector is fed the step's latency WITH its phases, all
    from the boundaries' one clock: the phases add up to the latency."""
    from paddle_tpu.framework import watchdog
    from paddle_tpu.framework.scope import Scope, scope_guard
    seen = []
    det = watchdog.enable_straggler_detection(warmup=1000)
    monkeypatch.setattr(
        det, "observe", lambda seconds, what="step", phases=None:
        seen.append((seconds, what, phases)))
    try:
        with scope_guard(Scope()):
            main, startup, feed, loss = _counted_program()
            exe = pt.Executor()
            exe.run(startup)
            exe.run(main, feed=feed, fetch_list=[loss])     # compiles
            exe.run(main, feed=feed, fetch_list=[loss])
            stacked = {k: np.stack([v] * 4) for k, v in feed.items()}
            exe.run_steps(main, feed=stacked, fetch_list=[loss])
    finally:
        watchdog.disable_straggler_detection()
    assert [what for _s, what, _p in seen] \
        == ["Executor.run", "Executor.run", "Executor.run_steps"]
    base = {"feed_prepare_s", "execute_s", "writeback_s", "release_s"}
    assert [set(p) for _s, _w, p in seen] \
        == [base | {"compile_s"}, base, base | {"compile_s"}]
    for (seconds, _what, phases), n_steps in zip(seen, (1, 1, 4)):
        assert all(v >= 0 for v in phases.values())
        assert sum(phases.values()) == pytest.approx(seconds * n_steps,
                                                     rel=1e-6)


# ---------------------------------------------------------------------------
# the propagation chain (in-process fleet)
# ---------------------------------------------------------------------------

def _fleet2x2(stack, artifact):
    """2 replicas + 2 routers on one auto-sized CoordServer."""
    srv = CoordServer(None, hb_deadline_s=2.0).start()
    stack.callback(srv.close)
    reps = []
    for i in range(2):
        rep = ReplicaMember(artifact, srv.address, 2, i, n_routers=2,
                            ctl_interval_s=0.05, hb_interval_s=0.1,
                            join_timeout_s=WAIT_S).start()
        stack.callback(rep.close)
        reps.append(rep)
    routers = []
    for rid in range(2):
        r = FleetRouter(srv.address, 2, router_id=rid, n_routers=2,
                        max_batch=8, batch_deadline_s=0.005,
                        ctl_interval_s=0.05, hb_interval_s=0.1,
                        poll_interval_s=0.03,
                        join_timeout_s=WAIT_S).start()
        stack.callback(r.close)
        routers.append(r)
    _wait(lambda: all(len(r.routable()) == 2 for r in routers),
          "both routers see both replicas")
    return srv, reps, routers


def test_trace_context_spans_client_router_replica(artifact):
    """ONE trace_id covers the whole request across client, router and
    replica legs, with parentage intact at every hop — and the
    router's slow-request exemplars carry the same trace id."""
    obs.enable("chain")
    with contextlib.ExitStack() as stack:
        _, _, routers = _fleet2x2(stack, artifact)
        client = FleetClient([r.url for r in routers],
                             request_deadline_s=15.0)
        obs.clear()
        resp = client.infer({"x": np.ones((1, 6), np.float32).tolist()})
        assert resp["replica"] in (0, 1)
        roots = obs.spans(name="client.infer")
        assert len(roots) == 1
        trace = roots[0]["trace"]
        tr = obs.spans(trace_id=trace)
        names = {s["name"] for s in tr}
        assert {"client.infer", "router.serve", "router.queue",
                "router.dispatch", "replica.serve"} <= names
        serve = [s for s in tr if s["name"] == "router.serve"][0]
        assert serve["parent"] == roots[0]["id"]
        rep = [s for s in tr if s["name"] == "replica.serve"][0]
        assert rep["parent"] == serve["id"]
        assert rep["labels"]["status"] == 200
        disp = [s for s in tr if s["name"] == "router.dispatch"]
        assert all(d["parent"] == serve["id"] for d in disp)
        assert disp[-1]["labels"]["outcome"] == "ok"
        q = [s for s in tr if s["name"] == "router.queue"][0]
        assert q["parent"] == serve["id"]
        # the serve span brackets queue + dispatch
        assert serve["t0"] <= q["t0"] and disp[-1]["t1"] <= serve["t1"] \
            + 0.05
        # slow-request exemplars expose (latency, trace id)
        slow = resilience.router_totals()["slow_requests"]
        assert any(e["trace"] == trace for e in slow)


def test_retry_on_sibling_is_two_dispatch_spans_under_one_parent(
        artifact):
    """Sever one replica's HTTP listener (its lease stays live, so the
    router keeps routing to it): the dispatch that lands on the dead
    endpoint retries on the sibling, and the trace shows BOTH attempts
    as dispatch spans under the same router.serve parent — the first
    unreachable, the second ok."""
    obs.enable("retry")
    with contextlib.ExitStack() as stack:
        _, reps, routers = _fleet2x2(stack, artifact)
        client = FleetClient([routers[0].url],
                             request_deadline_s=15.0)
        client.infer({"x": np.ones((1, 6), np.float32).tolist()})
        # kill the listener only — the member still heartbeats
        reps[0]._server.shutdown()
        reps[0]._server.server_close()
        found = None
        for _ in range(8):     # round-robin lands on the corpse soon
            obs.clear()
            client.infer({"x": np.ones((1, 6), np.float32).tolist()})
            root = obs.spans(name="client.infer")[-1]
            disp = [s for s in obs.spans(trace_id=root["trace"])
                    if s["name"] == "router.dispatch"]
            if len(disp) >= 2:
                found = disp
                break
        assert found, "no retry hop was ever traced"
        assert len({d["parent"] for d in found}) == 1
        outcomes = [d["labels"]["outcome"] for d in found]
        assert outcomes[0] == "unreachable" and outcomes[-1] == "ok", \
            outcomes
        replicas = {d["labels"]["replica"] for d in found}
        assert len(replicas) == 2      # two different replicas tried


def test_probe_obs_group_and_strict_overflow(monkeypatch, capsys):
    """serving_probe folds executor_step_seconds /
    trace_spans_dropped_total under "obs" and --strict fails on
    span-ring overflow (dropped spans = the timeline is lying)."""
    import serving_probe
    obs.enable("probe")
    resilience.observe_executor_step("execute", 0.003)
    with resilience.serve_metrics() as srv:
        summary = serving_probe.scrape_metrics(srv.url)
        assert "obs" in summary
        assert summary["obs"]["trace_spans_dropped_total"] == 0
        assert any(k.startswith("executor_step_seconds")
                   for k in summary["obs"])
        assert serving_probe.obs_overflow_flags(summary) == []
        # overflow the ring -> the strict flag fires
        import collections
        monkeypatch.setattr(obs, "_ring",
                            collections.deque(maxlen=2))
        for i in range(5):
            with obs.span("x%d" % i):
                pass
        summary = serving_probe.scrape_metrics(srv.url)
        assert summary["obs"]["trace_spans_dropped_total"] == 3
        flags = serving_probe.obs_overflow_flags(summary)
        assert flags and "overflow" in flags[0]


# ---------------------------------------------------------------------------
# the REAL-process timeline proof (acceptance criterion)
# ---------------------------------------------------------------------------

def _spawn_svc(args):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in (env.get("PYTHONPATH"), ROOT) if p])
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["PADDLE_TPU_TRACE"] = "1"
    return subprocess.Popen(
        [sys.executable, os.path.join(ROOT, "tools", "servingsvc.py")]
        + args, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, env=env)


def test_end_to_end_timeline_across_real_processes(artifact, tmp_path):
    """THE acceptance scenario: real servingsvc router + replica
    processes (PADDLE_TPU_TRACE=1) serve a traced client request;
    tools/traceview.py merges the client's own dump with live
    /admin/trace pulls from both processes into ONE valid Chrome-trace
    JSON where the request's spans cross 3 processes with consistent
    parentage and clock-aligned timestamps."""
    obs.enable("client")
    srv = CoordServer(2, hb_deadline_s=5.0).start()
    procs = []
    try:
        rep = _spawn_svc(["replica", "--coord", srv.address,
                          "--n-replicas", "1", "--replica-id", "0",
                          "--artifact", artifact,
                          "--ctl-interval-s", "0.05",
                          "--hb-interval-s", "0.1"])
        procs.append(rep)
        rep_line = json.loads(rep.stdout.readline())
        rout = _spawn_svc(["router", "--coord", srv.address,
                           "--n-replicas", "1",
                           "--ctl-interval-s", "0.05",
                           "--hb-interval-s", "0.1"])
        procs.append(rout)
        rout_line = json.loads(rout.stdout.readline())
        url = rout_line["url"]

        def ready():
            try:
                status, h = http_json("GET", url + "/healthz",
                                      timeout_s=2.0)
            except OSError:
                return False
            return status == 200 and len(h.get("replicas", {})) == 1

        _wait(ready, "real-process fleet routable")
        obs.clear()
        client = FleetClient([url], request_deadline_s=15.0)
        resp = client.infer({"x": np.ones((1, 6),
                                          np.float32).tolist()})
        assert resp["replica"] == 0
        trace_id = obs.spans(name="client.infer")[-1]["trace"]
        # merge: own dump file + live pulls from router and replica
        own = str(tmp_path / "client.json")
        obs.dump(own)
        out = str(tmp_path / "merged.json")
        import traceview
        rc = traceview.main([own, "--from",
                             "%s,%s" % (url, rep_line["addr"]),
                             "-o", out])
        assert rc == 0
        with open(out) as f:
            merged = json.load(f)
        evs = [e for e in merged["traceEvents"] if e["ph"] == "X"
               and e["args"].get("trace_id") == trace_id]
        by_pid = {}
        for e in evs:
            by_pid.setdefault(e["pid"], []).append(e)
        assert len(by_pid) >= 3, (
            "the trace must span >= 3 processes, saw pids %s"
            % sorted(by_pid))
        # consistent parentage across the hops
        by_span = {e["args"]["span_id"]: e for e in evs}
        roots = [e for e in evs if e["name"] == "client.infer"]
        serve = [e for e in evs if e["name"] == "router.serve"]
        repl = [e for e in evs if e["name"] == "replica.serve"]
        assert roots and serve and repl
        assert serve[0]["args"]["parent_id"] \
            == roots[0]["args"]["span_id"]
        assert repl[0]["args"]["parent_id"] \
            == serve[0]["args"]["span_id"]
        # distinct processes per leg
        assert len({roots[0]["pid"], serve[0]["pid"],
                    repl[0]["pid"]}) == 3
        # clock-aligned: each child's interval sits inside (or within
        # 100ms of) its parent's — same-host clocks + offset probe
        for child, parent in ((serve[0], roots[0]),
                              (repl[0], serve[0])):
            assert child["ts"] >= parent["ts"] - 1e5
            assert child["ts"] + child["dur"] \
                <= parent["ts"] + parent["dur"] + 1e5
        for p in procs:
            p.terminate()
            assert p.wait(timeout=15) == 0
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        srv.close()
