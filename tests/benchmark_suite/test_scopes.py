"""The program's own names in the traced window: role/op scopes and kernel
names on the device's operations, the Executor's phase spans on the host's
thread line. Arithmetic on hand-made event lists; the wire-format reader,
the seven readers and the profiler's table on two traces recorded on a TPU
v5e: data/tiny_tpu.xplane.pb (PR 23: a bare jitted function, no scopes) and
data/tiny_exec_tpu.xplane.pb.gz (PR 24: a 2-layer GPT-style program with
recompute and the flash kernels, 3 steps through `Executor.run` with obs
on, inside `bench.traced` / `bench.exe_run`; data/record_tiny_exec.py
recorded it)."""
import gzip
import os
import types

import pytest

from benchmark import cells, trace_reduce
from benchmark.layer_metrics import _scopes

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BARE = os.path.join(DATA, "tiny_tpu.xplane.pb")
NEW_METRICS = ["fwd_device_ms", "bwd_device_ms", "opt_device_ms",
               "unscoped_device_pct", "idle_feed_ms", "idle_dispatch_ms",
               "idle_fetch_ms"]
MS = 1e6    # hand-made times are in ms, events in ns


# ---------------------------------------------------------------------------
# names
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tf_op,want", [
    ("jit(step)/forward/mul/jvp()/dot_general:",
     ("forward", "mul", None)),
    ("jit(step)/backward/mul/transpose(jvp())/dot_general",
     ("backward", "mul", None)),
    ("jit(step)/backward/layer_norm/transpose(jvp(jit(_var)))/reduce_sum",
     ("backward", "layer_norm", None)),
    ("jit(step)/backward/relu/transpose(forward/relu)/jvp()/select_n",
     ("backward", "relu", None)),
    ("jit(step)/optimize/adam/sub", ("optimize", "adam", None)),
    ("jit(step)/lr_sched/increment/add", ("lr_sched", "increment", None)),
    # a recompute segment: the role is the outermost scope's, the op type
    # the innermost's, through checkpoint / rematted_computation
    ("jit(step)/forward/remat_block/jvp(forward/mul)/dot_general",
     ("forward", "mul", None)),
    ("jit(step)/backward/remat_block/transpose(jvp(forward/remat_block))/"
     "jvp()/checkpoint/rematted_computation/forward/tanh/tanh",
     ("backward", "tanh", None)),
    ("jit(step)/backward/remat_block/transpose(jvp(forward/remat_block))/"
     "jvp()/checkpoint/forward/scaled_dot_product_attention/flash_bwd_dkv/"
     "pallas_call:", ("backward", "scaled_dot_product_attention",
                      "flash_bwd_dkv")),
    # two op_names joined by XLA: the first one's role
    ("jit(step)/backward/square/transpose(jvp())/mul;jit(step)/forward/"
     "reduce_mean/jvp()/div", ("backward", "reduce_mean", None)),
    ("jit(<lambda>)/dot_general:", (None, None, None)),
    ("jit(f)/layer_norm_fwd/pallas_call", (None, None, "layer_norm_fwd")),
    ("", (None, None, None)),
    (None, (None, None, None)),
])
def test_role_type_and_kernel_are_parsed_from_the_op_name(tf_op, want):
    assert _scopes.parse_scope(tf_op) == want


@pytest.mark.parametrize("tf_op,row", [
    ("jit(step)/forward/mul/jvp()/dot_general:", "forward/mul"),
    ("jit(step)/backward/remat_block/transpose(jvp(forward/remat_block))/"
     "jvp()/checkpoint/rematted_computation/forward/"
     "scaled_dot_product_attention/flash_fwd/pallas_call:",
     "backward/flash_fwd"),
    ("jit(f)/layer_norm_fwd/pallas_call", "layer_norm_fwd"),
    ("", "unscoped"),
])
def test_a_table_row_is_role_and_type_and_a_kernel_goes_by_its_name(tf_op,
                                                                    row):
    """The program's own reader (the profiler's table) names rows by the
    same rule."""
    from paddle_tpu.framework import xplane
    assert xplane.scope_row(tf_op) == row


# ---------------------------------------------------------------------------
# arithmetic on hand-made lists
# ---------------------------------------------------------------------------

FWD = "jit(step)/forward/mul/jvp()/dot_general:"
BWD = "jit(step)/backward/mul/transpose(jvp())/dot_general:"
OPT = "jit(step)/optimize/adam/sub:"
WHILE = "jit(step)/forward/while_loop/while:"
BODY = "jit(step)/forward/while_loop/while/body/forward/elementwise_add/add:"


def _step_ops(t0):
    """One step's operations from t0 (ms): a forward while 0-30 holding a
    body op 5-15, backward 30-70, optimize 70-85, an unnamed copy 85-95;
    the program runs t0..t0+100, so 5 ms of it are idle."""
    rows = [("%while.1", 0, 30, WHILE), ("%add.2", 5, 15, BODY),
            ("%fusion.3", 30, 70, BWD), ("%fusion.4", 70, 85, OPT),
            ("%copy.5", 85, 95, "")]
    return [(n, (t0 + s) * MS, (t0 + e) * MS, tf) for n, s, e, tf in rows]


def _hand_trace(spans=True):
    host = [("bench.traced", 0, 500 * MS)]
    if spans:
        for t0 in (60, 250):
            host += [
                ("bench.exe_run", t0 * MS, (t0 + 160) * MS),
                ("exec.step", (t0 + 1) * MS, (t0 + 159) * MS),
                ("exec.feed", (t0 + 1) * MS, (t0 + 21) * MS),
                ("exec.prepare", (t0 + 21) * MS, (t0 + 26) * MS),
                ("exec.execute", (t0 + 26) * MS, (t0 + 50) * MS),
                ("exec.writeback", (t0 + 50) * MS, (t0 + 159) * MS),
                ("exec.fetch", (t0 + 52) * MS, (t0 + 158) * MS)]
    return {"devices": {0: {
        "ops": _step_ops(100) + _step_ops(300),
        "modules": [("jit_step(1)", 100 * MS, 200 * MS),
                    ("jit_step(1)", 300 * MS, 400 * MS),
                    ("jit_convert(2)", 10 * MS, 11 * MS)]}},
        "host": {"python3": host, "other": [("noise", 0, 1)]}}


def test_each_instant_goes_to_the_innermost_operation():
    got = _scopes.exclusive_ns(
        [("a", 0, 10), ("b", 2, 4), ("c", 3, 4), ("d", 12, 15),
         ("a", 14, 20)], 1, 18)
    assert got == {"a": 11.0, "b": 1.0, "c": 1.0, "d": 2.0}
    assert sum(got.values()) == trace_reduce.total(trace_reduce.busy_union(
        [("x", 0, 10), ("x", 12, 20)], 1, 18))


def test_the_role_split_adds_up_to_the_steps_device_time():
    trace = _hand_trace()
    split = _scopes.role_split(trace)
    assert split["fwd_ms"] == pytest.approx(30.0)
    assert split["bwd_ms"] == pytest.approx(40.0)
    assert split["opt_ms"] == pytest.approx(15.0)
    assert split["unscoped_ms"] == pytest.approx(10.0)
    assert split["unscoped_pct"] == pytest.approx(100.0 * 10 / 95)
    assert split["steps"] == 2
    dev = trace["devices"][0]
    step_ms = [ns / MS for ns in trace_reduce.per_step_busy_ns(
        [ev[:3] for ev in dev["ops"]], dev["modules"][:2])]
    assert step_ms == [95.0, 95.0]      # what device_step_ms reads
    assert split["fwd_ms"] + split["bwd_ms"] + split["opt_ms"] \
        + split["unscoped_ms"] == pytest.approx(step_ms[0])
    assert split["step_ms"] == pytest.approx(step_ms[0])


def test_a_program_without_scopes_has_no_role_split():
    trace = _hand_trace()
    dev = trace["devices"][0]
    dev["ops"] = [(n, s, e, "jit(step)/dot_general:")
                  for n, s, e, _tf in dev["ops"]]
    assert _scopes.role_split(trace) is None


def test_idle_time_is_split_by_the_executor_span_it_falls_under():
    idle = _scopes.idle_split(_hand_trace())
    # gaps: 0-100, 195-300, 395-500 = 310 ms over 2 steps
    assert idle["idle_ms"] == pytest.approx(155.0)
    # feed+prepare 61-86 and 251-276; execute 86-110 and 276-300 (the
    # device starts at 100 and 300); writeback 110-219 and 300-409 (the
    # device went idle at 195 and 395); the rest is the loop outside
    assert idle["feed_ms"] == pytest.approx((25 + 25) / 2.0)
    assert idle["dispatch_ms"] == pytest.approx((14 + 24) / 2.0)
    assert idle["fetch_ms"] == pytest.approx((24 + 14) / 2.0)
    assert idle["feed_ms"] + idle["dispatch_ms"] + idle["fetch_ms"] \
        <= idle["idle_ms"]


def test_nothing_to_read_gives_none():
    no_spans = _hand_trace(spans=False)
    assert _scopes.idle_split(no_spans) is None     # obs off / the parent
    assert _scopes.role_split(no_spans) is not None
    no_device = dict(_hand_trace(), devices={})
    assert _scopes.role_split(no_device) is None
    assert _scopes.idle_split(no_device) is None
    no_window = dict(_hand_trace(), host={"python3": []})
    assert _scopes.role_split(no_window) is None
    assert _scopes.idle_split(no_window) is None


# ---------------------------------------------------------------------------
# the recorded traces
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def exec_root(tmp_path_factory):
    """A checkout-shaped directory whose `.bench_trace/tiny.cell/` holds
    the recorded trace, unpacked, where the harness would have left it."""
    root = tmp_path_factory.mktemp("traced")
    where = os.path.join(str(root), ".bench_trace", "tiny.cell", "plugins",
                         "profile", "2026_09_27")
    os.makedirs(where)
    with gzip.open(os.path.join(DATA, "tiny_exec_tpu.xplane.pb.gz")) as f:
        with open(os.path.join(where, "host.xplane.pb"), "wb") as out:
            out.write(f.read())
    return str(root)


@pytest.fixture(scope="module")
def EXEC(exec_root):
    return os.path.join(exec_root, ".bench_trace", "tiny.cell")


@pytest.fixture(params=["bare", "exec"])
def path(request, EXEC):
    return {"bare": BARE, "exec": EXEC}[request.param]


def test_the_wire_reader_agrees_with_profile_data(path):
    """Same planes, lines, events and names; times within 2 ns (ProfileData
    cuts start and duration to whole ns, each on its own)."""
    import jax.profiler
    path = _scopes.find_xplane(path)
    data = jax.profiler.ProfileData.from_file(path)
    mine = _scopes.read_planes(path)
    planes = list(data.planes)
    assert [p.name for p in planes] == [p["name"] for p in mine]
    seen = 0
    for plane, got in zip(planes, mine):
        lines = list(plane.lines)
        assert [ln.name for ln in lines] == [ln["name"]
                                             for ln in got["lines"]]
        for line, got_line in zip(lines, got["lines"]):
            events = list(line.events)
            assert len(events) == len(got_line["events"])
            for ev, (name, start, end, _stats) in zip(events,
                                                      got_line["events"]):
                assert ev.name == name
                assert abs(ev.start_ns - start) < 2
                assert abs(ev.start_ns + ev.duration_ns - end) < 2
                seen += 1
    assert seen > 100


def test_read_xplane_is_trace_reduces_with_tf_op(path):
    mine = _scopes.read_xplane(path)
    ref = trace_reduce.read_xplane(path)
    assert sorted(mine["devices"]) == sorted(ref["devices"])
    for dev in ref["devices"]:
        for key in ("ops", "modules"):
            a, b = mine["devices"][dev][key], ref["devices"][dev][key]
            assert [ev[0] for ev in a] == [ev[0] for ev in b]
            assert all(abs(x[1] - y[1]) < 2 and abs(x[2] - y[2]) < 2
                       for x, y in zip(a, b))
    assert sorted(mine["host"]) == sorted(ref["host"])
    # the program's reader of the same file (paddle_tpu keeps its own)
    from paddle_tpu.framework import xplane
    theirs = xplane.device_ops(path)
    assert theirs == {dev: mine["devices"][dev]["ops"]
                      for dev in mine["devices"]}


def test_the_bare_trace_names_no_role_and_no_span():
    trace = _scopes.read_xplane(BARE)
    assert any(op[3] == "jit(<lambda>)/dot_general:"
               for op in trace["devices"][0]["ops"])
    assert _scopes.role_split(trace) is None
    assert _scopes.idle_split(trace) is None


def test_the_recorded_step_splits_by_role_and_the_kernels_have_names(EXEC):
    trace = _scopes.read_xplane(EXEC)
    split = _scopes.role_split(trace)
    assert split["steps"] == 3
    for key in ("fwd_ms", "bwd_ms", "opt_ms"):
        assert split[key] > 0, key
    reduced = trace_reduce.reduce_trace(trace_reduce.read_xplane(EXEC))
    total = split["fwd_ms"] + split["bwd_ms"] + split["opt_ms"] \
        + split["unscoped_pct"] / 100.0 * reduced["step_busy_ms"]
    assert total == pytest.approx(reduced["step_busy_ms"], rel=0.01)
    # kernels by the program's names; the recomputed forward under backward
    found = {_scopes.parse_scope(op[3]) for op in trace["devices"][0]["ops"]}
    for want in (("forward", "scaled_dot_product_attention", "flash_fwd"),
                 ("backward", "scaled_dot_product_attention", "flash_fwd"),
                 ("backward", "scaled_dot_product_attention",
                  "flash_bwd_dkv"),
                 ("backward", "scaled_dot_product_attention",
                  "flash_bwd_dq"),
                 ("forward", "mul", None), ("backward", "mul", None),
                 ("optimize", "adam", None)):
        assert want in found, (want, sorted(map(str, found)))
    kinds = {trace_reduce.op_kind(op[0])
             for op in trace["devices"][0]["ops"]}
    assert {"custom-call:flash_fwd", "custom-call:flash_bwd_dkv",
            "custom-call:flash_bwd_dq"} <= kinds


def test_the_recorded_idle_gaps_fall_under_the_executor_spans(EXEC):
    trace = _scopes.read_xplane(EXEC)
    idle = _scopes.idle_split(trace)
    parts = idle["feed_ms"] + idle["dispatch_ms"] + idle["fetch_ms"]
    assert 0 < parts <= idle["idle_ms"] * (1 + 1e-9)
    assert idle["feed_ms"] > 0 and idle["dispatch_ms"] > 0
    reduced = trace_reduce.reduce_trace(trace_reduce.read_xplane(EXEC))
    assert idle["idle_ms"] * 3 == pytest.approx(
        reduced["idle_share"] * reduced["window_s"] * 1e3, rel=0.01)
    # trace_reduce names the gaps after the innermost event: now an
    # exec.* span (or a runtime event inside one), no longer bench.exe_run
    names = [name for name, _s in reduced["idle_gaps"][:3]]
    assert all(n != "bench.traced>bench.exe_run" for n in names), names


# ---------------------------------------------------------------------------
# through the readers, as the harness calls them
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def exec_record(exec_root, EXEC):
    """A run's record whose traced window is the recorded trace."""
    cell = types.SimpleNamespace(root=exec_root, name="tiny.cell")
    traced = trace_reduce.reduce_trace(trace_reduce.read_xplane(EXEC))
    return {"cell": cell, "traced": traced}


def _reader(metric):
    return cells.Cell("bert-base.s128-b256").layer_reader(metric)


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_each_new_metric_reads_the_recorded_trace(exec_record, metric):
    value = _reader(metric).read(exec_record)
    assert value is not None and value >= 0
    if metric == "unscoped_device_pct":
        assert value < 100


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_each_new_metric_is_left_out_without_a_device_plane(metric):
    cell = types.SimpleNamespace(root="/nonexistent", name="tiny.cell")
    assert _reader(metric).read({"cell": cell, "traced": None}) is None


def test_the_sum_rules_hold_through_the_readers(exec_record):
    read = {m: _reader(m).read(exec_record) for m in NEW_METRICS}
    step = _reader("device_step_ms").read(exec_record)
    total = read["fwd_device_ms"] + read["bwd_device_ms"] \
        + read["opt_device_ms"] + read["unscoped_device_pct"] / 100 * step
    assert total == pytest.approx(step, rel=0.01)
    traced = exec_record["traced"]
    idle_ms_a_step = traced["idle_share"] * traced["window_s"] * 1e3 \
        / traced["steps_seen"]
    assert read["idle_feed_ms"] + read["idle_dispatch_ms"] \
        + read["idle_fetch_ms"] <= idle_ms_a_step * 1.001
    # the file is parsed once a run: the second reader found it cached
    assert set(exec_record["_scopes"]) == {"trace", "roles", "idle"}


def test_every_new_entry_has_its_reader_and_its_layer():
    import json
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-7:] == NEW_METRICS
    for name in NEW_METRICS:
        assert entries[name]["moves"] == "tokens_per_s_per_chip"
        assert "workloads" not in entries[name]
        assert os.path.exists(os.path.join(
            cells.ROOT, "benchmark", "layer_metrics", name + ".py"))
    assert {entries[n]["layer"] for n in NEW_METRICS[:4]} == {"Step program"}
    assert {entries[n]["layer"] for n in NEW_METRICS[4:]} \
        == {"Executor host path"}
    assert {entries[n]["source"] for n in NEW_METRICS[:4]} \
        == {"device_trace"}
    assert {entries[n]["source"] for n in NEW_METRICS[4:]} \
        == {"program_span"}


# ---------------------------------------------------------------------------
# the operator's table (paddle_tpu.profiler) on the same trace
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sorted_key,column", [
    (None, 2), ("total", 2), ("calls", 1), ("ave", 3), ("max", 4),
    ("min", 5)])
def test_the_profilers_table_is_sorted_and_adds_up_to_busy_time(
        EXEC, sorted_key, column):
    from paddle_tpu import profiler
    rows = profiler.op_table(EXEC, sorted_key)
    values = [r[column] for r in rows]
    assert values == sorted(values, reverse=True)
    names = [r[0] for r in rows]
    assert {"forward/flash_fwd", "backward/flash_fwd",
            "backward/flash_bwd_dkv", "backward/flash_bwd_dq",
            "forward/mul", "backward/mul", "optimize/adam"} <= set(names)
    assert all("/" in n or n == "unscoped" for n in names), names
    ops = trace_reduce.read_xplane(EXEC)["devices"][0]["ops"]
    busy_ms = trace_reduce.total(trace_reduce.busy_union(ops)) / 1e6
    assert sum(r[2] for r in rows) == pytest.approx(busy_ms, rel=0.02)
    assert sum(r[6] for r in rows) == pytest.approx(100.0)
    for _name, calls, total, ave, mx, mn, _share in rows:
        assert ave * calls == pytest.approx(total)
        assert mn <= ave <= mx


def test_the_table_prints_and_a_trace_without_a_device_says_so(capsys,
                                                                EXEC):
    from paddle_tpu import profiler
    profiler.print_table(profiler.op_table(EXEC), top_k=5)
    out = capsys.readouterr().out.splitlines()
    assert out[0].split() == ["Role/Op", "Calls", "Total(ms)", "Ave(ms)",
                              "Share%"]
    assert len(out) == 7 and out[-1].startswith("device busy")
    profiler.print_table([])
    assert "no device operations" in capsys.readouterr().out
    with pytest.raises(ValueError):
        profiler.op_table(EXEC, "bogus")
