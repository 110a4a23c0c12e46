"""The account of the host's time in a step (`layer_metrics/_account.py`):
the six idle buckets add up to the idle time, each reader reads its own,
the skew reader catches a device line that sits early or late against the
host's, and the two whole-window readers find the one slow step. On
hand-made traces (as `test_scopes.py` builds them) and on the trace
recorded on a TPU v5e in PR 24, whose program had neither `exec.release`
nor `exec.records`."""
import gzip
import json
import os
import types

import pytest

from benchmark import cells, trace_reduce
from benchmark.layer_metrics import _account, _scopes

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e6    # hand-made times are in ms, events in ns
NEW = ["idle_release_ms", "idle_records_ms", "idle_unnamed_ms",
       "host_device_skew_ms", "step_wall_max_over_median",
       "worst_step_wait_pct"]
# one step's phases from the step's start (ms); exec.fetch inside writeback
PHASES = [("exec.prepare", 1, 3), ("exec.feed", 3, 23),
          ("exec.prepare", 23, 26), ("exec.execute", 26, 50),
          ("exec.writeback", 50, 150), ("exec.fetch", 52, 149),
          ("exec.release", 150, 160), ("exec.records", 160, 169)]


def _trace(shift=0.0, leave_out=()):
    """Two steps at 60 and 250 ms inside a window of 0..500. The program
    starts 0.5 ms after `exec.execute` opens and ends 0.5 ms before
    `exec.fetch` closes, its last 5 ms idle; `shift` moves the device's
    line against the host's."""
    host = [("bench.traced", 0, 500 * MS)]
    ops, modules = [], [("jit_convert(2)", 10 * MS, 11 * MS)]
    for t0 in (60, 250):
        host.append(("bench.exe_run", t0 * MS, (t0 + 170) * MS))
        host += [(n, (t0 + s) * MS, (t0 + e) * MS)
                 for n, s, e in [("exec.step", 1, 169)] + PHASES
                 if n not in leave_out]
        start, end = t0 + 26.5 + shift, t0 + 148.5 + shift
        modules.append(("jit_step(1)", start * MS, end * MS))
        ops.append(("%fusion.1", start * MS, (end - 5) * MS, ""))
    return {"devices": {0: {"ops": ops, "modules": modules}},
            "host": {"python3": host, "other": [("noise", 0, 1)]}}


def test_the_six_buckets_add_up_to_the_idle_time():
    trace = _trace()
    account = _account.idle_account(trace)
    # gaps 0-86.5, 203.5-276.5, 393.5-500: 266 ms over 2 steps
    assert account["idle_ms"] == pytest.approx(133.0)
    assert account["feed_ms"] == pytest.approx(25.0)
    assert account["dispatch_ms"] == pytest.approx(0.5)
    assert account["fetch_ms"] == pytest.approx(6.5)
    assert account["release_ms"] == pytest.approx(10.0)
    assert account["records_ms"] == pytest.approx(9.0)
    # before the first step 61, between the steps 22, after the last 81
    assert account["unnamed_ms"] == pytest.approx(82.0)
    # the three old readers' buckets are `_scopes.idle_split`'s own
    old = _scopes.idle_split(trace)
    assert old["idle_ms"] == pytest.approx(account["idle_ms"])
    for key in ("feed_ms", "dispatch_ms", "fetch_ms"):
        assert old[key] == pytest.approx(account[key])
    parts = old["feed_ms"] + old["dispatch_ms"] + old["fetch_ms"] \
        + account["release_ms"] + account["records_ms"] \
        + account["unnamed_ms"]
    assert abs(parts - old["idle_ms"]) < 0.01


def test_a_program_without_the_new_phases_reads_zero_and_unnamed():
    """The parent of PR 36: its frame exit and its counters' reads are
    idle time under no phase."""
    account = _account.idle_account(
        _trace(leave_out=("exec.release", "exec.records")))
    assert account["release_ms"] == 0.0 and account["records_ms"] == 0.0
    assert account["unnamed_ms"] == pytest.approx(82.0 + 19.0)
    assert sum(account[k + "_ms"] for k in _account.BUCKETS) \
        + account["unnamed_ms"] == pytest.approx(account["idle_ms"])


@pytest.mark.parametrize("shift,want", [(0.0, 0.0), (-1.0, 0.5),
                                        (1.0, 0.5), (-3.0, 2.5)])
def test_the_skew_is_the_largest_violation_of_the_loops_causality(shift,
                                                                  want):
    """The device line a millisecond early: the program starts before
    `exec.execute` opened; a millisecond late: it ends after `exec.fetch`
    returned."""
    assert _account.skew_ms(_trace(shift=shift)) == pytest.approx(want)


def test_the_skew_of_a_program_without_the_two_spans_is_zero():
    bare = _trace(shift=-3.0, leave_out=("exec.execute", "exec.fetch"))
    assert _account.skew_ms(bare) == 0.0


def test_nothing_to_read_gives_none():
    no_spans = _trace(leave_out=["exec.step"] + [n for n, _s, _e in PHASES])
    no_device = dict(_trace(), devices={})
    no_window = dict(_trace(), host={"python3": []})
    for trace in (no_spans, no_device, no_window):
        assert _account.idle_account(trace) is None
        assert _account.skew_ms(trace) is None
    assert _account.worst_step([]) is None
    assert _account.worst_step([{"name": "exec.fetch", "id": "f",
                                 "parent": "w", "t0": 0, "t1": 1}]) is None


def _window_spans(slow_wall=None, slow_fetch=None, steps=21):
    """obs's dicts of a window of 0.1 s steps that wait 0.08 s in
    `exec.fetch`; step 7 takes `slow_wall` and waits `slow_fetch`."""
    spans, at = [], 0.0
    for i in range(steps):
        wall, fetch = 0.1, 0.08
        if i == 7 and slow_wall is not None:
            wall, fetch = slow_wall, slow_fetch
        step, wb = "s%d" % i, "w%d" % i
        spans += [
            {"name": "exec.fetch", "id": "f%d" % i, "parent": wb,
             "t0": at + 0.01, "t1": at + 0.01 + fetch},
            {"name": "exec.writeback", "id": wb, "parent": step,
             "t0": at + 0.01, "t1": at + 0.011 + fetch},
            {"name": "moe.load", "id": "m%d" % i, "parent": step,
             "t0": at, "t1": at},
            {"name": "exec.step", "id": step, "parent": None,
             "t0": at, "t1": at + wall}]
        at += wall
    return spans


@pytest.mark.parametrize("slow_wall,slow_fetch,ratio,wait_pct", [
    (1.0, 0.93, 10.0, 100.0 * 0.85 / 0.9),   # the wait for the device grew
    (1.0, 0.08, 10.0, 0.0),                  # a host phase grew
    (1.0, 0.99, 10.0, 100.0),                # held to 100
    (0.104, 0.084, 1.04, 0.0),               # no step stands out
    (None, None, 1.0, 0.0),
])
def test_the_windows_worst_step_and_where_its_excess_sat(
        slow_wall, slow_fetch, ratio, wait_pct):
    worst = _account.worst_step(_window_spans(slow_wall, slow_fetch))
    assert worst["max_over_median"] == pytest.approx(ratio)
    assert worst["wait_pct"] == pytest.approx(wait_pct)


# ---------------------------------------------------------------------------
# through the readers, as the harness calls them
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def exec_record(tmp_path_factory):
    """A run's record whose traced window is PR 24's recorded trace."""
    root = str(tmp_path_factory.mktemp("traced"))
    where = os.path.join(root, ".bench_trace", "tiny.cell", "plugins",
                         "profile", "2026_09_27")
    os.makedirs(where)
    with gzip.open(os.path.join(DATA, "tiny_exec_tpu.xplane.pb.gz")) as f:
        with open(os.path.join(where, "host.xplane.pb"), "wb") as out:
            out.write(f.read())
    path = os.path.join(root, ".bench_trace", "tiny.cell")
    return {"cell": types.SimpleNamespace(root=root, name="tiny.cell"),
            "traced": trace_reduce.reduce_trace(
                trace_reduce.read_xplane(path)),
            "obs_spans": _window_spans(1.0, 0.93)}


def _read(metric, record):
    return cells.Cell("bert-base.s128-b256").layer_reader(metric) \
        .read(record)


@pytest.mark.parametrize("metric", NEW)
def test_each_reader_reads_the_recorded_trace(exec_record, metric):
    value = _read(metric, exec_record)
    assert isinstance(value, float) and value >= 0.0
    if metric in ("idle_release_ms", "idle_records_ms"):
        assert value == 0.0         # PR 24's program had neither phase
    if metric == "step_wall_max_over_median":
        assert value == pytest.approx(10.0)


def test_the_recorded_steps_buckets_add_up(exec_record):
    old = sum(_read(m, exec_record) for m in (
        "idle_feed_ms", "idle_dispatch_ms", "idle_fetch_ms"))
    new = sum(_read(m, exec_record) for m in NEW[:3])
    idle = _account.account_of(exec_record)["idle_ms"]
    assert abs(old + new - idle) < 0.01
    assert _read("idle_unnamed_ms", exec_record) > 0
    # the recorded run's two lines do NOT agree: each of its three programs
    # starts 0.47-0.59 ms before the `exec.execute` that dispatched it opens
    assert _read("host_device_skew_ms", exec_record) \
        == pytest.approx(0.592, abs=0.001)


@pytest.mark.parametrize("metric", NEW)
def test_off_the_tpu_and_without_spans_every_reader_reads_none(
        exec_record, metric):
    """Off the TPU a traced run has no device plane (`traced` None): no
    reader gives a host time the name of a chip run's metric
    (`test_harness.py` holds a CPU run to three metrics). On the chip a
    window without an `exec.step` span has nothing to read either."""
    cpu = {"cell": None, "traced": None, "obs_spans": _window_spans()}
    assert _read(metric, cpu) is None
    if metric in NEW[4:]:
        assert _read(metric, dict(exec_record, obs_spans=[])) is None


def test_the_six_entries_are_in_every_cell_and_resolve():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        entry = entries[name]
        assert entry["layer"] == "Executor host path"
        assert entry["moves"] == "tokens_per_s_per_chip"
        assert entry["source"] == "program_span"
        assert entry["better"] == "lower" and "workloads" not in entry
    for workload in bench["workloads"]:
        cell = cells.Cell(workload["name"])
        assert set(NEW) <= {m["name"] for m in cell.per_layer}
        for name in NEW:
            assert callable(cell.layer_reader(name).read)
