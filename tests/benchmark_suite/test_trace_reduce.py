"""The reduction from trace to numbers: arithmetic on hand-made event
lists, and the reader on a small trace recorded on a TPU v5e
(data/tiny_tpu.xplane.pb: three runs of a jitted tanh(x @ x).sum() inside
`bench.traced`, each in a `bench.exe_run`)."""
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "tiny_tpu.xplane.pb")


def test_op_kind_strips_numbers_and_marks_custom_calls():
    assert tr.op_kind("%fusion.337 = (f32[8]{0}) fusion(f32[8]{0} %p)") \
        == "fusion"
    assert tr.op_kind("%copy-done.4 = f32[2] copy-done(%copy-start.4)") \
        == "copy-done"
    assert tr.op_kind("%jvp__.21 = (bf16[48,4096,64]) custom-call(bf16[4] "
                      "%x), custom_call_target=\"tpu_custom_call\"") \
        == "custom-call:jvp__"
    assert tr.op_kind("all-reduce.7") == "all-reduce"


def test_collectives_are_told_from_compute():
    for name in ("%all-reduce.3 = f32[4] all-reduce(%x)",
                 "all-gather-start.1", "reduce-scatter.2",
                 "%all-reduce-done.9 = f32[4] all-reduce-done(%s)"):
        assert tr.is_collective(name), name
    for name in ("%fusion.3 = f32[4] fusion(%all-reduce.3)", "copy.1",
                 "%reduce.5 = f32[] reduce(%x)"):
        assert not tr.is_collective(name), name


def test_busy_union_and_idle_share_on_a_hand_made_list():
    ops = [("a", 0, 10), ("b", 5, 20), ("c", 30, 40), ("d", 40, 45)]
    busy = tr.busy_union(ops)
    assert busy == [(0, 20), (30, 45)]
    assert tr.total(busy) == 35
    assert tr.busy_union(ops, 10, 35) == [(10, 20), (30, 35)]
    assert tr.idle_gaps(busy, 0, 50) == [(20, 30), (45, 50)]
    assert 1 - tr.total(busy) / 50 == pytest.approx(0.3)


def test_exposed_collective_is_the_part_no_compute_covers():
    ops = [("fusion.1", 0, 10), ("all-reduce.1", 5, 25),
           ("fusion.2", 20, 30), ("all-reduce-done.2", 40, 50)]
    # 10..20 of the first all-reduce and all of the second are exposed
    assert tr.exposed_collective_ns(ops, 0, 60) == 20
    assert tr.exposed_collective_ns(ops, 15, 45) == 10


def test_per_step_busy_follows_the_step_programs_runs():
    ops = [("f", 0, 40), ("g", 50, 90), ("f", 100, 130), ("x", 200, 205)]
    modules = [("jit_step(1)", 0, 95), ("jit_step(1)", 100, 195),
               ("jit_norms(2)", 200, 210)]
    assert tr.per_step_busy_ns(ops, modules) == [80, 30]
    assert tr.per_step_busy_ns(ops, []) == []


def test_gaps_are_attributed_to_what_the_host_was_doing():
    host = [("bench.traced", 0, 1000), ("bench.exe_run", 100, 400),
            ("PjitFunction(step)", 120, 200), ("unrelated", 2000, 2100)]
    assert tr.attribute_gap((150, 160), host) \
        == "bench.traced>PjitFunction(step)"
    assert tr.attribute_gap((300, 320), host) == "bench.traced>bench.exe_run"
    assert tr.attribute_gap((500, 600), host) == "bench.traced"
    assert tr.attribute_gap((1500, 1600), host) == "(nothing recorded)"


def test_top_ops_sums_kinds_inside_the_window():
    ops = [("%fusion.1 = f32[] fusion()", 0, 10e9),
           ("%fusion.2 = f32[] fusion()", 10e9, 15e9),
           ("%copy.1 = f32[] copy()", 15e9, 16e9)]
    assert tr.top_ops(ops, 0, 20e9) == [["fusion", 15.0], ["copy", 1.0]]
    assert tr.top_ops(ops, 12e9, 20e9, n=1) == [["fusion", 3.0]]


def test_reduce_trace_on_a_hand_made_two_chip_trace():
    def chip(shift):
        return {"ops": [("%fusion.1 = f32[] fusion()", 100 + shift, 400),
                        ("%all-reduce.1 = f32[] all-reduce()", 400, 500)],
                "modules": [("jit_step(1)", 100, 500)]}
    trace = {"devices": {0: chip(0), 1: chip(100)},
             "host": {"main/1": [("bench.traced", 0, 1000),
                                 ("bench.exe_run", 50, 600)]}}
    out = tr.reduce_trace(trace)
    assert out["window_s"] == pytest.approx(1e-6)
    assert out["busy_s"] == pytest.approx((400 + 300) / 2 / 1e9)
    assert out["idle_share"] == pytest.approx(1 - 350 / 1000)
    assert out["exposed_collective_s"] == pytest.approx(100 / 1e9)
    assert out["step_busy_ms"] == pytest.approx(350 / 1e6)
    assert out["idle_gaps"][0] == ["bench.traced", 500 / 1e9]
    assert out["device_ops"][0][0] == "fusion"
    with pytest.raises(ValueError):
        tr.reduce_trace({"devices": trace["devices"], "host": {}})


def test_the_recorded_tpu_trace_reads_and_reduces():
    trace = tr.read_xplane(DATA)
    assert sorted(trace["devices"]) == [0]
    dev = trace["devices"][0]
    assert len(dev["modules"]) == 3          # three runs of the program
    assert dev["ops"] and all(e > s for _n, s, e in dev["ops"])
    out = tr.reduce_trace(trace)
    # the device's timestamps lead the host's by ~0.2 ms in this trace, so
    # the first 2-microsecond run falls just before `bench.traced` opens
    assert out["steps_seen"] in (2, 3)
    assert 0 < out["busy_s"] < out["window_s"]
    assert 0 < out["idle_share"] < 1
    assert out["exposed_collective_s"] == 0
    assert out["step_busy_ms"] > 0
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    labels = {g[0] for g in out["idle_gaps"]}
    assert any(label.startswith("bench.traced") for label in labels)
