"""`kda_roofline_pct` (benchmark/layer_metrics/kda_roofline_pct.py) on
hand-made traces: the least time of the `kda_*` calls a window RAN over
their device time; a window with forward kernels alone is held to the
forward's work; two kernels that share a backward count it once; None on
a program with no such kernel (the parent of PR 39) and on an untraced
run; the entry stands in `BENCHMARK.json` with the Kimi cell alone."""
import json
import os
import types

import pytest

from benchmark import cells, flops, flops_kda

CELL = "kimi-linear-48b-a3b.t8192-b2"
SHAPE = (2, 8192, 16, 128, 128)         # batch, seq, heads held, K, V


@pytest.fixture(scope="module")
def cell():
    return cells.Cell(CELL)


def _op(kernel, number, start_ms, ms):
    name = "%%%s.%d = (bf16[2,8192,2048]{2,1,0}) custom-call(%%x)" % (
        kernel, number)
    return (name, int(start_ms * 1e6), int((start_ms + ms) * 1e6),
            "jit(step)/forward/kda_attention/%s/pallas_call" % kernel)


def _record(cell, ops, chips=1):
    """A record whose traced window (0..1000 ms) holds `ops` on each of
    `chips` device planes, and one fusion besides."""
    ops = list(ops) + [("%fusion.7 = f32[8]{0} fusion(%y)", 0, 10 ** 6, "")]
    trace = {"devices": {i: {"ops": list(ops), "modules": []}
                         for i in range(chips)},
             "host": {"main": [("bench.traced", 0, 10 ** 9)]}}
    fake = types.SimpleNamespace(root="/nonexistent", name=CELL,
                                 family=cell.family, config=cell.config,
                                 traffic=cell.traffic)
    return {"cell": fake, "traced": {"steps_seen": 1},
            "peaks": flops.peaks_for("TPU v5 lite"),
            "_scopes": {"trace": trace}}


def _least(peaks):
    ops = flops_kda.call_flops(*SHAPE)
    moved = flops_kda.call_bytes(*SHAPE, 2)
    return [flops.roofline_seconds(ops[i], moved[i], peaks)[0]
            for i in range(2)]


def test_the_calls_seen_are_held_to_their_own_work(cell):
    read = cell.layer_reader("kda_roofline_pct").read
    # a step of one layer: forward, replayed forward, backward
    record = _record(cell, [_op("kda_fwd", 1, 10, 5.0),
                            _op("kda_fwd", 2, 30, 5.0),
                            _op("kda_bwd", 3, 50, 10.0)])
    fwd, bwd = _least(record["peaks"])
    assert fwd == pytest.approx(0.492e-3, rel=0.01)    # 403 MB over 819 GB/s
    assert bwd == pytest.approx(2 * fwd)
    assert read(record) == pytest.approx(100 * (2 * fwd + bwd) / 20e-3)
    assert 0 < read(record) < 100
    # the same on two chips is the same share
    assert read(_record(cell, record["_scopes"]["trace"]["devices"][0]
                        ["ops"][:-1], chips=2)) \
        == pytest.approx(read(record))


def test_a_forward_alone_is_held_to_the_forwards_work(cell):
    read = cell.layer_reader("kda_roofline_pct").read
    record = _record(cell, [_op("kda_fwd", 1, 10, 5.0),
                            _op("kda_fwd", 2, 30, 5.0)])
    fwd, _bwd = _least(record["peaks"])
    assert read(record) == pytest.approx(100 * 2 * fwd / 10e-3)


def test_two_kernels_that_share_a_backward_count_it_once(cell):
    read = cell.layer_reader("kda_roofline_pct").read
    record = _record(cell, [_op("kda_bwd_walk", 1, 10, 4.0),
                            _op("kda_bwd_intra", 2, 30, 6.0)])
    _fwd, bwd = _least(record["peaks"])
    assert read(record) == pytest.approx(100 * bwd / 10e-3)


def test_a_call_outside_the_window_is_not_counted(cell):
    read = cell.layer_reader("kda_roofline_pct").read
    inside = [_op("kda_fwd", 1, 10, 5.0)]
    assert read(_record(cell, inside + [_op("kda_fwd", 2, 1200, 5.0)])) \
        == pytest.approx(read(_record(cell, inside)))


def test_none_without_the_kernels_and_without_a_trace(cell):
    read = cell.layer_reader("kda_roofline_pct").read
    assert read(_record(cell, [])) is None              # the parent of PR 39
    assert read({"cell": cell, "traced": None,
                 "peaks": flops.peaks_for("TPU v5 lite")}) is None
    record = _record(cell, [_op("kda_fwd", 1, 10, 5.0)])
    record["peaks"] = None
    assert read(record) is None


def test_the_entry_stands_in_the_benchmark_with_the_kimi_cell_alone(cell):
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [m for m in bench["per_layer"]
             if m["name"] == "kda_roofline_pct"]
    assert entry == [{"name": "kda_roofline_pct", "unit": "%",
                      "better": "higher", "source": "device_trace",
                      "layer": "Pallas kernels",
                      "moves": "tokens_per_s_per_chip",
                      "workloads": [CELL]}]
    assert "kda_roofline_pct" in [m["name"] for m in cell.per_layer]
