"""The two readers of the expert layer's rows in use (PR 37):
`expert_rows_in_use_pct` on `moe.load` spans made by hand, on the spans a tiny
expert program really records, and on spans from before the labels;
`expert_layer_ms` where a trace holds no expert op; and their entries."""
import json
import os
import types

import numpy as np
import pytest

from benchmark import cells, harness
from benchmark.layer_metrics import _moe

from test_hybrid_readers import _record, _unpack

CELLS = ["lfm2-8b-a1b.t8192-b2", "kimi-linear-48b-a3b.t8192-b2"]
NEW = ["expert_layer_ms", "expert_rows_in_use_pct"]


def _read(metric, record):
    return cells.Cell(CELLS[1]).layer_reader(metric).read(record)


def _cell(trace_steps):
    return types.SimpleNamespace(traffic={"trace_steps": trace_steps})


def _spans(shares_by_layer, buffer=1000, labelled=True):
    """`moe.load` spans of a window: the warm steps before the profiler
    (a full buffer: a reader that took them would show), then one span a
    traced step with `share` of `buffer` rows laid out, then two more."""
    spans = []
    for layer, shares in shares_by_layer.items():
        seen = [1.0] * harness.TRACE_WARM_STEPS + list(shares) + [1.0, 1.0]
        for share in seen:
            labels = {"layer": layer, "rows_held": 7, "rows_max": 3,
                      "rows_mean": 1.75}
            if labelled:
                labels.update(rows_in_use=int(share * buffer),
                              rows_buffer=buffer,
                              bounded=int(share <= 0.5))
            spans.append({"name": "moe.load", "labels": labels})
    return spans + [{"name": "exec.step", "labels": {}}]


def test_the_two_entries_stand_at_the_end_and_list_both_expert_cells():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    assert [m["name"] for m in per_layer[-2:]] == NEW
    by_name = {m["name"]: m for m in per_layer}
    for name, source in zip(NEW, ("device_trace", "program_span")):
        entry = by_name[name]
        assert entry["workloads"] == CELLS
        assert (entry["source"], entry["layer"], entry["better"],
                entry["moves"]) == (source, "Step program", "lower",
                                    "tokens_per_s_per_chip")
    assert (by_name["expert_layer_ms"]["unit"],
            by_name["expert_rows_in_use_pct"]["unit"]) == ("ms", "%")
    for cell in CELLS:
        listed = {m["name"] for m in cells.Cell(cell).per_layer}
        assert set(NEW) <= listed, cell
    assert not set(NEW) & {m["name"] for m in cells.Cell(
        "gpt2.t1024-b16").per_layer}


def test_rows_in_use_is_the_layers_together_median_over_the_traced_steps():
    record = {"cell": _cell(3), "obs_spans": _spans({
        "a": [0.10, 0.20, 0.60], "b": [0.30, 0.20, 0.20]})}
    # steps: (100 + 300) / 2000, (200 + 200) / 2000, (600 + 200) / 2000
    assert _read("expert_rows_in_use_pct", record) == pytest.approx(20.0)
    # layers with buffers of their own weigh by their rows
    spans = _spans({"a": [0.5]}, buffer=3000) + _spans({"b": [0.1]})
    record = {"cell": _cell(1), "obs_spans": spans}
    assert _read("expert_rows_in_use_pct", record) == pytest.approx(
        100.0 * (1500 + 100) / 4000)


@pytest.mark.parametrize("metric", NEW)
def test_each_reader_is_left_out_where_there_is_nothing_to_read(metric):
    """A program from before this PR records `moe.load` without the three
    labels, a dense program records none, a CPU run has no device plane:
    the readers return None and do not raise."""
    cell = types.SimpleNamespace(root="/nonexistent", name="tiny.cell",
                                 family=types.SimpleNamespace(),
                                 config={"precision": "bfloat16"},
                                 traffic={"trace_steps": 2})
    old = _spans({"a": [0.1, 0.2]}, labelled=False)
    for record in ({"cell": cell, "traced": None},
                   {"cell": cell, "traced": None, "obs_spans": []},
                   {"cell": cell, "traced": None, "obs_spans": old},
                   {"cell": cell, "obs_spans": [{"name": "exec.step",
                                                 "labels": {}}],
                    "traced": {"op_seconds": {}, "steps_seen": 2,
                               "busy_s": 1.0},
                    "_scopes": {"trace": None}}):   # no device plane
        assert _read(metric, record) is None


def test_the_layers_ms_finds_no_expert_op_in_a_dense_trace(
        tmp_path_factory):
    """On the trace recorded from the hybrid program (six layers, no expert
    layer) the reader finds no `moe_*` scope, where the same walk finds the
    scan layer's ops."""
    from benchmark.layer_metrics import _hybrid
    hybrid = _record(_unpack(tmp_path_factory,
                             "tiny_hybrid_tpu.xplane.pb.gz"))
    assert _hybrid.op_type_ms(hybrid, ("selective_scan",)) > 0
    assert _read("expert_layer_ms", hybrid) is None
    assert _read("expert_rows_in_use_pct", hybrid) is None


def test_the_spans_a_tiny_expert_program_records_are_read():
    """Through `Executor.run` with obs on: the labels `layers.moe_balance`
    computes from the load it keeps, read back over the traced steps."""
    import paddle_tpu as pt
    from paddle_tpu import layers, optimizer
    from paddle_tpu.framework import obs
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.ops import moe_ops
    from paddle_tpu.ops.pallas import grouped_matmul as gmm
    x = np.random.RandomState(2).randn(64, 16).astype(np.float32)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        xv = layers.data("x", [64, 16], dtype="float32",
                         append_batch_size=False)
        outs = [layers.recompute_segment(
            lambda h, name=name: list(layers.moe_ffn(
                h, 16, 2, 8, experts_held=(0, 2), name=name)), [xv])
            for name in ("one", "two")]
        for name, (_out, load) in zip(("one", "two"), outs):
            layers.moe_balance(load, name, (0, 2))
        loss = layers.reduce_mean(layers.elementwise_add(outs[0][0],
                                                         outs[1][0]))
        optimizer.SGD(0.1).minimize(loss)
    scope, exe = Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    steps = harness.TRACE_WARM_STEPS + 2
    obs.clear()
    obs.enable()
    try:
        for _ in range(steps):
            exe.run(main, feed={"x": x}, fetch_list=[loss], scope=scope)
        spans = [{"name": s["name"], "labels": dict(s["labels"])}
                 for s in obs.spans(name="moe.load")]
    finally:
        obs.disable()
        obs.clear()
    assert len(spans) == 2 * steps
    tm = gmm.row_tile(128)
    buffer = gmm.buffer_rows(128, 2, tm)
    for span in spans:
        lab = span["labels"]
        assert lab["rows_buffer"] == buffer
        assert lab["rows_in_use"] % tm == 0
        assert lab["rows_held"] <= lab["rows_in_use"] <= buffer
        assert lab["bounded"] == int(moe_ops.takes_bounded_form(
            lab["rows_in_use"], buffer))
    last = {s["labels"]["layer"]: s["labels"] for s in spans}
    for layer, lab in last.items():
        load = np.asarray(scope.find_var(layer + "_expert_load"))[:2]
        assert lab["rows_in_use"] == int(moe_ops.rows_laid_out(load, tm))
    by_layer = _moe.loads({"cell": _cell(2), "obs_spans": spans})
    want = np.median([100.0 * sum(by_layer[n][i]["rows_in_use"]
                                  for n in ("one", "two")) / (2 * buffer)
                      for i in range(2)])
    got = _read("expert_rows_in_use_pct", {"cell": _cell(2),
                                        "obs_spans": spans})
    assert got == pytest.approx(want) and 0 < got <= 100
