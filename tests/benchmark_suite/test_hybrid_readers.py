"""The five per-layer readers of the hybrid cells on a trace recorded on a
v5e (`data/record_tiny_hybrid.py`: six layers, one of each kind, of the phi4flash
program at hidden 256, T=512), on the older GPT trace (flash kernels, no
scan), and without a device plane."""
import gzip
import os
import types

import pytest

from benchmark import cells, flops, trace_reduce
from benchmark.layer_metrics import _hybrid, _scopes

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRICS = ["ssm_scan_share_pct", "attn_share_pct", "ssm_scan_roofline_pct",
           "attn_roofline_pct", "ssm_device_ms"]
RECORDED = {  # what record_tiny_hybrid.py built
    "family": "phi4flash", "precision": "bfloat16", "hidden_size": 256,
    "intermediate_size": 512, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 1024, "sliding_window": 128,
    "layer_norm_eps": 1e-5, "ssm_expand": 2, "ssm_state_size": 16,
    "ssm_conv_width": 4, "ssm_dt_rank": 16,
    "layer_kinds": ["mamba", "window", "memory", "full", "gmu", "cross"],
    "published_layer_index": [0, 1, 16, 17, 18, 19]}
TRAFFIC = {"seq_len": 512, "batch_per_chip": 1, "global_batch": 1}


def _unpack(tmp_path_factory, gz_name):
    root = tmp_path_factory.mktemp("traced")
    where = os.path.join(str(root), ".bench_trace", "tiny.cell", "plugins",
                         "profile", "2026_09_28")
    os.makedirs(where)
    with gzip.open(os.path.join(DATA, gz_name)) as f:
        with open(os.path.join(where, "host.xplane.pb"), "wb") as out:
            out.write(f.read())
    return str(root)


def _record(root):
    family = cells._load_module(
        os.path.join(cells.ROOT, "benchmark", "families", "phi4flash.py"),
        "benchmark_family_phi4flash_for_readers")
    cell = types.SimpleNamespace(root=root, name="tiny.cell", family=family,
                                 config=RECORDED, traffic=TRAFFIC)
    traced = trace_reduce.reduce_trace(trace_reduce.read_xplane(
        os.path.join(root, ".bench_trace", "tiny.cell")))
    return {"cell": cell, "traced": traced,
            "peaks": flops.peaks_for("TPU v5 lite")}


@pytest.fixture(scope="module")
def hybrid(tmp_path_factory):
    return _record(_unpack(tmp_path_factory, "tiny_hybrid_tpu.xplane.pb.gz"))


@pytest.fixture(scope="module")
def gpt(tmp_path_factory):
    return _record(_unpack(tmp_path_factory, "tiny_exec_tpu.xplane.pb.gz"))


def _read(metric, record):
    return cells.Cell("phi4-mini-flash.t8192-b1").layer_reader(
        metric).read(record)


def test_the_recorded_step_holds_both_families_of_kernels(hybrid):
    kinds = hybrid["traced"]["op_seconds"]
    assert {"custom-call:ssm_scan_fwd", "custom-call:ssm_scan_bwd",
            "custom-call:flash_fwd", "custom-call:flash_bwd_dkv",
            "custom-call:flash_bwd_dq"} <= set(kinds)
    trace = _scopes.trace_of(hybrid)
    scopes = {_scopes.parse_scope(tf_op)[:2]
              for dev in trace["devices"].values()
              for _n, _s, _e, tf_op in dev["ops"]}
    for op_type in ("selective_scan", "causal_conv1d", "rms_norm"):
        assert ("forward", op_type) in scopes, op_type
        assert ("backward", op_type) in scopes, op_type


@pytest.mark.parametrize("metric", METRICS)
def test_each_reader_reads_the_recorded_trace(hybrid, metric):
    value = _read(metric, hybrid)
    assert value is not None and value > 0
    if metric.endswith("_pct"):
        assert value < 100


def test_the_readers_agree_with_each_other(hybrid):
    read = {m: _read(m, hybrid) for m in METRICS}
    traced = hybrid["traced"]
    scan_ms = (read["ssm_scan_share_pct"] / 100 * traced["busy_s"]
               / traced["steps_seen"] * 1e3)
    # the op types' device time holds the kernels' and the passes around
    assert read["ssm_device_ms"] >= 0.99 * scan_ms
    assert read["ssm_scan_share_pct"] + read["attn_share_pct"] < 100
    # the least times are counted once, from shapes
    least = _hybrid.scan_least_seconds(hybrid)
    wide, narrow = 512 * 512 * 2, 512 * 16 * 2
    assert least == pytest.approx(
        2 * (2 * (3 * wide + 2 * narrow) + 5 * wide + 4 * narrow) / 819e9)
    assert read["ssm_scan_roofline_pct"] == pytest.approx(
        100 * least * traced["steps_seen"]
        / _hybrid.kernel_seconds(hybrid, _hybrid.SCAN))


@pytest.mark.parametrize("metric", METRICS)
def test_each_reader_is_left_out_without_a_device_plane(metric):
    cell = types.SimpleNamespace(root="/nonexistent", name="tiny.cell")
    assert _read(metric, {"cell": cell, "traced": None}) is None


@pytest.mark.parametrize("metric", ["ssm_scan_share_pct",
                                    "ssm_scan_roofline_pct",
                                    "ssm_device_ms"])
def test_a_step_without_the_scan_gives_the_scan_readers_nothing(gpt,
                                                                metric):
    assert _read(metric, gpt) is None
    assert _read("attn_share_pct", gpt) > 0
