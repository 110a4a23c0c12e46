"""BENCHMARK.json against the contract's shape rules, and every cell
resolving to its files by name alone."""
import json
import os
import re

import pytest

from benchmark import cells

REPO = cells.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def all_metrics():
    b = bench()
    return [(kind, m) for kind in ("end_to_end", "per_layer")
            for m in b[kind]]


def test_top_level_keys_and_command():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmark", "tests/benchmark_suite"]
    assert b["command"] == ["python3", "benchmark/run.py"]
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_run_seconds_fits_a_full_check_with_24_cells():
    rs = bench()["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("kind,metric", all_metrics(),
                         ids=lambda x: x if isinstance(x, str) else x["name"])
def test_metric_entry(kind, metric):
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if kind == "end_to_end" else {"layer", "moves"}
    assert set(metric) <= allowed and {"name", "unit", "better",
                                       "source"} <= set(metric)
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    b = bench()
    cells_ = {w["name"] for w in b["workloads"]}
    assert set(metric.get("workloads", [])) <= cells_
    if kind == "end_to_end":
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert metric["source"] in SOURCES
        assert metric["moves"] in {m["name"] for m in b["end_to_end"]}
        assert 1 <= len(metric["layer"]) <= 200
        assert os.path.exists(os.path.join(
            REPO, "benchmark", "layer_metrics", metric["name"] + ".py"))
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_names_are_unique_and_setup_s_is_there():
    b = bench()
    for key in ("configs", "workloads"):
        names = [x["name"] for x in b[key]]
        assert len(names) == len(set(names))
    names = [m["name"] for _k, m in all_metrics()]
    assert len(names) == len(set(names))
    setup = [m for m in b["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.1
    assert [m["name"] for m in b["end_to_end"]] == [
        "tokens_per_s_per_chip", "step_ms_p90", "peak_hbm_gib", "setup_s"]


@pytest.mark.parametrize("cfg", bench()["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"])
    assert cfg["file"].startswith("benchmark/configs/")
    assert len(cfg["source"]) <= 200 and len(cfg["why"]) <= 200
    with open(os.path.join(REPO, cfg["file"])) as f:
        held = json.load(f)
    assert held["name"] == cfg["name"]
    # the file lists the same changed keys, and none of them is a width
    assert sorted(held["reduced"]) == sorted(cfg["reduced"])
    assert len(cfg["reduced"]) <= 16
    for key in cfg["reduced"]:
        assert NAME.match(key)
        assert not re.search(r"(_dim|_rank|hidden_size|n_embd|n_inner|"
                             r"intermediate_size|head)", key), key
    assert cfg["name"] in {w["config"] for w in bench()["workloads"]}


PUBLISHED = {
    "bert-base": dict(vocab_size=30522, hidden_size=768,
                      num_hidden_layers=12, num_attention_heads=12,
                      intermediate_size=3072, max_position_embeddings=512,
                      type_vocab_size=2, initializer_range=0.02),
    "bert-large": dict(vocab_size=30522, hidden_size=1024,
                       num_hidden_layers=24, num_attention_heads=16,
                       intermediate_size=4096, max_position_embeddings=512,
                       type_vocab_size=2, initializer_range=0.02),
    "gpt2": dict(vocab_size=50257, n_embd=768, n_layer=12, n_head=12,
                 layer_norm_epsilon=1e-5, initializer_range=0.02),
}


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_published_sizes_equal_their_source(name):
    with open(os.path.join(REPO, "benchmark", "configs",
                           name + ".json")) as f:
        held = json.load(f)
    for key, value in PUBLISHED[name].items():
        assert held[key] == value, key
        assert key not in held["reduced"]


@pytest.mark.parametrize("w", bench()["workloads"], ids=lambda w: w["name"])
def test_cell_resolves_to_its_files_by_name(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["name"] == "%s.%s" % (w["config"], w["traffic"])
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    cell = cells.Cell(w["name"])
    assert cell.chips == w["chips"] == cell.mesh_size()
    t = cell.traffic
    assert t["tokens_per_step"] == t["global_batch"] * t["seq_len"]
    assert t["global_batch"] == t["batch_per_chip"] * cell.chips
    assert t["global_batch"] % t["reference_block_rows"] == 0
    assert t["pool_batches"] == 8
    for fn in ("build", "param_specs", "make_batch", "train_flops",
               "reference_loss", "block_of", "attention_calls"):
        assert callable(getattr(cell.family, fn))
    assert {"loss_gap", "grad_diff", "grad_norm_gap",
            "delta_norm_gap"} <= set(cell.limits)
    # every cell reports setup_s, another end-to-end metric and a
    # per-layer metric
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(cell.layer_reader(m["name"]).read)


def test_the_queued_four_chip_cells_files_are_ready():
    """`bert-large.s128-b256-dp4` ran on the chip but is not admitted yet
    (PERF.md, Open questions 1): its configuration, traffic and the
    collectives' reader are in the tree, so that the PR that proves it adds
    its limits and the BENCHMARK.json entries only."""
    bdir = os.path.join(REPO, "benchmark")
    with open(os.path.join(bdir, "traffic", "s128-b256-dp4.json")) as f:
        t = json.load(f)
    assert t["mesh_axes"] == {"dp": 4} and t["global_batch"] == 256
    assert t["batch_per_chip"] * 4 == t["global_batch"]
    assert os.path.exists(os.path.join(bdir, "configs", "bert-large.json"))
    assert os.path.exists(os.path.join(bdir, "layer_metrics",
                                       "collective_exposed_pct.py"))
    assert "bert-large.s128-b256-dp4" not in {
        w["name"] for w in bench()["workloads"]}


def test_at_most_a_quarter_of_the_cells_and_one_at_least_ask_for_4_chips():
    ws = bench()["workloads"]
    four = [w for w in ws if w["chips"] == 4]
    assert len(four) <= max(1, len(ws) // 4)


def test_an_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        cells.Cell("no-such.cell")
