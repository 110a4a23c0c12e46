"""The six readers that say where `setup_s` went
(`layer_metrics/_setup.py`): each against a hand-made miss log, nothing to
read on an empty one, off the TPU and on a program that keeps no log; the
five times add up to `setup_s`; every new entry of `BENCHMARK.json` has its
file, its layer and `"moves": "setup_s"`."""
import json
import os

import pytest

from benchmark import cells
from benchmark.layer_metrics import _setup

SIX = ["compile_trace_s", "compile_lower_s", "compile_backend_s",
       "first_execute_s", "compile_cache_hit_pct", "setup_other_s"]
TIMES = [m for m in SIX if m != "compile_cache_hit_pct"]
# the step's miss as a loading run writes it, and a small second one (a
# startup program that went through the jitted path)
LOG = [{"entry": "run", "trace_s": 13.0, "lower_s": 14.0, "backend_s": 4.0,
        "first_run_s": 0.4, "builder_s": 0.008, "cache": "hit",
        "cache_requests": 1, "cache_hits": 1, "retrieval_s": 3.5},
       {"entry": "run", "trace_s": 0.5, "lower_s": 0.25, "backend_s": 1.0,
        "first_run_s": 0.1, "builder_s": 0.002, "cache": "miss",
        "cache_requests": 3, "cache_hits": 1, "retrieval_s": 0.0}]
WANT = {"compile_trace_s": 13.5, "compile_lower_s": 14.25,
        "compile_backend_s": 5.0, "first_execute_s": 0.5,
        "compile_cache_hit_pct": 50.0, "setup_other_s": 54.0 - 33.25}


def _reader(name):
    return cells.Cell("bert-base.s128-b256").layer_reader(name)


def _record(log):
    return {"setup_s": 54.0, "miss_log": log, "peaks": {"flops": 197e12}}


@pytest.mark.parametrize("name", SIX)
def test_each_reader_against_a_hand_made_log(name):
    assert _reader(name).read(_record(LOG)) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", SIX)
def test_an_empty_log_gives_nothing_to_read(name):
    assert _reader(name).read(_record([])) is None


def test_the_five_times_add_up_to_setup_s():
    record = _record(LOG)
    assert sum(_reader(name).read(record) for name in TIMES) \
        == pytest.approx(record["setup_s"], abs=1e-9)
    one = _record(LOG[:1])
    assert sum(_reader(name).read(one) for name in TIMES) \
        == pytest.approx(one["setup_s"], abs=1e-9)


def test_no_hit_share_where_no_miss_asked_the_cache():
    off = [dict(LOG[0], cache="off", cache_requests=0, cache_hits=0)]
    assert _reader("compile_cache_hit_pct").read(_record(off)) is None
    assert _reader("compile_backend_s").read(_record(off)) == 4.0
    loaded = _reader("compile_cache_hit_pct").read(_record(LOG[:1]))
    assert loaded == 100.0


@pytest.mark.parametrize("name", SIX)
def test_off_the_tpu_and_without_a_miss_log_nothing_is_read(
        name, monkeypatch):
    """A record with no log of its own is read from the program. Off the
    TPU (`peaks` None) a CPU compiler's seconds are not written under the
    chip's names; the parent of PR 51 keeps no log: neither raises."""
    from paddle_tpu.framework import executor
    assert _reader(name).read({"setup_s": 5.0, "peaks": None}) is None
    monkeypatch.delattr(executor, "miss_log")
    assert _reader(name).read({"setup_s": 5.0, "peaks": {}}) is None


def test_a_record_without_a_log_reads_the_programs_own(monkeypatch):
    from paddle_tpu.framework import executor
    monkeypatch.setattr(executor, "miss_log", lambda: LOG[:1])
    record = {"setup_s": 54.0, "peaks": {"flops": 197e12}}
    assert _reader("compile_lower_s").read(record) == 14.0
    assert _reader("setup_other_s").read(record) == pytest.approx(22.6)
    assert _setup.log_of(record) == LOG[:1]


def test_every_new_entry_has_its_file_its_layer_and_moves_setup_s():
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]}
    for name in SIX:
        entry = entries[name]
        assert entry["moves"] == "setup_s" and "workloads" not in entry
        assert entry["layer"] == "Executor host path" in layers
        assert entry["better"] == ("higher" if name.endswith("_pct")
                                   else "lower")
        assert entry["unit"] == ("%" if name.endswith("_pct") else "s")
        assert entry["source"] == ("program_counter" if name.endswith(
            "_pct") else "program_span")
        assert os.path.exists(os.path.join(
            cells.ROOT, "benchmark", "layer_metrics", name + ".py"))
    # every cell reports setup_s, so every cell reports the six (by
    # presence: a later PR appends its own)
    for workload in bench["workloads"]:
        names = {m["name"] for m in cells.Cell(workload["name"]).per_layer}
        assert set(SIX) <= names
