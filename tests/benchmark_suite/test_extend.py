"""A later PR adds a cell, a configuration, a traffic mix, a family and a
per-layer metric by adding files and BENCHMARK.json entries, and edits no
file that is there. Shown in a temp copy: a third family (a new file that
reuses the GPT model under another parameter list), its configuration, a
traffic mix, a per-layer metric with its reader, and the cell that ties them
together, then one traced run through the unedited harness."""
import hashlib
import json
import os

from benchmark import cells, harness

NEW_FAMILY = '''
"""A third family for the test: the GPT model with every block matrix held
in float32 (the program run with dtype float32)."""
import importlib.util, os
_spec = importlib.util.spec_from_file_location(
    "benchmark_family_gpt_for_wide", os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "gpt.py"))
_gpt = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_gpt)
globals().update({k: getattr(_gpt, k) for k in (
    "build", "batch_rows", "tokens_per_step", "param_specs", "make_batch",
    "train_flops", "attention_calls", "block_of", "reference_loss")})
'''
NEW_METRIC = '''
"""Window steps: how many steps the window held."""
def read(record):
    return float(len(record["step_ms"]))
'''


def _digest(root):
    out = {}
    for base, _dirs, files in os.walk(root):
        if "__pycache__" in base:
            continue
        for f in files:
            p = os.path.join(base, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_new_cell_family_and_metric_need_only_new_files(tiny):
    before = _digest(os.path.join(tiny, "benchmark"))
    bdir = os.path.join(tiny, "benchmark")
    with open(os.path.join(bdir, "families", "widegpt.py"), "w") as f:
        f.write(NEW_FAMILY)
    with open(os.path.join(bdir, "layer_metrics", "window_steps.py"),
              "w") as f:
        f.write(NEW_METRIC)
    with open(os.path.join(bdir, "configs", "tiny-gpt.json")) as f:
        cfg = json.load(f)
    cfg.update(name="wide-gpt", family="widegpt", precision="float32")
    with open(os.path.join(bdir, "configs", "wide-gpt.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bdir, "traffic", "t16-b4.json")) as f:
        traffic = json.load(f)
    traffic.update(global_batch=2, batch_per_chip=2, tokens_per_step=32,
                   reference_block_rows=1)
    with open(os.path.join(bdir, "traffic", "t16-b2.json"), "w") as f:
        json.dump(traffic, f)
    with open(os.path.join(bdir, "limits", "wide-gpt.t16-b2.json"),
              "w") as f:
        json.dump({"loss_gap": 1e-5, "grad_diff": 1e-3,
                   "grad_norm_gap": 1e-3, "delta_norm_gap": 1e-2}, f)
    with open(os.path.join(tiny, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "wide-gpt", "source": "test",
                             "file": "benchmark/configs/wide-gpt.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "wide-gpt.t16-b2",
                               "config": "wide-gpt", "traffic": "t16-b2",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "window_steps", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "Executor host path",
        "moves": "tokens_per_s_per_chip", "workloads": ["wide-gpt.t16-b2"]})
    with open(os.path.join(tiny, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = cells.Cell("wide-gpt.t16-b2", tiny)
    assert "window_steps" in {m["name"] for m in cell.per_layer}
    out = harness.run_cell("wide-gpt.t16-b2", 3, 0.3, 1, platform="cpu",
                           root=tiny)
    assert out["correct"] is True
    assert out["metrics"]["window_steps"]["value"] == out["attempted"]
    timed = harness.run_cell("wide-gpt.t16-b2", 4, 0.3, 0, platform="cpu",
                             root=tiny)
    assert "step_ms_p90" not in timed["metrics"]
    assert timed["metrics"]["tokens_per_s_per_chip"]["value"] > 0
    # an older cell is not given the new cell's metric
    old = cells.Cell("tiny-gpt.t16-b4", tiny)
    assert "window_steps" not in {m["name"] for m in old.per_layer}

    after = _digest(os.path.join(tiny, "benchmark"))
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/wide-gpt.json", "families/widegpt.py",
        "layer_metrics/window_steps.py", "limits/wide-gpt.t16-b2.json",
        "traffic/t16-b2.json"]
