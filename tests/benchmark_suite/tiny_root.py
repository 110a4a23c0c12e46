"""A temp copy of the benchmark with tiny cells ADDED to it: new config,
traffic and limits files and new BENCHMARK.json entries, no edit to any file
of the copy. The CPU tests drive the harness through these cells, and
`test_extend.py` shows the same for a new family and a new per-layer
metric."""
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_BERT = {
    "name": "tiny-bert", "family": "bert", "source": "test", "precision":
    "bfloat16", "vocab_size": 96, "hidden_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 4, "intermediate_size": 64,
    "max_position_embeddings": 16, "type_vocab_size": 2,
    "initializer_range": 0.02, "hidden_dropout_prob": 0.0,
    "attention_probs_dropout_prob": 0.0, "reduced": [],
    "optimizer": {"name": "adam", "learning_rate": 1e-4, "beta1": 0.9,
                  "beta2": 0.999, "epsilon": 1e-8}}
TINY_GPT = {
    "name": "tiny-gpt", "family": "gpt", "source": "test", "precision":
    "bfloat16", "vocab_size": 96, "n_embd": 32, "n_layer": 2, "n_head": 4,
    "n_inner": 64, "n_positions": 16, "initializer_range": 0.02,
    "resid_pdrop": 0.0, "reduced": [],
    "optimizer": {"name": "adam", "learning_rate": 1e-4, "beta1": 0.9,
                  "beta2": 0.999, "epsilon": 1e-8}}
TRAFFIC = {
    "s8-b8": {"seq_len": 8, "max_predictions": 3, "batch_per_chip": 8,
              "global_batch": 8, "mesh_axes": None, "tokens_per_step": 64,
              "pool_batches": 4, "warmup_steps": 1, "trace_steps": 2,
              "reference_block_rows": 4},
    "s8-b8-dp4": {"seq_len": 8, "max_predictions": 3, "batch_per_chip": 2,
                  "global_batch": 8, "mesh_axes": {"dp": 4},
                  "tokens_per_step": 64, "pool_batches": 4,
                  "warmup_steps": 1, "trace_steps": 2,
                  "reference_block_rows": 4},
    "t16-b4": {"seq_len": 16, "batch_per_chip": 4, "global_batch": 4,
               "mesh_axes": None, "tokens_per_step": 64, "pool_batches": 4,
               "warmup_steps": 1, "trace_steps": 2,
               "reference_block_rows": 2},
}
CELLS = [("tiny-bert", "s8-b8", 1), ("tiny-bert", "s8-b8-dp4", 4),
         ("tiny-gpt", "t16-b4", 1)]
# readings at these sizes on the CPU (test_reference.py): the program
# (bfloat16) reads grad_diff 0.008-0.013, grad_norm_gap <= 0.007 and
# delta_norm_gap ~0.1; the float8 control reads grad_diff 0.067-0.133
LIMITS = {"loss_gap": 2e-3, "grad_diff": 0.03, "grad_norm_gap": 0.02,
          "delta_norm_gap": 0.4}


def make(tmp_path, limits=None):
    """Copy benchmark/ + BENCHMARK.json into tmp_path and add the tiny
    cells. Returns the new root."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bdir = os.path.join(root, "benchmark")
    for cfg in (TINY_BERT, TINY_GPT):
        _write(os.path.join(bdir, "configs", cfg["name"] + ".json"), cfg)
        bench["configs"].append({
            "name": cfg["name"], "source": "test",
            "file": "benchmark/configs/%s.json" % cfg["name"],
            "reduced": [], "why": "tiny, for the CPU tests"})
    for name, traffic in TRAFFIC.items():
        _write(os.path.join(bdir, "traffic", name + ".json"), traffic)
    for config, traffic, chips in CELLS:
        cell = "%s.%s" % (config, traffic)
        _write(os.path.join(bdir, "limits", cell + ".json"),
               limits or LIMITS)
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": traffic, "chips": chips,
                                   "why": "tiny, for the CPU tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == "step_ms_p90":
            m["workloads"].append("tiny-bert.s8-b8")
        if m["name"].startswith("flash_"):
            m["workloads"].append("tiny-gpt.t16-b4")
    # the collectives' reader is in the tree for the four-chip cell that
    # PERF.md queues first; here the tiny dp4 cell is its only cell
    bench["per_layer"].append({
        "name": "collective_exposed_pct", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "Collectives",
        "moves": "tokens_per_s_per_chip",
        "workloads": ["tiny-bert.s8-b8-dp4"]})
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
