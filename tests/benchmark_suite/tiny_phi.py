"""Tiny `phi4flash` cells ADDED to a `tiny_root.make` copy, by new files and
new BENCHMARK.json entries only (as `test_extend.py` adds a family): the
five-layer cut's layout and the published layout rule at L=8, at d=64, 4/2
heads of 16, E=128, N=4, R=4, W=8, vocab 96, T=32."""
import json
import os

import tiny_root

CUT = ["window", "memory", "full", "gmu", "cross"]
RULE8 = ["mamba", "window", "mamba", "window", "memory", "full", "gmu",
         "cross"]
TINY_PHI = {
    "family": "phi4flash", "source": "test", "precision": "bfloat16",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 96, "sliding_window": 8,
    "layer_norm_eps": 1e-5, "initializer_range": 0.02, "ssm_expand": 2,
    "ssm_state_size": 4, "ssm_conv_width": 4, "ssm_dt_rank": 4,
    "reduced": [],
    "optimizer": {"name": "adam", "learning_rate": 1e-4, "beta1": 0.9,
                  "beta2": 0.999, "epsilon": 1e-8}}
CONFIGS = {
    "tiny-phi-cut": dict(TINY_PHI, name="tiny-phi-cut", layer_kinds=CUT,
                         num_hidden_layers=5,
                         published_layer_index=[1, 16, 17, 18, 19]),
    "tiny-phi-l8": dict(TINY_PHI, name="tiny-phi-l8", layer_kinds=RULE8,
                        num_hidden_layers=8,
                        published_layer_index=list(range(8))),
}
TRAFFIC = {"t32-b2": {"seq_len": 32, "batch_per_chip": 2, "global_batch": 2,
                      "mesh_axes": None, "tokens_per_step": 64,
                      "pool_batches": 4, "warmup_steps": 1, "trace_steps": 2,
                      "reference_block_rows": 1}}
CELLS = ["tiny-phi-cut.t32-b2", "tiny-phi-l8.t32-b2"]
# readings at these sizes on the CPU (test_phi4flash.py prints them with
# -s): the program (bfloat16) reads grad_diff 0.006-0.012, the float8
# control 0.06-0.13
LIMITS = {"loss_gap": 2e-3, "grad_diff": 0.03, "grad_norm_gap": 0.03,
          "delta_norm_gap": 0.4}
METRICS = ["ssm_scan_share_pct", "attn_share_pct", "ssm_scan_roofline_pct",
           "attn_roofline_pct", "ssm_device_ms"]


def add(root):
    """Add the tiny phi4flash cells to the temp root `tiny_root.make`
    made; returns the root."""
    bdir = os.path.join(root, "benchmark")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for name, cfg in CONFIGS.items():
        tiny_root._write(os.path.join(bdir, "configs", name + ".json"), cfg)
        bench["configs"].append({
            "name": name, "source": "test",
            "file": "benchmark/configs/%s.json" % name, "reduced": [],
            "why": "tiny, for the CPU tests"})
    for name, traffic in TRAFFIC.items():
        tiny_root._write(os.path.join(bdir, "traffic", name + ".json"),
                         traffic)
    for cell in CELLS:
        config, traffic = cell.split(".")
        tiny_root._write(os.path.join(bdir, "limits", cell + ".json"), LIMITS)
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "tiny, for the CPU tests"})
    for m in bench["per_layer"]:
        if m["name"] in METRICS:
            m["workloads"].extend(CELLS)
    tiny_root._write(os.path.join(root, "BENCHMARK.json"), bench)
    return root
