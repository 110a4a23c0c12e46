"""Tiny `lfm2moe` cells ADDED to a `tiny_root.make` copy, by new files and
new BENCHMARK.json entries only (as `tiny_phi.py` adds the hybrid cells): the
five-layer cut's layout (dense conv layer, then attention and three conv
layers over experts) holding a quarter of the experts (with the expert bias
at rest, and moved a step by `expert_bias_update_rate`), and the same
layers holding all of them, at d=64, 4/2 heads of 16, dense width 128, 8 experts of
width 32, top-2, conv width 3, vocab 96, T=32."""
import json
import os

import tiny_root

KINDS = ["conv", "attention", "conv", "conv", "conv"]
PUBLISHED = [0, 2, 3, 4, 5]
TINY_LFM = {
    "family": "lfm2moe", "source": "test", "precision": "bfloat16",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 96,
    "num_experts_routed": 8, "num_experts_per_tok": 2, "num_dense_layers": 2,
    "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "use_expert_bias": True, "rope_theta": 1000000,
    "initializer_range": 0.02, "layer_kinds": KINDS,
    "published_layer_index": PUBLISHED, "num_hidden_layers": 5,
    "reduced": [],
    "optimizer": {"name": "adam", "learning_rate": 1e-4, "beta1": 0.9,
                  "beta2": 0.999, "epsilon": 1e-8}}
CONFIGS = {
    "tiny-lfm-share": dict(TINY_LFM, name="tiny-lfm-share", num_experts=2,
                           experts_held=[2, 2]),
    "tiny-lfm-whole": dict(TINY_LFM, name="tiny-lfm-whole", num_experts=8,
                           experts_held=[0, 8]),
    "tiny-lfm-bias": dict(TINY_LFM, name="tiny-lfm-bias", num_experts=2,
                          experts_held=[2, 2], expert_bias_update_rate=0.001),
}
TRAFFIC = {"t32-b2": {"seq_len": 32, "batch_per_chip": 2, "global_batch": 2,
                      "mesh_axes": None, "tokens_per_step": 64,
                      "pool_batches": 4, "warmup_steps": 1, "trace_steps": 2,
                      "reference_block_rows": 1}}
AT_REST = ["tiny-lfm-share.t32-b2", "tiny-lfm-whole.t32-b2"]
CELLS = AT_REST + ["tiny-lfm-bias.t32-b2"]
# readings at these sizes on the CPU (`read_limits.py`, 4 seeds a cell):
# loss_gap 2e-5..5e-5, grad_norm_gap 0.002..0.077, delta_norm_gap ~0.1, and
# grad_diff 0.012 where bfloat16 and the float32 reference make the same
# picks, 0.13..0.37 where one near-tie of a top-2 falls differently: an
# expert here sees ~16 rows, so one pair that moves is a large part of its
# gradient (at the cell's 2,048 rows an expert it is a small one). The limits
# leave that room; the tight comparison at this size is the float32 one
# (test_lfm2moe_family.py), and the float8 control is read on the chip.
LIMITS = {"loss_gap": 2e-4, "grad_diff": 0.6, "grad_norm_gap": 0.15,
          "delta_norm_gap": 0.4}
METRICS = ["moe_share_pct", "moe_dispatch_ms", "moe_gmm_roofline_pct",
           "moe_load_max_over_mean"]


def add(root):
    """Add the tiny lfm2moe cells to the temp root `tiny_root.make` made;
    returns the root."""
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
