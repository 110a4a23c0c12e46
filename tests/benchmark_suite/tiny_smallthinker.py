"""Tiny `smallthinker` cells ADDED to a `tiny_root.make` copy, by new files
and new BENCHMARK.json entries only (as `tiny_lfm.py` adds the expert
cells): the four-layer cut's layout (a full position-free layer, then three
windowed rotary ones, all over experts routed by the layer's input) holding
half the experts, at d=64, 4/2 heads of 16, window 8, 8 experts of width 32,
top-2, vocab 96, T=32; once in bfloat16 as the cell runs, once in float32
under limits a lower precision cannot meet."""
import json
import os

import tiny_root

LAYOUT = [0, 1, 1, 1]
TINY_ST = {
    "family": "smallthinker", "source": "test", "precision": "bfloat16",
    "hidden_size": 64, "head_dim": 16, "moe_ffn_hidden_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 96,
    "moe_num_primary_experts": 4, "num_experts_routed": 8,
    "experts_held": [4, 4], "moe_num_active_primary_experts": 2,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "rope_theta": 1500000, "rope_layout": LAYOUT,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 8,
    "published_layer_index": [0, 1, 2, 3], "num_hidden_layers": 4,
    "initializer_range": 0.02, "absent_experts": "folded", "reduced": [],
    "optimizer": {"name": "adam", "learning_rate": 1e-4, "beta1": 0.9,
                  "beta2": 0.999, "epsilon": 1e-8}}
CONFIGS = {
    "tiny-st-share": dict(TINY_ST, name="tiny-st-share"),
    "tiny-st-f32": dict(TINY_ST, name="tiny-st-f32", precision="float32"),
}
TRAFFIC = {"t32-b2": {"seq_len": 32, "batch_per_chip": 2, "global_batch": 2,
                      "mesh_axes": None, "tokens_per_step": 64,
                      "pool_batches": 4, "warmup_steps": 1, "trace_steps": 2,
                      "reference_block_rows": 1}}
SHARE, F32 = "tiny-st-share.t32-b2", "tiny-st-f32.t32-b2"
CELLS = [SHARE, F32]
# bfloat16, as in tiny_lfm.py: a held expert sees ~16 rows here, so one
# near-tie of a top-2 that falls differently in bfloat16 is a large part of
# an expert leaf's gradient, and the limits leave that room. The float32 cell
# is the tight one: the program reads 1e-7 in the loss, 1e-6 in the two
# gradient gaps and 1e-5 in delta_norm_gap; the reference in bfloat16 reads
# loss_gap 2e-5, grad_diff 0.16..0.17, grad_norm_gap 0.004..0.013 and
# delta_norm_gap 0.002..0.006, in float8 3e-4, 0.23..0.36, 0.025 and 0.01
# (seeds 11 and 12; test_smallthinker_family.py reads both again)
LIMITS = {SHARE: {"loss_gap": 2e-4, "grad_diff": 0.6, "grad_norm_gap": 0.15,
                  "delta_norm_gap": 0.4},
          F32: {"loss_gap": 1e-5, "grad_diff": 1e-3, "grad_norm_gap": 1e-3,
                "delta_norm_gap": 1e-3}}
METRICS = ["swa_share_pct", "swa_roofline_pct", "global_attn_share_pct",
           "global_attn_roofline_pct", "st_expert_layer_ms",
           "st_gmm_roofline_pct", "st_load_max_over_mean",
           "st_expert_rows_in_use_pct"]


def add(root):
    """Add the tiny smallthinker cells to the temp root `tiny_root.make`
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
        tiny_root._write(os.path.join(bdir, "limits", cell + ".json"),
                         LIMITS[cell])
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "tiny, for the CPU tests"})
    for m in bench["per_layer"]:
        if m["name"] in METRICS:
            m["workloads"].extend(CELLS)
    tiny_root._write(os.path.join(root, "BENCHMARK.json"), bench)
    return root
