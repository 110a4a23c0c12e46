"""Tiny `nemotronh` cells ADDED to a `tiny_root.make` copy, by new files and
new BENCHMARK.json entries only (as `tiny_kimivl.py` adds its cells): the
seven-layer cut's layout in small (`MEM*E`: two Mamba-2 layers, two expert
layers, one attention layer, each layer one block alone) holding half the
experts with the absent ones folded onto them, at d=64, 4 Mamba heads of 8
in 2 groups at a state of 16 and chunks of 16, 4 query heads on 1 key head
of 16, 8 routed experts of width 24 (no multiple of 16: the width off the
lane grid in small), top-2, a shared expert of 48, vocab 64, T=40 (two and
a half chunks); once in bfloat16 as the cell runs, once in float32 under
limits a lower precision cannot meet."""
import json
import os

import tiny_root

TINY_NH = {
    "family": "nemotronh", "source": "test", "precision": "bfloat16",
    "hidden_size": 64, "hybrid_override_pattern": "MEM*E",
    "num_hidden_layers": 5, "mamba_num_heads": 4, "mamba_head_dim": 8,
    "n_groups": 2, "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16,
    "mamba_hidden_act": "silu", "use_conv_bias": True,
    "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 16,
    "mlp_hidden_act": "relu2", "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 48, "n_shared_experts": 1,
    "n_routed_experts": 4, "num_experts_routed": 8, "experts_held": [4, 4],
    "absent_experts": "folded", "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5, "n_group": 1,
    "topk_group": 1, "layer_norm_epsilon": 1e-5, "vocab_size": 64,
    "initializer_range": 0.02, "reduced": [],
    "optimizer": {"name": "adam", "learning_rate": 1e-4, "beta1": 0.9,
                  "beta2": 0.999, "epsilon": 1e-8}}
CONFIGS = {
    "tiny-nh-share": dict(TINY_NH, name="tiny-nh-share"),
    "tiny-nh-f32": dict(TINY_NH, name="tiny-nh-f32", precision="float32"),
}
TRAFFIC = {"t40-b2": {"seq_len": 40, "batch_per_chip": 2, "global_batch": 2,
                      "mesh_axes": None, "tokens_per_step": 80,
                      "pool_batches": 4, "warmup_steps": 1, "trace_steps": 2,
                      "reference_block_rows": 1}}
SHARE, F32 = "tiny-nh-share.t40-b2", "tiny-nh-f32.t40-b2"
CELLS = [SHARE, F32]
# bfloat16, as in tiny_kimivl.py: a held expert sees ~40 rows here, so one
# near-tie of a top-2 that falls differently in bfloat16 is a large part of
# an expert leaf's gradient, and the limits leave that room. The float32
# cell is the tight one (test_nemotronh_family.py reads both controls)
LIMITS = {SHARE: {"loss_gap": 2e-4, "grad_diff": 0.6, "grad_norm_gap": 0.15,
                  "delta_norm_gap": 0.4},
          F32: {"loss_gap": 1e-5, "grad_diff": 1e-3, "grad_norm_gap": 1e-3,
                "delta_norm_gap": 1e-3}}
METRICS = ["ssd_device_ms", "ssd_share_pct", "ssd_roofline_pct",
           "nt_expert_layer_ms", "nt_gmm_roofline_pct",
           "nt_expert_rows_in_use_pct", "nt_load_max_over_mean",
           "nt_attn_share_pct", "nt_attn_roofline_pct"]


def add(root):
    """Add the tiny nemotronh cells to the temp root `tiny_root.make` made;
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
