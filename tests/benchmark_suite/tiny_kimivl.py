"""Tiny `kimivl` cells ADDED to a `tiny_root.make` copy, by new files and new
BENCHMARK.json entries only (as `tiny_smallthinker.py` adds its cells): the
five-layer cut's layout in small (one dense layer, then two expert layers,
rotary latent attention in all three) holding half the experts with the
absent ones folded onto them, at d=64, 4 heads of 16 | 8 query/key numbers
and 16 values, latent 32, dense width 128, 8 routed experts of width 32,
top-2, 2 shared experts, vocab 64, T=32; once in bfloat16 as the cell runs,
once in float32 under limits a lower precision cannot meet."""
import json
import os

import tiny_root

TINY_KVL = {
    "family": "kimivl", "source": "test", "precision": "bfloat16",
    "hidden_size": 64, "num_attention_heads": 4, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
    "q_lora_rank": None, "rope_theta": 800000, "rope_scaling": None,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "num_experts_routed": 8, "experts_held": [4, 4],
    "absent_experts": "folded", "num_experts_per_tok": 2,
    "n_shared_experts": 2, "first_k_dense_replace": 1,
    "num_hidden_layers": 3, "published_layer_index": [0, 1, 2],
    "norm_topk_prob": True, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "rms_norm_eps": 1e-5, "vocab_size": 64, "initializer_range": 0.02,
    "reduced": [],
    "optimizer": {"name": "adam", "learning_rate": 1e-4, "beta1": 0.9,
                  "beta2": 0.999, "epsilon": 1e-8}}
CONFIGS = {
    "tiny-kvl-share": dict(TINY_KVL, name="tiny-kvl-share"),
    "tiny-kvl-f32": dict(TINY_KVL, name="tiny-kvl-f32", precision="float32"),
}
TRAFFIC = {"t32-b2": {"seq_len": 32, "batch_per_chip": 2, "global_batch": 2,
                      "mesh_axes": None, "tokens_per_step": 64,
                      "pool_batches": 4, "warmup_steps": 1, "trace_steps": 2,
                      "reference_block_rows": 1}}
SHARE, F32 = "tiny-kvl-share.t32-b2", "tiny-kvl-f32.t32-b2"
CELLS = [SHARE, F32]
# bfloat16, as in tiny_lfm.py: a held expert sees ~32 rows here, so one
# near-tie of a top-2 that falls differently in bfloat16 is a large part of
# an expert leaf's gradient, and the limits leave that room. The float32
# cell is the tight one: the program reads 1e-7 in the loss, 1e-6 in the two
# gradient gaps and 1e-5 in delta_norm_gap; the reference in bfloat16 reads
# loss_gap 3e-5..1.5e-4, grad_diff 0.007..0.008, grad_norm_gap 0.001..0.002
# and delta_norm_gap 0.002..0.003, in float8 2e-4..3e-4, 0.27..0.31, 0.027..
# 0.030 and 0.005..0.011 (seeds 11 and 12; test_kimivl_family.py reads both
# again)
LIMITS = {SHARE: {"loss_gap": 2e-4, "grad_diff": 0.6, "grad_norm_gap": 0.15,
                  "delta_norm_gap": 0.4},
          F32: {"loss_gap": 1e-5, "grad_diff": 1e-3, "grad_norm_gap": 1e-3,
                "delta_norm_gap": 1e-3}}
METRICS = ["kvl_rope_ms", "kvl_attn_share_pct", "kvl_attn_roofline_pct",
           "kvl_expert_layer_ms", "kvl_gmm_roofline_pct",
           "kvl_load_max_over_mean", "kvl_expert_rows_in_use_pct"]


def add(root):
    """Add the tiny kimivl cells to the temp root `tiny_root.make` made;
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
