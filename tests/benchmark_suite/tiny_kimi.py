"""A tiny `kimilinear` cell ADDED to a `tiny_root.make` copy, by new files
and new BENCHMARK.json entries only (as `tiny_lfm.py` adds the expert
cells): the five-layer cut's layout (a KDA layer over the dense MLP, then
KDA, KDA, MLA, KDA over experts with a shared expert) holding half the heads
and half the experts, at d=64, 4 heads (KDA 16 wide; MLA 16 + 8 query/key,
16 value, latent 24), gates of rank 8, dense width 128, 16 experts of width
32, top-2, conv width 4, vocab 96, T=64 (one chunk of the delta rule; the
op's own test walks several, and tests/test_kimi_linear.py adds the shares
up to the uncut layer)."""
import json
import os

import tiny_root

KINDS = ["kda", "kda", "kda", "mla", "kda"]
PUBLISHED = [1, 2, 3, 4, 5]
LINEAR = {"full_attn_layers": [4, 8], "head_dim": 16, "kda_layers":
          [1, 2, 3, 5, 6, 7], "num_heads": 4, "short_conv_kernel_size": 4}
TINY_KIMI = {
    "family": "kimilinear", "source": "test", "precision": "bfloat16",
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "linear_attn_config": LINEAR, "gate_low_rank": 8, "kv_lora_rank": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "num_experts_routed": 16, "num_experts_per_token": 2,
    "num_shared_experts": 1, "first_k_dense_replace": 1,
    "moe_renormalize": True, "routed_scaling_factor": 2.446,
    "rms_norm_eps": 1e-5, "vocab_size": 96, "initializer_range": 0.02,
    "layer_kinds": KINDS, "published_layer_index": PUBLISHED,
    "num_hidden_layers": 5, "reduced": [],
    "optimizer": {"name": "adam", "learning_rate": 1e-4, "beta1": 0.9,
                  "beta2": 0.999, "epsilon": 1e-8}}
CONFIGS = {
    "tiny-kimi-share": dict(TINY_KIMI, name="tiny-kimi-share", num_experts=8,
                            experts_held=[8, 8], num_attention_heads=2,
                            heads_held=[2, 2]),
}
TRAFFIC = {"t64-b2": {"seq_len": 64, "batch_per_chip": 2, "global_batch": 2,
                      "mesh_axes": None, "tokens_per_step": 128,
                      "pool_batches": 4, "warmup_steps": 1, "trace_steps": 2,
                      "reference_block_rows": 1}}
CELLS = ["tiny-kimi-share.t64-b2"]
# as in tiny_lfm.py a held expert sees ~16 rows here, so one near-tie of a
# top-2 that falls differently in bfloat16 is a large part of an expert
# leaf's gradient: tiny_lfm's limits leave that room. The tight comparison at
# this size is the float32 one (test_kimilinear_family.py).
LIMITS = {"loss_gap": 2e-4, "grad_diff": 0.6, "grad_norm_gap": 0.15,
          "delta_norm_gap": 0.4}
METRICS = ["kda_share_pct", "kda_device_ms", "mla_attn_share_pct",
           "mla_attn_roofline_pct"]


def add(root):
    """Add the tiny kimilinear cells to the temp root `tiny_root.make`
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
