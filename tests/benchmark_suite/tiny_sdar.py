"""Tiny `sdarmoe` cells ADDED to a `tiny_root.make` copy, by new files and
new BENCHMARK.json entries only (as `tiny_smallthinker.py` adds its cells):
two layers at d=64, 8/1 heads of 16 (the cell's eight query heads a key
head), 16 experts of width 32 routed over with 8 held and the rest folded
onto them, top-2, vocab 96 (MASK = 95), T=32 in blocks of 4, so 64 rows a
sequence; once in bfloat16 as the cell runs, once in float32 under limits a
lower precision cannot meet."""
import json
import os

import tiny_root

TINY_SDAR = {
    "family": "sdarmoe", "source": "test", "precision": "bfloat16",
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 16,
    "hidden_act": "silu", "hidden_size": 64, "mlp_only_layers": [],
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "num_attention_heads": 8, "num_key_value_heads": 1, "num_experts": 8,
    "num_experts_routed": 16, "experts_held": [8, 8],
    "num_experts_per_tok": 2, "num_hidden_layers": 2, "rms_norm_eps": 1e-6,
    "rope_scaling": None, "rope_theta": 1000000, "use_sliding_window": False,
    "vocab_size": 96, "mask_token_id": 95, "initializer_range": 0.02,
    "absent_experts": "folded", "reduced": [],
    "optimizer": {"name": "adam", "learning_rate": 1e-4, "beta1": 0.9,
                  "beta2": 0.999, "epsilon": 1e-8}}
CONFIGS = {
    "tiny-sdar-share": dict(TINY_SDAR, name="tiny-sdar-share"),
    "tiny-sdar-f32": dict(TINY_SDAR, name="tiny-sdar-f32",
                          precision="float32"),
}
TRAFFIC = {"bd4-t32-b2": {
    "seq_len": 32, "batch_per_chip": 2, "global_batch": 2, "mesh_axes": None,
    "tokens_per_step": 64, "pool_batches": 4, "warmup_steps": 1,
    "trace_steps": 2, "reference_block_rows": 1, "block_length": 4,
    "noise_range": [0.45, 0.95], "model_rows_per_step": 128}}
# long enough for the op's "auto" to take the flash kernels (768 rows a
# sequence, three float32 tiles of 128 a half): the program's own path at the
# cell's size, here in interpret mode
TRAFFIC["bd4-t384-b1"] = dict(
    TRAFFIC["bd4-t32-b2"], seq_len=384, batch_per_chip=1, global_batch=1,
    tokens_per_step=384, model_rows_per_step=768)
SHARE, F32 = "tiny-sdar-share.bd4-t32-b2", "tiny-sdar-f32.bd4-t32-b2"
F32_FLASH = "tiny-sdar-f32.bd4-t384-b1"
CELLS = [SHARE, F32, F32_FLASH]
# bfloat16, as in tiny_smallthinker.py: a held expert sees ~32 rows here, so
# one near-tie of a top-2 that falls differently in bfloat16 is a large part
# of an expert leaf's gradient, and the limits leave that room. The float32
# cell is the tight one (test_sdarmoe_family.py reads both controls again)
LIMITS = {SHARE: {"loss_gap": 2e-3, "grad_diff": 0.6, "grad_norm_gap": 0.15,
                  "delta_norm_gap": 0.4},
          F32: {"loss_gap": 1e-5, "grad_diff": 1e-3, "grad_norm_gap": 1e-3,
                "delta_norm_gap": 1e-3}}
LIMITS[F32_FLASH] = LIMITS[F32]
METRICS = ["bd_attn_share_pct", "bd_attn_roofline_pct", "bd_masked_rows_pct",
           "sd_expert_layer_ms", "sd_gmm_roofline_pct"]


def add(root):
    """Add the tiny sdarmoe cells to the temp root `tiny_root.make` made;
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
