"""The `kimilinear` family: the configuration file against the catalog row
it was cut from, the new cell and its entries against the contract,
`flops_kda`'s hand counts, the plain reference against the program at a tiny
size (float32 to rounding), the reference's recurrence against its blocking,
the four new readers with and without something to read, and a tiny cell
through the unedited `run_cell` on the CPU."""
import json
import os
import types

import numpy as np
import pytest

import tiny_kimi
import tiny_root
from benchmark import cells, flops, flops_kda, harness
from benchmark.layer_metrics import _kda

REPO = cells.ROOT
CELL = "kimi-linear-48b-a3b.t8192-b2"
SOURCE = ("https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/"
          "blob/main/config.json")

# the catalog row's `config` (model-configs guide, architectures.jsonl,
# "Kimi-Linear-48B-A3B-Instruct"), every key
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}
REDUCED = ["num_hidden_layers", "num_experts", "num_attention_heads",
           "vocab_size"]


@pytest.fixture(scope="module")
def kimi(tmp_path_factory):
    return tiny_kimi.add(tiny_root.make(tmp_path_factory.mktemp("kimi")))


def held():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        return json.load(f)


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_published_sizes_equal_the_catalog_row(key):
    cfg = held()
    if key in REDUCED:
        assert key in cfg["reduced"]
        assert cfg["published"][key] == CATALOG[key]
        assert cfg[key] < CATALOG[key]
    else:
        assert cfg[key] == CATALOG[key]     # nested groups whole
        assert key not in cfg["reduced"]


def test_the_cut_is_written_into_the_file():
    cfg = held()
    assert cfg["reduced"] == REDUCED
    assert cfg["layer_kinds"] == tiny_kimi.KINDS
    assert cfg["published_layer_index"] == tiny_kimi.PUBLISHED
    # one whole period of the published 3 : 1 pattern behind the dense layer
    lin = CATALOG["linear_attn_config"]
    assert ["mla" if i in lin["full_attn_layers"] else "kda"
            for i in cfg["published_layer_index"]] == cfg["layer_kinds"]
    assert cfg["num_hidden_layers"] == len(cfg["layer_kinds"]) == 5
    # the floors: 8 experts held of all 256 routed over, an eighth of the
    # vocabulary; half the heads of both mixers
    assert cfg["num_experts"] == 8 and cfg["experts_held"] == [0, 8]
    assert cfg["num_experts_routed"] == CATALOG["num_experts"]
    assert cfg["num_attention_heads"] == 16 and cfg["heads_held"] == [0, 16]
    assert cfg["vocab_size"] * 8 == CATALOG["vocab_size"]
    for key in ("published", "assumed", "departures", "reduced_why",
                "deployment"):
        assert cfg[key], key
    assert "32 chips share each layer" in cfg["deployment"]
    assert "22 layers" in cfg["deployment"]
    assert "24 bytes a parameter" in cfg["reduced_why"]
    for key in ("gate_low_rank", "qk_l2_norm", "output_gate", "A_log",
                "dt_bias", "mla_scale", "initializer_range",
                "tie_word_embeddings"):
        assert cfg["assumed"][key], key
    # the parameter list adds up to what the file says it holds
    cell = cells.Cell(CELL)
    specs = cell.family.param_specs(cell.config, cell.traffic)
    count = sum(int(np.prod(shape)) for shape, _d, _k in specs.values())
    assert round(count / 1e6, 1) == 510.7
    per_layer = {}
    for name, (shape, _d, _k) in specs.items():
        if name.startswith("kimi_layer_"):
            i = int(name.split("_")[2])
            per_layer[i] = per_layer.get(i, 0) + int(np.prod(shape))
    millions = [round(per_layer[i] / 1e6, 1) for i in range(5)]
    assert millions == [83.8, 84.3, 84.3, 79.5, 84.3]
    assert specs["kimi_layer_1_experts_gate_up"][0] == (8, 2304, 2048)
    assert specs["kimi_layer_1_router.w_0"] == ((2304, 256), "float32",
                                                "normal")
    assert specs["kimi_layer_3_mla_q.w_0"][0] == (2304, 16 * 192)
    assert specs["kimi_layer_3_mla_kv_b.w_0"][0] == (512, 16 * 256)
    assert specs["kimi_layer_0_kda_qkv.w_0"][0] == (2304, 3 * 16 * 128)
    assert specs["kimi_lm_head"] == ((20480, 2304), "float32", "normal")
    assert not [n for n in specs if "bias" in n and "dt_bias" not in n]


def test_the_new_entries_are_appended_and_resolve():
    """Present, and behind the entries that were there (a later PR appends
    its own behind these: nothing here asks to be last)."""
    b = bench()
    configs = [c["name"] for c in b["configs"]]
    assert configs.index("kimi-linear-48b-a3b") > configs.index("lfm2-8b-a1b")
    config = b["configs"][configs.index("kimi-linear-48b-a3b")]
    assert config["source"] == held()["source"] == SOURCE
    assert len(SOURCE) <= 200
    assert config["reduced"] == REDUCED
    names = [w["name"] for w in b["workloads"]]
    assert names.index(CELL) > names.index("lfm2-8b-a1b.t8192-b2")
    cell_entry = b["workloads"][names.index(CELL)]
    assert cell_entry == dict(cell_entry, config="kimi-linear-48b-a3b",
                              traffic="t8192-b2", chips=1)
    assert len(cell_entry["why"]) <= 200
    metrics = [m["name"] for m in b["per_layer"]]
    first = metrics.index(tiny_kimi.METRICS[0])
    assert first > metrics.index("moe_load_max_over_mean")
    assert metrics[first:first + 4] == tiny_kimi.METRICS
    cell = cells.Cell(CELL)
    t = cell.traffic
    assert (t["seq_len"], t["global_batch"], t["tokens_per_step"],
            t["pool_batches"], t["reference_block_rows"]) \
        == (8192, 2, 16384, 8, 1)
    assert set(tiny_kimi.METRICS) <= {m["name"] for m in cell.per_layer}
    # the expert cell's metrics stay LFM2's alone
    assert not {m["name"] for m in cell.per_layer
                if m["name"].startswith("moe_")}
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s_per_chip", "peak_hbm_gib", "setup_s"}
    assert set(cell.limits) >= set(harness.GAPS)


@pytest.mark.parametrize("name", tiny_kimi.METRICS)
def test_every_new_entry_has_its_reader_and_lists_the_cell(name):
    entry = {m["name"]: m for m in bench()["per_layer"]}[name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "tokens_per_s_per_chip"
    assert entry["source"] == "device_trace"
    assert callable(cells.Cell(CELL).layer_reader(name).read)
    # the old cells do not report it
    assert name not in {m["name"] for m in
                        cells.Cell("lfm2-8b-a1b.t8192-b2").per_layer}


def test_kda_flops_hand_counts():
    assert flops_kda.token_flops(128, 128) == 4 * 2 * 128 * 128 == 131072
    fwd, bwd = flops_kda.call_flops(2, 8192, 16, 128, 128)
    assert fwd == 2 * 8192 * 16 * 131072 and bwd == 2 * fwd
    fwd_b, bwd_b = flops_kda.call_bytes(2, 8192, 16, 128, 128, 2)
    rows = 2 * 8192 * 16
    # q, k, v, o and beta in bfloat16, g in float32
    assert fwd_b == rows * (2 * (4 * 128 + 1) + 4 * 128) and bwd_b == 2 * fwd_b


def test_the_cells_counts_at_full_size():
    cell = cells.Cell(CELL)
    family = cell.family
    # tokens x 8 picks x 8 of 256 experts: 512 rows a held expert
    assert family.expected_held_rows(cell.config, cell.traffic) == 4096
    total = family.train_flops(cell.config, cell.traffic)
    assert 25e12 < total < 28e12
    # the delta rule's required work is a small part of it
    kda = 3 * 4 * flops_kda.call_flops(2, 8192, 16, 128, 128)[0]
    assert 0.01 < kda / total < 0.02
    calls = family.attention_calls(cell.config, cell.traffic)
    assert [(c["kind"], c["count"]) for c in calls] \
        == [("forward", 2), ("backward", 1)]
    assert all((c["batch"], c["q_heads"], c["kv_heads"], c["seq"],
                c["d_qk"], c["d_v"], c["window"])
               == (2, 16, 16, 8192, 192, 128, None) for c in calls)


def _float32_against_the_reference(root, name):
    cell = cells.Cell(name, root)
    cell.config = dict(cell.config, precision="float32")
    devices, _ = harness.attach("cpu", cell.chips)
    runner = harness.Runner(cell, devices)
    try:
        pool = harness.make_pool(cell, 5)
        runner.reset(5)
        got = runner.check_steps(5, pool)
        ref = harness.reference_numbers(
            cell, runner, 5, pool, keep_first_gradient=True,
            compare_with={"program": got["first_gradient"]})
    finally:
        runner.close()
    return got, ref


def test_float32_program_equals_the_reference(kimi):
    """Loss and every leaf's gradient to 1e-4 relative, through the chunked
    delta rule on one side and the token-by-token recurrence on the other,
    for half the heads and half the experts (`Runner` also holds the
    family's parameter list to the program's)."""
    got, ref = _float32_against_the_reference(kimi, "tiny-kimi-share.t64-b2")
    rows = harness.compare(got, ref, {"loss_gap": 1e-5, "grad_diff": 1e-4,
                                      "grad_norm_gap": 1e-4,
                                      "delta_norm_gap": 1e-2})
    assert all(r[3] for r in rows), rows
    for leaf, mine in got["first_gradient"].items():
        theirs = ref["first_gradient"][leaf]
        scale = max(float(np.max(np.abs(theirs))), 1e-6)
        assert float(np.max(np.abs(mine - theirs))) <= 1e-4 * scale, leaf
    assert {leaf.split("_", 3)[-1] for leaf in got["first_gradient"]} >= {
        "kda_A_log", "kda_dt_bias", "kda_f_b.w_0", "mla_kv_b.w_0",
        "shared_down.w_0", "experts_down", "router.w_0"}


def test_blocking_the_reference_changes_no_value(kimi, monkeypatch):
    """The reference walks the recurrence under a checkpoint a block of
    steps, and the MLPs, the experts, the head and the queries in blocks;
    here the same loss and gradient with blocks of 8 and with one block."""
    import jax
    from benchmark import reference, weights
    from benchmark.families import lfm2moe
    cell = cells.Cell("tiny-kimi-share.t64-b2", kimi)
    family = cell.family
    specs = family.param_specs(cell.config, cell.traffic)
    params = weights.as_float32(weights.weight_maker(specs, 0.02)(7))
    blk = family.block_of(harness.make_pool(cell, 7)[0], 0, 2)
    mm = reference.matmul_at("float32")

    def loss_and_grad():
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(lambda p: family.reference_loss(
                p, blk, cell.config, cell.traffic, mm))(params)

    whole_loss, whole = loss_and_grad()
    for name in ("MLP_CHUNK", "Q_BLOCK", "SCAN_BLOCK"):
        monkeypatch.setattr(family, name, 8)
    monkeypatch.setattr(lfm2moe, "MLP_CHUNK", 8)
    blocked_loss, blocked = loss_and_grad()
    assert float(blocked_loss) == pytest.approx(float(whole_loss), rel=1e-6)
    for leaf in whole:
        scale = max(float(np.max(np.abs(whole[leaf]))), 1e-8)
        assert float(np.max(np.abs(blocked[leaf] - whole[leaf]))) \
            <= 1e-4 * scale, leaf


def test_a_tiny_cell_runs_through_run_cell_on_the_cpu(kimi):
    name = "tiny-kimi-share.t64-b2"
    cell = cells.Cell(name, kimi)
    assert set(tiny_kimi.METRICS) <= {m["name"] for m in cell.per_layer}
    # long enough for the three steps before the profiler and the two under
    # it, on a loaded machine (a step here takes 0.07-0.25 s)
    traced = harness.run_cell(name, 2 ** 31 + 5, 2.0, 1, platform="cpu",
                              root=kimi)
    assert traced["correct"] is True and traced["failed"] == 0
    # no device plane off the TPU: the four trace readers find nothing and
    # the line leaves them out
    assert not set(tiny_kimi.METRICS) & set(traced["metrics"])
    assert traced["metrics"]["recompiles_in_window"]["value"] == 0
    assert traced["metrics"]["state_gib"]["value"] > 0


# ---------------------------------------------------------------------------
# the readers on records made by hand
# ---------------------------------------------------------------------------

def _read(metric, record):
    return cells.Cell(CELL).layer_reader(metric).read(record)


def test_the_kda_readers_take_the_delta_rule_layers_own_op_types(
        monkeypatch):
    assert _kda.OP_TYPES == ("kda_attention", "causal_conv1d",
                             "head_l2_norm", "kda_gate", "kda_out_norm")
    seen = {}

    def op_type_ms(record, op_types):
        seen["types"] = op_types
        return 42.0

    monkeypatch.setattr(_kda._hybrid, "op_type_ms", op_type_ms)
    record = {"traced": {"step_busy_ms": 420.0}}
    assert _read("kda_device_ms", record) == 42.0
    assert seen["types"] == _kda.OP_TYPES
    assert _read("kda_share_pct", record) == pytest.approx(10.0)


def test_the_mla_readers_count_the_flash_kernels_by_name():
    cell = cells.Cell(CELL)
    peaks = flops.peaks_for("TPU v5 lite")
    record = {"cell": cell, "peaks": peaks,
              "traced": {"op_seconds": {"custom-call:flash_fwd": 0.08,
                                        "custom-call:flash_bwd_dkv": 0.06,
                                        "custom-call:flash_bwd_dq": 0.06,
                                        "custom-call:moe_gmm_fwd": 9.0},
                         "steps_seen": 4, "busy_s": 2.0}}
    assert _read("mla_attn_share_pct", record) == pytest.approx(10.0)
    # one layer's calls: 16 heads x 2 rows over the causal area at 192 and
    # 128; forward twice (the replay), backward once; all compute-bound
    area = 2 * 16 * (8192 * 8193 // 2)
    qk, pv = 2 * area * 192, 2 * area * 128
    least = (2 * (qk + pv) + 3 * qk + 2 * pv) / peaks["bf16_flops_per_s"]
    got = _read("mla_attn_roofline_pct", record)
    assert got == pytest.approx(100 * least * 4 / 0.2)
    assert 0 < got < 100


@pytest.mark.parametrize("metric", tiny_kimi.METRICS)
def test_each_reader_is_left_out_where_there_is_nothing_to_read(metric):
    """A parent program has no `kda_*` scope; a run off the chip no device
    plane: every reader returns None and does not raise."""
    cell = types.SimpleNamespace(root="/nonexistent", name="tiny.cell",
                                 family=types.SimpleNamespace(),
                                 config={"precision": "bfloat16"},
                                 traffic={"trace_steps": 4})
    for record in ({"cell": cell, "traced": None},
                   {"cell": cell, "traced": None, "obs_spans": [],
                    "peaks": None},
                   {"cell": cell, "obs_spans": [],
                    "traced": {"op_seconds": {"custom-call:moe_gmm_fwd": 1.0},
                               "steps_seen": 4, "busy_s": 2.0,
                               "step_busy_ms": 100.0},
                    "peaks": flops.peaks_for("TPU v5 lite"),
                    "_scopes": {"trace": None}}):
        assert _read(metric, record) is None
