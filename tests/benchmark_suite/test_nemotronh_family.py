"""The `nemotronh` family: the configuration file against the catalog row it
was cut from, the new cell and its entries against the contract (present and
in order; a later PR appends its own behind them), the cell's counts at full
size, the plain reference against the program at a tiny size (float32 to
rounding), the reference's blocking and its recurrence, `correct` under the
lower-precision controls and under a broken timed path, the new readers on
records made by hand, and a tiny cell through the unedited `run_cell` on the
CPU."""
import json
import os
import types

import numpy as np
import pytest

import tiny_root
import tiny_nemotron as tiny_nh
from benchmark import cells, flops, flops_ssd, harness

REPO = cells.ROOT
CELL = "nemotron-twotower-30b-a3b.t8192-b2"
CONFIG = "nemotron-twotower-30b-a3b"
SOURCE = ("https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-"
          "Base-BF16/blob/main/config.json")
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

# the catalog row's `config` (model-configs guide, architectures.jsonl,
# "Nemotron-Labs-TwoTower-30B-A3B-Base-BF16"), every key
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern": PATTERN, "intermediate_size": 1856,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h", "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_limit": [0, None],
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072}
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size"]


@pytest.fixture(scope="module")
def nhr(tmp_path_factory):
    return tiny_nh.add(tiny_root.make(tmp_path_factory.mktemp("nh")))


def held():
    with open(os.path.join(REPO, "benchmark", "configs",
                           CONFIG + ".json")) as f:
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
        assert cfg[key] != CATALOG[key]
        if key == "hybrid_override_pattern":    # the model's own start
            assert CATALOG[key].startswith(cfg[key])
        else:
            assert cfg[key] < CATALOG[key]
    else:
        assert cfg[key] == CATALOG[key]
        assert key not in cfg["reduced"]


def test_the_cut_is_written_into_the_file():
    cfg = held()
    assert cfg["reduced"] == REDUCED
    assert not [key for key in cfg["reduced"] if "head" in key]
    # published layers 0-6: three Mamba-2, three expert, one attention
    assert cfg["hybrid_override_pattern"] == "MEMEM*E" == PATTERN[:7]
    assert cfg["num_hidden_layers"] == 7
    assert cfg["published_layer_index"] == list(range(7))
    # the pattern's unit, seven layers, four times in a row from layer 6
    assert PATTERN[6:34] == "EMEMEM*" * 4
    assert (PATTERN.count("M"), PATTERN.count("E"),
            PATTERN.count("*")) == (23, 23, 6)
    # the floors: 8 experts held of all 128 routed over, an eighth of the
    # vocabulary; no head count cut
    assert cfg["n_routed_experts"] == 8 and cfg["experts_held"] == [0, 8]
    assert cfg["num_experts_routed"] == CATALOG["n_routed_experts"]
    assert cfg["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert cfg["absent_experts"] == "folded"
    assert any("folded" in d and "PR 38" in d for d in cfg["departures"])
    assert any("NOT padded" in d and "1856" in d for d in cfg["departures"])
    for key in ("published", "assumed", "departures", "reduced_why",
                "deployment", "not_built"):
        assert cfg[key], key
    assert "16 chips share each layer" in cfg["deployment"]
    assert "45 layers" in cfg["deployment"]
    assert "24 bytes a parameter" in cfg["reduced_why"]
    # what is not built is said, with why
    tower = cfg["not_built"]["denoiser_tower"]
    for word in ("denoiser", "adaLN", "cross-tower conditioning",
                 "block-diffusion decoding", "cannot be written down"):
        assert word in tower, word
    for key in ("denoiser_tower", "mamba", "mamba_start", "attention",
                "router", "expert_bias", "auxiliary_loss", "absent_experts",
                "experts", "norm", "head", "initializer_range", "optimizer",
                "recompute"):
        assert cfg["assumed"][key], key
    assert "NOT assumed" in cfg["assumed"]["denoiser_tower"]
    assert "NO rotary turn" in cfg["assumed"]["attention"]
    assert "1e-20" in cfg["assumed"]["router"]
    # the parameter list adds up to what the file says it holds
    cell = cells.Cell(CELL)
    specs = cell.family.param_specs(cell.config, cell.traffic)
    count = sum(int(np.prod(shape)) for shape, _d, _k in specs.values())
    assert count == 528092736 and "528.09M" in cfg["reduced_why"]
    assert abs(count - 528.1e6) < 0.02 * 528.1e6
    per_layer = {}
    for name, (shape, _d, _k) in specs.items():
        if name.startswith("nh_layer_"):
            i = int(name.split("_")[2])
            per_layer[i] = per_layer.get(i, 0) + int(np.prod(shape))
    mamba = 2688 * 10304 + 4096 * 2688 + 4 * 6144 + 6144 + 3 * 64 + 4096 \
        + 2688
    experts = 8 * 2 * 2688 * 1856 + 2 * 2688 * 3712 + 2688 * 128 + 2688
    attention = 2688 * 36 * 128 + 4096 * 2688 + 2688
    assert per_layer == {0: mamba, 1: experts, 2: mamba, 3: experts,
                         4: mamba, 5: attention, 6: experts}
    assert specs["nh_layer_1_experts_up"] == ((8, 2688, 1856), "bfloat16",
                                              "normal")
    assert specs["nh_layer_1_experts_down"][0] == (8, 1856, 2688)
    assert specs["nh_layer_1_shared_up.w_0"][0] == (2688, 3712)
    assert specs["nh_layer_1_router.w_0"] == ((2688, 128), "float32",
                                              "normal")
    assert specs["nh_layer_0_mamba_in_proj.w_0"][0] == (2688, 10304)
    assert specs["nh_layer_0_mamba_conv.w_0"][0] == (4, 6144)
    assert specs["nh_layer_0_mamba_conv.b_0"] == ((6144,), "bfloat16",
                                                  "zeros")
    assert specs["nh_layer_0_mamba_A_log"] == ((64,), "float32", "zeros")
    assert specs["nh_layer_0_mamba_D"] == ((64,), "float32", "ones")
    assert specs["nh_layer_5_attn_qkv.w_0"][0] == (2688, 36 * 128)
    assert specs["nh_lm_head"] == specs["nh_word_embedding"] \
        == ((16384, 2688), "float32", "normal")
    # one norm a layer
    assert len([n for n in specs if n.endswith("_norm_s")]) == 7 + 3


def test_the_new_entries_are_present_in_order_and_resolve():
    """Behind the entries that were there, in the order given; nothing here
    asks to be last."""
    b = bench()
    configs = [c["name"] for c in b["configs"]]
    assert configs.index(CONFIG) > configs.index("kimi-vl-a3b")
    config = b["configs"][configs.index(CONFIG)]
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["source"] == held()["source"] == SOURCE
    assert len(SOURCE) <= 200 and len(config["why"]) <= 200
    assert config["reduced"] == REDUCED
    names = [w["name"] for w in b["workloads"]]
    assert names.index(CELL) > names.index("kimi-vl-a3b.t8192-b2")
    entry = b["workloads"][names.index(CELL)]
    assert entry == dict(entry, config=CONFIG, traffic="t8192-b2", chips=1)
    assert len(entry["why"]) <= 200 and "16x" in entry["why"]
    metrics = [m["name"] for m in b["per_layer"]]
    first = metrics.index(tiny_nh.METRICS[0])
    assert first > metrics.index("kvl_expert_rows_in_use_pct")
    assert metrics[first:first + len(tiny_nh.METRICS)] == tiny_nh.METRICS
    cell = cells.Cell(CELL)
    t = cell.traffic
    assert (t["seq_len"], t["batch_per_chip"], t["global_batch"],
            t["tokens_per_step"], t["pool_batches"], t["warmup_steps"],
            t["trace_steps"], t["reference_block_rows"]) \
        == (8192, 2, 2, 16384, 8, 2, 4, 1)
    assert set(tiny_nh.METRICS) <= {m["name"] for m in cell.per_layer}
    assert "mfu_pct" in {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s_per_chip", "peak_hbm_gib", "setup_s"}
    assert set(cell.limits) >= set(harness.GAPS)
    assert all(cell.limits["readings"][gap] for gap in harness.GAPS)


@pytest.mark.parametrize("name", tiny_nh.METRICS)
def test_every_new_entry_has_its_reader_and_lists_the_cell(name):
    entry = {m["name"]: m for m in bench()["per_layer"]}[name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "tokens_per_s_per_chip"
    assert entry["layer"] in ("Pallas kernels", "Step program")
    assert (entry["unit"] == "%") == name.endswith("_pct")
    assert callable(cells.Cell(CELL).layer_reader(name).read)
    # the old cells do not report it
    for old in ("kimi-vl-a3b.t8192-b2", "phi4-mini-flash.t8192-b1"):
        assert name not in {m["name"] for m in cells.Cell(old).per_layer}


def test_the_cells_counts_at_full_size():
    cell = cells.Cell(CELL)
    family = cell.family
    assert family.expected_held_rows(cell.config, cell.traffic) == 98304
    assert family.expected_held_rows(
        dict(cell.config, absent_experts="nothing"), cell.traffic) == 6144
    calls = family.attention_calls(cell.config, cell.traffic)
    assert [(c["kind"], c["count"]) for c in calls] == [
        ("forward", 2), ("backward", 1)]
    assert all((c["batch"], c["q_heads"], c["kv_heads"], c["seq"],
                c["d_qk"], c["d_v"], c["window"])
               == (2, 32, 2, 8192, 128, 128, None) for c in calls)
    gmm = family.gmm_calls(cell.config, cell.traffic)
    assert [(c["layer"], c["k"], c["n"]) for c in gmm] == [
        ("nh_layer_%d" % i, k, n) for i in (1, 3, 6)
        for k, n in ((2688, 1856), (1856, 2688))]
    assert all((c["groups"], c["fwd"], c["dx"], c["dw"]) == (8, 2, 1, 1)
               for c in gmm)
    scans = family.scan_calls(cell.config, cell.traffic)
    assert [c["layer"] for c in scans] == ["nh_layer_0", "nh_layer_2",
                                           "nh_layer_4"]
    assert all((c["batch"], c["seq"], c["heads"], c["head_dim"],
                c["groups"], c["state"], c["fwd"], c["bwd"])
               == (2, 8192, 64, 64, 8, 128, 2, 1) for c in scans)
    # by hand, at the PUBLISHED expert width: the mixers' projections and
    # the recurrence's required work, attention by the visible pairs, the
    # router, every pick's two matmuls, the shared expert, the head;
    # backward twice the forward, the replay not counted
    tokens, d = 16384, 2688
    mamba = 2 * tokens * (d * 10304 + 4096 * d) \
        + 2 * 8192 * 64 * 3 * 2 * 128 * 64
    experts = 2 * tokens * d * 128 + 2 * 98304 * 2 * d * 1856 \
        + 2 * tokens * 2 * d * 3712
    attention = 2 * tokens * (d * 36 * 128 + 4096 * d) \
        + 2 * 32 * 2 * (8192 * 8193 // 2) * 2 * 128
    head = 2 * tokens * d * 16384
    want = 3 * (3 * mamba + 3 * experts + attention + head)
    assert family.train_flops(cell.config, cell.traffic) == want
    assert 45.4e12 < want < 45.5e12
    # the shares the issue gave: experts about half, Mamba-2 about a
    # quarter, attention about an eighth
    assert 0.50 < 3 * 3 * experts / want < 0.54
    assert 0.24 < 3 * 3 * mamba / want < 0.28
    assert 0.10 < 3 * attention / want < 0.14
    assert flops_ssd.call_flops(2, 8192, 64, 64, 128) == (
        2 * 8192 * 64 * 49152, 2 * 2 * 8192 * 64 * 49152)
    assert flops_ssd.call_bytes(2, 8192, 64, 64, 8, 128, 2)[0] \
        == 2 * 8192 * 2 * (2 * 4096 + 2 * 1024 + 64)


def _against_the_reference(root, name, **kw):
    cell = cells.Cell(name, root)
    devices, _ = harness.attach("cpu", cell.chips)
    runner = harness.Runner(cell, devices)
    try:
        pool = harness.make_pool(cell, 5)
        runner.reset(5)
        got = runner.check_steps(5, pool)
        ref = harness.reference_numbers(
            cell, runner, 5, pool, keep_first_gradient=True,
            compare_with={"program": got["first_gradient"]}, **kw)
    finally:
        runner.close()
    return cell, got, ref


def test_float32_program_equals_the_reference(nhr):
    """Loss and every leaf's gradient to 1e-4 relative under the float32
    cell's own limits (`Runner` also holds the family's parameter list to
    the program's)."""
    cell, got, ref = _against_the_reference(nhr, tiny_nh.F32)
    rows = harness.compare(got, ref, cell.limits)
    assert all(r[3] for r in rows), rows
    for leaf, mine in got["first_gradient"].items():
        theirs = ref["first_gradient"][leaf]
        scale = max(float(np.max(np.abs(theirs))), 1e-6)
        assert float(np.max(np.abs(mine - theirs))) <= 1e-4 * scale, leaf
    assert {leaf.split("_", 3)[-1] for leaf in got["first_gradient"]} >= {
        "norm_s", "mamba_in_proj.w_0", "mamba_conv.w_0", "mamba_conv.b_0",
        "mamba_dt_bias", "mamba_A_log", "mamba_D", "mamba_norm_s",
        "mamba_out_proj.w_0", "router.w_0", "experts_up", "experts_down",
        "shared_up.w_0", "shared_down.w_0", "attn_qkv.w_0", "attn_out.w_0"}


@pytest.mark.parametrize("precision", ["bfloat16", "float8"])
def test_correct_fails_under_a_lower_precision_control(nhr, precision):
    """The reference computed in the precision below the float32 cell's,
    compared as a program is: outside the cell's limits by `grad_diff` and
    `loss_gap` at least, on both seeds."""
    from benchmark import read_control
    cell = cells.Cell(tiny_nh.F32, nhr)
    got = read_control.read(tiny_nh.F32, [11, 12], platform="cpu", root=nhr,
                            say=lambda _line: None,
                            bfloat16=precision == "bfloat16")
    kind = "bfloat16" if precision == "bfloat16" else "control_float8"
    times = 3 if precision == "bfloat16" else 100
    for seed, gaps in got[kind].items():
        assert gaps["grad_diff"] > times * cell.limits["grad_diff"], seed
        assert gaps["loss_gap"] > cell.limits["loss_gap"], seed


def test_a_broken_timed_path_is_not_correct(nhr):
    from test_harness import _half_batch, _state_unchanged
    for broken, failing in ((_half_batch, "grad_diff"),
                            (_state_unchanged, "delta_norm_gap")):
        lines = []
        out = harness.run_cell(tiny_nh.F32, 2 ** 31 + 5, 0.3, 0,
                               platform="cpu", root=nhr, say=lines.append,
                               broken=broken)
        assert out["correct"] is False
        failed = [ln for ln in lines if ln.startswith("check ")
                  and "FAILED" in ln]
        assert any(failing in ln for ln in failed), lines


def test_blocking_the_reference_changes_no_value(nhr, monkeypatch):
    """The reference walks the projections, the experts, the head, the
    queries and the recurrence in blocks so that it fits the chip at the
    cell's size; here the same loss and gradient with blocks of 8 and with
    one block."""
    import jax
    from benchmark import reference, weights
    from benchmark.families import lfm2moe
    cell = cells.Cell(tiny_nh.F32, nhr)
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


def test_the_references_scan_is_the_recurrence_written_out():
    """`recurrence` against a Python loop over t in float64 (no chunk, no
    matmul), and the family imports nothing of paddle_tpu but the model it
    builds."""
    import jax.numpy as jnp
    family = cells.Cell(CELL).family
    rng = np.random.default_rng(1)
    n, t, h, p, st = 2, 11, 3, 4, 5
    x = rng.standard_normal((n, t, h, p))
    dt = rng.uniform(0.1, 1.0, (n, t, h))
    a = rng.uniform(0.2, 0.9, (n, t, h))
    b, c = (rng.standard_normal((n, t, st)) for _ in range(2))
    state = np.zeros((n, h, st, p))
    want = np.zeros((n, t, h, p))
    for i in range(t):
        state = a[:, i, :, None, None] * state \
            + dt[:, i, :, None, None] * b[:, i, None, :, None] \
            * x[:, i, :, None, :]
        want[:, i] = np.einsum("nhsp,ns->nhp", state, c[:, i])
    got = family.recurrence(*(jnp.asarray(m, jnp.float32)
                              for m in (x, dt, a, b, c)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)
    with open(os.path.join(REPO, "benchmark", "families",
                           "nemotronh.py")) as f:
        source = f.read()
    assert "paddle_tpu" not in source.replace(
        "from paddle_tpu.models import nemotron_h", "").replace(
        "paddle_tpu's normal path", "").replace(
        "nothing of\npaddle_tpu", "")
    assert "lax.scan(step" in source and "cumsum" not in source


def test_a_tiny_cell_runs_through_run_cell_on_the_cpu(nhr):
    cell = cells.Cell(tiny_nh.SHARE, nhr)
    assert set(tiny_nh.METRICS) <= {m["name"] for m in cell.per_layer}
    out = harness.run_cell(tiny_nh.SHARE, 2 ** 31 + 5, 0.3, 0,
                           platform="cpu", root=nhr)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s_per_chip", "peak_hbm_gib",
                                   "setup_s"}
    traced = harness.run_cell(tiny_nh.SHARE, 6, 1.0, 1, platform="cpu",
                              root=nhr)
    assert traced["correct"] is True
    # no device plane off the TPU: the trace readers find nothing and the
    # line leaves them out; the spans' readers read
    assert set(tiny_nh.METRICS) & set(traced["metrics"]) == {
        "nt_load_max_over_mean", "nt_expert_rows_in_use_pct"}
    assert traced["metrics"]["nt_load_max_over_mean"]["value"] >= 1.0
    assert 0 < traced["metrics"]["nt_expert_rows_in_use_pct"]["value"] <= 100
    assert traced["metrics"]["recompiles_in_window"]["value"] == 0


# ---------------------------------------------------------------------------
# the readers on records made by hand
# ---------------------------------------------------------------------------

def _read(metric, record):
    return cells.Cell(CELL).layer_reader(metric).read(record)


FWD = "jit(step)/forward/remat_block/jvp(forward/%s)/%s"
REPLAY = ("jit(step)/backward/remat_block/transpose(jvp(forward/"
          "remat_block))/jvp()/checkpoint/rematted_computation/forward/"
          "%s/%s")
PULL = ("jit(step)/backward/remat_block/transpose(jvp(forward/"
        "remat_block))/jvp()/checkpoint/forward/%s/%s")


def _record():
    """A traced window of two steps on one chip: the scan forward (its two
    inner scopes), replayed and pulled back, its convolution and gate norm,
    a flash forward and its split backward, a grouped matmul under the
    expert op and a projection; times in ns."""
    ops, at = [], [1000.0]

    def op(name, ns, tf_op):
        ops.append(("%%%s.1 = bf16[2]{0} %s" % (name, "custom-call(...)"
                    if "fusion" not in name else "fusion(...)"),
                    at[0], at[0] + ns, tf_op))
        at[0] += ns

    modules = []
    for _step in range(2):
        start = at[0]
        op("fusion", 4e6, FWD % ("causal_conv1d", "mul"))
        op("fusion", 6e6, FWD % ("mamba2_scan", "ssd_states/dot_general"))
        op("fusion", 14e6, FWD % ("mamba2_scan", "ssd_outputs/dot_general"))
        op("fusion", 3e6, FWD % ("mamba2_gate_norm", "mul"))
        op("fusion", 100e6, "jit(step)/forward/mul/dot_general")
        op("flash_fwd", 10e6, "jit(step)/forward/"
           "scaled_dot_product_attention/flash_fwd/pallas_call")
        op("moe_gmm_fwd", 30e6, "jit(step)/forward/moe_experts/moe_gmm_fwd/"
           "pallas_call")
        op("fusion", 6e6, "jit(step)/forward/moe_combine/add")
        op("fusion", 20e6, REPLAY % ("mamba2_scan", "ssd_outputs/exp"))
        op("fusion", 40e6, PULL % ("mamba2_scan",
                                   "ssd_outputs_back/dot_general"))
        op("fusion", 5e6, PULL % ("causal_conv1d", "mul"))
        op("flash_bwd_dkv", 12e6, "jit(step)/backward/"
           "scaled_dot_product_attention/flash_bwd_dkv/pallas_call")
        op("flash_bwd_dq", 8e6, "jit(step)/backward/"
           "scaled_dot_product_attention/flash_bwd_dq/pallas_call")
        modules.append(("jit_step(1)", start, at[0]))
    trace = {"devices": {0: {"ops": ops, "modules": modules}},
             "host": {"main": [("bench.traced", 0.0, at[0] + 1000.0)]}}
    busy = (at[0] - 1000.0) / 1e9
    return {"cell": cells.Cell(CELL), "peaks": flops.peaks_for("TPU v5 lite"),
            "traced": {"busy_s": busy, "steps_seen": 2,
                       "step_busy_ms": busy * 1e3 / 2,
                       "op_seconds": {"custom-call:flash_fwd": 0.020,
                                      "custom-call:flash_bwd_dkv": 0.024,
                                      "custom-call:flash_bwd_dq": 0.016,
                                      "custom-call:moe_gmm_fwd": 0.060,
                                      "fusion": 0.396}},
            "obs_spans": [
                {"name": "moe.load", "labels": {
                    "layer": "nh_layer_%d" % i, "rows_held": 98304,
                    "rows_max": 24576, "rows_mean": 12288.0,
                    "rows_in_use": 100352, "rows_buffer": 102400,
                    "bounded": 0}}
                for _step in range(8) for i in (1, 3, 6)],
            "_scopes": {"trace": trace}}


def test_the_scans_time_is_read_under_its_own_op_types():
    record = _record()
    # scan 6 + 14 + 20 + 40, convolution 4 + 5, gate norm 3
    assert _read("ssd_device_ms", record) == pytest.approx(92.0)
    assert _read("ssd_share_pct", record) == pytest.approx(
        100 * 92.0 / record["traced"]["step_busy_ms"])
    # by hand: the recurrent form is bytes-bound on a v5e (0.26 ms of FLOPs
    # against 0.41 ms of traffic a forward call); three layers, the forward
    # twice and the backward at twice its bytes
    peaks = record["peaks"]
    fwd_bytes = 2 * 8192 * 2 * (2 * 4096 + 2 * 1024 + 64)
    assert fwd_bytes / peaks["hbm_bytes_per_s"] \
        > 2 * 8192 * 64 * 49152 / peaks["bf16_flops_per_s"]
    least = 3 * 4 * fwd_bytes / peaks["hbm_bytes_per_s"]
    assert _read("ssd_roofline_pct", record) == pytest.approx(
        100 * least * 1e3 / 80.0, rel=1e-6)
    assert _read("ssd_roofline_pct", record) < 100
    # the expert layer's four op types: the kernel and the combine
    assert _read("nt_expert_layer_ms", record) == pytest.approx(36.0)


def test_the_flash_and_grouped_matmul_readers_count_this_cells_calls():
    record = _record()
    assert _read("nt_attn_share_pct", record) == pytest.approx(
        100 * 0.060 / record["traced"]["busy_s"])
    # by hand: one layer x 2 rows x 32 query heads over the visible pairs
    # at 128 and 128: QK^T and PV forward twice (the replay), five matmuls
    # of the backward, compute-bound
    peak = record["peaks"]["bf16_flops_per_s"]
    area = 8192 * 8193 // 2
    fwd = 2 * 2 * 32 * area * (128 + 128)
    bwd = 2 * 2 * 32 * area * (3 * 128 + 2 * 128)
    least = (2 * fwd + bwd) / peak
    assert _read("nt_attn_roofline_pct", record) == pytest.approx(
        100 * least * 2 / 0.060, rel=1e-3)
    # the grouped matmuls at the rows the spans counted, 98,304 a layer,
    # at the published 1856: two matrices an expert
    gmm = _read("nt_gmm_roofline_pct", record)
    one = 2 * 98304 * 2 * 2688 * 1856
    assert gmm == pytest.approx(100 * 3 * 4 * one / peak * 2 / 0.060,
                                rel=0.02)
    assert _read("nt_load_max_over_mean", record) == pytest.approx(2.0)
    assert _read("nt_expert_rows_in_use_pct", record) == pytest.approx(98.0)


@pytest.mark.parametrize("metric", tiny_nh.METRICS)
def test_each_reader_is_left_out_where_there_is_nothing_to_read(metric):
    """A parent program has no `mamba2_scan` scope and no `moe.load` span of
    these layers; a run off the chip no device plane: every reader returns
    None and does not raise."""
    cell = types.SimpleNamespace(root="/nonexistent", name="tiny.cell",
                                 family=types.SimpleNamespace(),
                                 config={"precision": "bfloat16"},
                                 traffic={"trace_steps": 4})
    for record in ({"cell": cell, "traced": None},
                   {"cell": cell, "traced": None, "obs_spans": [],
                    "peaks": None},
                   {"cell": cell, "obs_spans": [{"name": "exec.step",
                                                 "labels": {}}],
                    "traced": {"op_seconds": {"custom-call:fusion": 1.0},
                               "steps_seen": 4, "busy_s": 2.0,
                               "step_busy_ms": 100.0},
                    "peaks": flops.peaks_for("TPU v5 lite"),
                    "_scopes": {"trace": None}}):
        assert _read(metric, record) is None
