"""The `kimivl` family: the configuration file against the catalog row it was
cut from, the new cell and its entries against the contract (present and in
order; a later PR appends its own behind them), the cell's counts at full
size, the plain reference against the program at a tiny size (float32 to
rounding), the reference's blocking, `correct` under the lower-precision
controls and under a broken timed path, the new readers on records made by
hand, and a tiny cell through the unedited `run_cell` on the CPU."""
import json
import os
import types

import numpy as np
import pytest

import tiny_root
import tiny_kimivl as tiny_kvl
from benchmark import cells, flops, harness

REPO = cells.ROOT
CELL = "kimi-vl-a3b.t8192-b2"
CONFIG = "kimi-vl-a3b"
SOURCE = ("https://huggingface.co/moonshotai/Kimi-VL-A3B-Instruct/"
          "blob/main/config.json")

# the catalog row's `config` (model-configs guide, architectures.jsonl,
# "Kimi-VL-A3B-Instruct"), every key
CATALOG = {
    "vocab_size": 163840, "max_position_embeddings": 131072,
    "hidden_size": 2048, "intermediate_size": 11264,
    "moe_intermediate_size": 1408, "num_hidden_layers": 27,
    "num_attention_heads": 16, "n_shared_experts": 2, "n_routed_experts": 64,
    "ep_size": 1, "routed_scaling_factor": 2.446, "kv_lora_rank": 512,
    "q_lora_rank": None, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "num_experts_per_tok": 6, "moe_layer_freq": 1,
    "first_k_dense_replace": 1, "norm_topk_prob": True,
    "scoring_func": "sigmoid", "seq_aux": True, "num_key_value_heads": 16,
    "hidden_act": "silu", "rms_norm_eps": 1e-05, "rope_theta": 800000,
    "rope_scaling": None, "attention_bias": False,
    "tie_word_embeddings": False}
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]


@pytest.fixture(scope="module")
def kvl(tmp_path_factory):
    return tiny_kvl.add(tiny_root.make(tmp_path_factory.mktemp("kvl")))


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
        assert cfg[key] < CATALOG[key]
    else:
        assert cfg[key] == CATALOG[key]
        assert key not in cfg["reduced"]


def test_the_cut_is_written_into_the_file():
    cfg = held()
    assert cfg["reduced"] == REDUCED
    assert not [key for key in cfg["reduced"] if "head" in key]
    # the leading dense layer and the four expert layers behind it
    assert cfg["published_layer_index"] == [0, 1, 2, 3, 4]
    assert cfg["num_hidden_layers"] == len(cfg["published_layer_index"])
    assert cfg["first_k_dense_replace"] == 1
    # the floors: 8 experts held of all 64 routed over, an eighth of the
    # vocabulary; all 16 heads
    assert cfg["n_routed_experts"] == 8 and cfg["experts_held"] == [0, 8]
    assert cfg["num_experts_routed"] == CATALOG["n_routed_experts"]
    assert cfg["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert "heads_held" not in cfg
    assert cfg["absent_experts"] == "folded"
    assert cfg["assumed"]["absent_experts"]
    assert any("folded" in d and "PR 38" in d for d in cfg["departures"])
    for key in ("published", "assumed", "departures", "reduced_why",
                "deployment", "not_built"):
        assert cfg[key], key
    assert "8 chips share each layer" in cfg["deployment"]
    assert "22 layers" in cfg["deployment"]
    assert "24 bytes a parameter" in cfg["reduced_why"]
    assert "start weights and both moments to the host" in cfg["reduced_why"]
    # what is not built is said, with why
    assert "projector" in cfg["not_built"]["vision_tower"]
    assert "cannot be written down" in cfg["not_built"]["vision_tower"]
    for key in ("attention", "rotary", "router", "expert_bias",
                "auxiliary_loss", "experts", "norm", "head",
                "initializer_range", "optimizer", "recompute"):
        assert cfg["assumed"][key], key
    assert "(2i, 2i+1)" in cfg["assumed"]["rotary"]
    assert "1e-20" in cfg["assumed"]["router"]
    assert any("1e-6" in d and "1e-20" in d for d in cfg["departures"])
    assert any("2816" in d for d in cfg["departures"])
    # the parameter list adds up to what the file says it holds
    cell = cells.Cell(CELL)
    specs = cell.family.param_specs(cell.config, cell.traffic)
    count = sum(int(np.prod(shape)) for shape, _d, _k in specs.values())
    assert count == 568484352 and "568.48M" in cfg["reduced_why"]
    per_layer = {}
    for name, (shape, _d, _k) in specs.items():
        if name.startswith("kvl_layer_"):
            i = int(name.split("_")[2])
            per_layer[i] = per_layer.get(i, 0) + int(np.prod(shape))
    assert per_layer == {0: 82973184, 1: 100405760, 2: 100405760,
                         3: 100405760, 4: 100405760}
    attention = 2048 * 3072 + 2048 * 576 + 512 * 4096 + 2048 * 2048
    assert attention == 13762560
    assert per_layer[0] == attention + 3 * 2048 * 11264 + 512 + 2 * 2048
    assert per_layer[1] == attention + 8 * 3 * 2048 * 1408 \
        + 3 * 2048 * 2816 + 2048 * 64 + 512 + 2 * 2048
    assert specs["kvl_layer_1_experts_gate_up"] == ((8, 2048, 2816),
                                                    "bfloat16", "normal")
    assert specs["kvl_layer_1_experts_down"][0] == (8, 1408, 2048)
    assert specs["kvl_layer_1_shared_gate_up.w_0"][0] == (2048, 2 * 2816)
    assert specs["kvl_layer_1_router.w_0"] == ((2048, 64), "float32",
                                               "normal")
    assert specs["kvl_layer_0_mla_q.w_0"][0] == (2048, 16 * 192)
    assert specs["kvl_layer_0_mla_kv_a.w_0"][0] == (2048, 512 + 64)
    assert specs["kvl_layer_0_mla_kv_b.w_0"][0] == (512, 16 * 256)
    assert specs["kvl_layer_0_mlp_gate_up.w_0"][0] == (2048, 2 * 11264)
    assert specs["kvl_lm_head"] == specs["kvl_word_embedding"] \
        == ((20480, 2048), "float32", "normal")
    assert not [n for n in specs if "bias" in n]
    assert not [n for n in specs if "layer_0" in n and "expert" in n]


def test_the_new_entries_are_present_in_order_and_resolve():
    """Behind the entries that were there, in the order given; nothing here
    asks to be last."""
    b = bench()
    configs = [c["name"] for c in b["configs"]]
    assert configs.index(CONFIG) > configs.index("smallthinker-21b-a3b")
    config = b["configs"][configs.index(CONFIG)]
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["source"] == held()["source"] == SOURCE
    assert len(SOURCE) <= 200 and len(config["why"]) <= 200
    assert config["reduced"] == REDUCED
    names = [w["name"] for w in b["workloads"]]
    assert names.index(CELL) > names.index("smallthinker-21b-a3b.t16384-b2")
    entry = b["workloads"][names.index(CELL)]
    assert entry == dict(entry, config=CONFIG, traffic="t8192-b2", chips=1)
    assert len(entry["why"]) <= 200
    metrics = [m["name"] for m in b["per_layer"]]
    first = metrics.index(tiny_kvl.METRICS[0])
    assert first > metrics.index("kda_roofline_pct")
    assert metrics[first:first + len(tiny_kvl.METRICS)] == tiny_kvl.METRICS
    cell = cells.Cell(CELL)
    t = cell.traffic
    assert (t["seq_len"], t["batch_per_chip"], t["global_batch"],
            t["tokens_per_step"], t["pool_batches"], t["warmup_steps"],
            t["trace_steps"], t["reference_block_rows"]) \
        == (8192, 2, 2, 16384, 8, 2, 4, 1)
    assert set(tiny_kvl.METRICS) <= {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s_per_chip", "peak_hbm_gib", "setup_s"}
    assert set(cell.limits) >= set(harness.GAPS)
    assert all(cell.limits["readings"][gap] for gap in harness.GAPS)


@pytest.mark.parametrize("name", tiny_kvl.METRICS)
def test_every_new_entry_has_its_reader_and_lists_the_cell(name):
    entry = {m["name"]: m for m in bench()["per_layer"]}[name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "tokens_per_s_per_chip"
    assert entry["layer"] in ("Pallas kernels", "Step program")
    assert (entry["unit"] == "%") == name.endswith("_pct")
    assert callable(cells.Cell(CELL).layer_reader(name).read)
    # the old cells do not report it
    for old in ("kimi-linear-48b-a3b.t8192-b2",
                "smallthinker-21b-a3b.t16384-b2"):
        assert name not in {m["name"] for m in cells.Cell(old).per_layer}


def test_the_cells_counts_at_full_size():
    cell = cells.Cell(CELL)
    family = cell.family
    # every pick is answered (absent experts folded onto the 8 held):
    # tokens x 6 picks, 12,288 rows a held expert at an even fold; were
    # they not, tokens x 6 picks x 8 of 64 experts: 1,536 rows each
    assert family.expected_held_rows(cell.config, cell.traffic) == 98304
    assert family.expected_held_rows(
        dict(cell.config, absent_experts="nothing"), cell.traffic) == 12288
    calls = family.attention_calls(cell.config, cell.traffic)
    assert [(c["kind"], c["count"]) for c in calls] == [
        ("forward", 2), ("backward", 1)] * 5
    assert all((c["batch"], c["q_heads"], c["kv_heads"], c["seq"],
                c["d_qk"], c["d_v"], c["window"])
               == (2, 16, 16, 8192, 192, 128, None) for c in calls)
    gmm = family.gmm_calls(cell.config, cell.traffic)
    assert [(c["layer"], c["k"], c["n"]) for c in gmm] == [
        ("kvl_layer_%d" % i, k, n) for i in range(1, 5)
        for k, n in ((2048, 2816), (1408, 2048))]
    assert all((c["groups"], c["fwd"], c["dx"], c["dw"]) == (8, 2, 1, 1)
               for c in gmm)
    # by hand: projections, attention by the visible pairs at 192 and 128,
    # the dense MLP, router, every pick's three matmuls, the shared
    # experts, the head; backward twice the forward, the replay not counted
    tokens, d = 16384, 2048
    attention = 2 * tokens * 13762560 \
        + 2 * 16 * 2 * (8192 * 8193 // 2) * (192 + 128)
    dense = 2 * tokens * 3 * d * 11264
    experts = 2 * tokens * d * 64 + 2 * 98304 * 3 * d * 1408 \
        + 2 * tokens * 3 * d * 2816
    head = 2 * tokens * d * 20480
    want = 3 * (5 * attention + dense + 4 * experts + head)
    assert family.train_flops(cell.config, cell.traffic) == want
    assert 55.2e12 < want < 55.3e12


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


def test_float32_program_equals_the_reference(kvl):
    """Loss and every leaf's gradient to 1e-4 relative under the float32
    cell's own limits (`Runner` also holds the family's parameter list to
    the program's)."""
    cell, got, ref = _against_the_reference(kvl, tiny_kvl.F32)
    rows = harness.compare(got, ref, cell.limits)
    assert all(r[3] for r in rows), rows
    for leaf, mine in got["first_gradient"].items():
        theirs = ref["first_gradient"][leaf]
        scale = max(float(np.max(np.abs(theirs))), 1e-6)
        assert float(np.max(np.abs(mine - theirs))) <= 1e-4 * scale, leaf
    assert {leaf.split("_", 3)[-1] for leaf in got["first_gradient"]} >= {
        "mla_q.w_0", "mla_kv_a.w_0", "mla_kv_a_norm_s", "mla_kv_b.w_0",
        "mla_out.w_0", "mlp_gate_up.w_0", "router.w_0", "experts_gate_up",
        "experts_down", "shared_gate_up.w_0", "shared_down.w_0",
        "attn_norm_s", "ffn_norm_s"}


@pytest.mark.parametrize("precision", ["bfloat16", "float8"])
def test_correct_fails_under_a_lower_precision_control(kvl, precision):
    """The reference computed in the precision below the float32 cell's,
    compared as a program is: outside the cell's limits by `grad_diff` and
    `loss_gap` at least, on both seeds (readings in `tiny_kimivl.py`: the
    bfloat16 reference flips no pick at this size, so its `grad_diff` is
    rounding alone, 7-8 times the limit; float8 reads 270 times it)."""
    from benchmark import read_control
    cell = cells.Cell(tiny_kvl.F32, kvl)
    got = read_control.read(tiny_kvl.F32, [11, 12], platform="cpu", root=kvl,
                            say=lambda _line: None,
                            bfloat16=precision == "bfloat16")
    kind = "bfloat16" if precision == "bfloat16" else "control_float8"
    times = 5 if precision == "bfloat16" else 100
    for seed, gaps in got[kind].items():
        assert gaps["grad_diff"] > times * cell.limits["grad_diff"], seed
        assert gaps["loss_gap"] > cell.limits["loss_gap"], seed


def test_a_broken_timed_path_is_not_correct(kvl):
    from test_harness import _half_batch, _state_unchanged
    for broken, failing in ((_half_batch, "grad_diff"),
                            (_state_unchanged, "delta_norm_gap")):
        lines = []
        out = harness.run_cell(tiny_kvl.F32, 2 ** 31 + 5, 0.3, 0,
                               platform="cpu", root=kvl, say=lines.append,
                               broken=broken)
        assert out["correct"] is False
        failed = [ln for ln in lines if ln.startswith("check ")
                  and "FAILED" in ln]
        assert any(failing in ln for ln in failed), lines


def test_blocking_the_reference_changes_no_value(kvl, monkeypatch):
    """The reference walks the MLPs, the experts, the head and the queries
    in blocks so that it fits the chip at the cell's size; here the same
    loss and gradient with blocks of 8 and with one block."""
    import jax
    from benchmark import reference, weights
    from benchmark.families import lfm2moe
    cell = cells.Cell(tiny_kvl.F32, kvl)
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
    for name in ("MLP_CHUNK", "Q_BLOCK"):
        monkeypatch.setattr(family, name, 8)
    monkeypatch.setattr(lfm2moe, "MLP_CHUNK", 8)
    blocked_loss, blocked = loss_and_grad()
    assert float(blocked_loss) == pytest.approx(float(whole_loss), rel=1e-6)
    for leaf in whole:
        scale = max(float(np.max(np.abs(whole[leaf]))), 1e-8)
        assert float(np.max(np.abs(blocked[leaf] - whole[leaf]))) \
            <= 1e-4 * scale, leaf


def test_the_reference_turns_explicit_pairs_and_the_key_part_once():
    """`rotate_pairs` is the rotation of (x_2i, x_2i+1) by t theta^(-2i/R)
    written out, position 0 is left as it was, and a rotation keeps each
    pair's length; it imports nothing of paddle_tpu."""
    import jax.numpy as jnp
    family = cells.Cell(CELL).family
    x = np.random.default_rng(1).standard_normal((2, 5, 8)).astype(np.float32)
    got = np.asarray(family.rotate_pairs(jnp.asarray(x), 800000.0))
    np.testing.assert_allclose(got[:, 0], x[:, 0], rtol=1e-6)
    for t in range(5):
        for i in range(4):
            phi = t * 800000.0 ** (-2.0 * i / 8)
            a, b = x[:, t, 2 * i], x[:, t, 2 * i + 1]
            np.testing.assert_allclose(
                got[:, t, 2 * i], a * np.cos(phi) - b * np.sin(phi),
                rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(
                got[:, t, 2 * i + 1], a * np.sin(phi) + b * np.cos(phi),
                rtol=1e-5, atol=1e-6)
    with open(os.path.join(REPO, "benchmark", "families", "kimivl.py")) as f:
        source = f.read()
    assert "import paddle_tpu" not in source.replace(
        "from paddle_tpu.models import kimi_vl", "")


def test_a_tiny_cell_runs_through_run_cell_on_the_cpu(kvl):
    cell = cells.Cell(tiny_kvl.SHARE, kvl)
    assert set(tiny_kvl.METRICS) <= {m["name"] for m in cell.per_layer}
    out = harness.run_cell(tiny_kvl.SHARE, 2 ** 31 + 5, 0.3, 0,
                           platform="cpu", root=kvl)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s_per_chip", "peak_hbm_gib",
                                   "setup_s"}
    # long enough for the three steps before the profiler and the two
    # under it, on a loaded machine
    traced = harness.run_cell(tiny_kvl.SHARE, 6, 1.0, 1, platform="cpu",
                              root=kvl)
    assert traced["correct"] is True
    # no device plane off the TPU: the trace readers find nothing and the
    # line leaves them out; the spans' readers read
    assert set(tiny_kvl.METRICS) & set(traced["metrics"]) == {
        "kvl_load_max_over_mean", "kvl_expert_rows_in_use_pct"}
    assert traced["metrics"]["kvl_load_max_over_mean"]["value"] >= 1.0
    # folded: 64 tokens x 2 picks on 4 held experts, tiles of 8 rows
    assert 0 < traced["metrics"]["kvl_expert_rows_in_use_pct"]["value"] <= 100
    assert traced["metrics"]["recompiles_in_window"]["value"] == 0


# ---------------------------------------------------------------------------
# the readers on records made by hand
# ---------------------------------------------------------------------------

def _read(metric, record):
    return cells.Cell(CELL).layer_reader(metric).read(record)


ROPE_FWD = "jit(step)/forward/partial_rope/%s"
ROPE_BWD = ("jit(step)/backward/remat_block/transpose(jvp(forward/"
            "remat_block))/jvp()/checkpoint/rematted_computation/forward/"
            "partial_rope/%s")
ROPE_PULL = ("jit(step)/backward/remat_block/transpose(jvp(forward/"
             "remat_block))/jvp()/checkpoint/forward/partial_rope/%s")


def _record():
    """A traced window of two steps on one chip: the rotary op forward,
    replayed and pulled back, a flash forward and its split backward, a
    grouped matmul under the expert op and a projection; times in ns."""
    ops, at = [], [1000.0]

    def op(name, ns, tf_op):
        ops.append(("%%%s.1 = bf16[2]{0} %s" % (name, "custom-call(...)"
                    if "fusion" not in name else "fusion(...)"),
                    at[0], at[0] + ns, tf_op))
        at[0] += ns

    modules = []
    for _step in range(2):
        start = at[0]
        op("fusion", 2e6, ROPE_FWD % "mul")
        op("fusion", 100e6, "jit(step)/forward/mul/dot_general")
        op("flash_fwd", 40e6, "jit(step)/forward/"
           "scaled_dot_product_attention/flash_fwd/pallas_call")
        op("moe_gmm_fwd", 30e6, "jit(step)/forward/moe_experts/moe_gmm_fwd/"
           "pallas_call")
        op("fusion", 6e6, "jit(step)/forward/moe_combine/add")
        op("fusion", 2e6, ROPE_BWD % "mul")
        op("fusion", 3e6, ROPE_PULL % "mul")
        op("flash_bwd_dkv", 50e6, "jit(step)/backward/"
           "scaled_dot_product_attention/flash_bwd_dkv/pallas_call")
        op("flash_bwd_dq", 30e6, "jit(step)/backward/"
           "scaled_dot_product_attention/flash_bwd_dq/pallas_call")
        modules.append(("jit_step(1)", start, at[0]))
    trace = {"devices": {0: {"ops": ops, "modules": modules}},
             "host": {"main": [("bench.traced", 0.0, at[0] + 1000.0)]}}
    busy = (at[0] - 1000.0) / 1e9
    return {"cell": cells.Cell(CELL), "peaks": flops.peaks_for("TPU v5 lite"),
            "traced": {"busy_s": busy, "steps_seen": 2,
                       "step_busy_ms": busy * 1e3 / 2,
                       "op_seconds": {"custom-call:flash_fwd": 0.080,
                                      "custom-call:flash_bwd_dkv": 0.100,
                                      "custom-call:flash_bwd_dq": 0.060,
                                      "custom-call:moe_gmm_fwd": 0.060,
                                      "fusion": 0.226}},
            "obs_spans": [
                {"name": "moe.load", "labels": {
                    "layer": "kvl_layer_%d" % i, "rows_held": 98304,
                    "rows_max": 24576, "rows_mean": 12288.0,
                    "rows_in_use": 100352, "rows_buffer": 102400,
                    "bounded": 0}}
                for _step in range(8) for i in range(1, 5)],
            "_scopes": {"trace": trace}}


def test_the_rotary_ops_time_is_read_under_its_own_op_type():
    record = _record()
    # forward, the replayed forward and the pullback, both roles: 2 + 2 + 3
    assert _read("kvl_rope_ms", record) == pytest.approx(7.0)
    # the expert layer's four op types: the kernel and the combine
    assert _read("kvl_expert_layer_ms", record) == pytest.approx(36.0)


def test_the_flash_and_grouped_matmul_readers_count_this_cells_calls():
    record = _record()
    assert _read("kvl_attn_share_pct", record) == pytest.approx(
        100 * 0.240 / record["traced"]["busy_s"])
    # by hand: 5 layers x 2 rows x 16 heads over the visible pairs at 192
    # and 128: QK^T and PV forward twice (the replay), five matmuls of the
    # backward, compute-bound
    peak = record["peaks"]["bf16_flops_per_s"]
    area = 8192 * 8193 // 2
    fwd = 2 * 2 * 16 * area * (192 + 128)
    bwd = 2 * 2 * 16 * area * (3 * 192 + 2 * 128)
    least = 5 * (2 * fwd + bwd) / peak
    assert _read("kvl_attn_roofline_pct", record) == pytest.approx(
        100 * least * 2 / 0.240, rel=1e-3)
    # the grouped matmuls at the rows the spans counted: 98,304 a layer
    gmm = _read("kvl_gmm_roofline_pct", record)
    one = 2 * 98304 * (2048 * 2816 + 1408 * 2048)
    assert gmm == pytest.approx(100 * 4 * 4 * one / peak * 2 / 0.060,
                                rel=0.02)
    assert _read("kvl_load_max_over_mean", record) == pytest.approx(2.0)
    assert _read("kvl_expert_rows_in_use_pct", record) == pytest.approx(98.0)


@pytest.mark.parametrize("metric", tiny_kvl.METRICS)
def test_each_reader_is_left_out_where_there_is_nothing_to_read(metric):
    """A parent program has no `partial_rope` scope and no `moe.load` span
    of these layers; a run off the chip no device plane: every reader
    returns None and does not raise."""
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
