"""The `lfm2moe` family: the configuration file against the catalog row it
was cut from, the new cell and its entries against the contract,
`flops_moe`'s hand counts, the plain reference against the program at a tiny
size (float32 to rounding), the reference's blocking, the four new readers
with and without something to read, and a tiny cell through the unedited
`run_cell` on the CPU."""
import json
import os
import types

import numpy as np
import pytest

import tiny_lfm
import tiny_root
from benchmark import cells, flops, flops_moe, harness
from benchmark.layer_metrics import _moe

REPO = cells.ROOT
CELL = "lfm2-8b-a1b.t8192-b2"

# the catalog row's `config` (model-configs guide, architectures.jsonl,
# "LFM2-8B-A1B"), every key
LAYER_TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv",
               "full_attention", "conv", "conv", "conv", "full_attention",
               "conv", "conv", "conv", "full_attention", "conv", "conv",
               "conv", "full_attention", "conv", "conv", "full_attention",
               "conv", "conv"]
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "layer_types": LAYER_TYPES,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]


@pytest.fixture(scope="module")
def lfm(tmp_path_factory):
    return tiny_lfm.add(tiny_root.make(tmp_path_factory.mktemp("lfm")))


def held():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "lfm2-8b-a1b.json")) as f:
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
    assert cfg["layer_kinds"] == tiny_lfm.KINDS
    assert cfg["published_layer_index"] == tiny_lfm.PUBLISHED
    # one whole period of the published pattern behind the dense layer
    assert [LAYER_TYPES[i] for i in cfg["published_layer_index"]] \
        == ["conv", "full_attention", "conv", "conv", "conv"]
    assert cfg["num_hidden_layers"] == len(cfg["layer_kinds"]) == 5
    # the floors: 8 experts held, of all 32 routed over; a quarter of the
    # vocabulary (at least an eighth)
    assert cfg["num_experts"] == 8 and cfg["experts_held"] == [0, 8]
    assert cfg["num_experts_routed"] == CATALOG["num_experts"]
    assert cfg["vocab_size"] * 4 == CATALOG["vocab_size"]
    for key in ("published", "assumed", "departures", "reduced_why",
                "deployment"):
        assert cfg[key], key
    assert "4 chips share each layer" in cfg["deployment"]
    assert "19 layers" in cfg["deployment"]
    assert "24 bytes a parameter" in cfg["reduced_why"]
    assert "no Parameter" in " ".join(cfg["departures"])
    # the parameter list adds up to what the file says it holds
    cell = cells.Cell(CELL)
    specs = cell.family.param_specs(cell.config, cell.traffic)
    count = sum(int(np.prod(shape)) for shape, _d, _k in specs.values())
    assert round(count / 1e6, 1) == 507.8
    per_layer = {}
    for name, (shape, _d, _k) in specs.items():
        if name.startswith("lfm_layer_"):
            i = int(name.split("_")[2])
            per_layer[i] = per_layer.get(i, 0) + int(np.prod(shape))
    millions = [round(per_layer[i] / 1e6, 1) for i in range(5)]
    assert millions == [60.8, 98.6, 104.9, 104.9, 104.9]
    assert specs["lfm_layer_1_experts_gate_up"][0] == (8, 2048, 3584)
    assert specs["lfm_layer_1_experts_down"][0] == (8, 1792, 2048)
    assert specs["lfm_layer_1_router.w_0"] == ((2048, 32), "float32",
                                               "normal")
    assert not [n for n in specs if "bias" in n]


def test_the_new_entries_are_appended_and_resolve():
    b = bench()
    assert b["configs"][-1]["name"] == "lfm2-8b-a1b"
    assert b["configs"][-1]["source"] == held()["source"] \
        == "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
    assert b["configs"][-1]["reduced"] == REDUCED
    cell_entry = b["workloads"][-1]
    assert cell_entry == dict(cell_entry, name=CELL, config="lfm2-8b-a1b",
                              traffic="t8192-b2", chips=1)
    assert [m["name"] for m in b["per_layer"]][-4:] == tiny_lfm.METRICS
    cell = cells.Cell(CELL)
    t = cell.traffic
    assert (t["seq_len"], t["global_batch"], t["tokens_per_step"],
            t["pool_batches"], t["reference_block_rows"]) \
        == (8192, 2, 16384, 8, 1)
    assert set(tiny_lfm.METRICS) <= {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s_per_chip", "peak_hbm_gib", "setup_s"}


@pytest.mark.parametrize("name", tiny_lfm.METRICS)
def test_every_new_entry_has_its_reader_and_lists_the_cell(name):
    entry = {m["name"]: m for m in bench()["per_layer"]}[name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "tokens_per_s_per_chip"
    assert callable(cells.Cell(CELL).layer_reader(name).read)
    # the old cells do not report it
    assert name not in {m["name"] for m in
                        cells.Cell("gpt2.t4096-b4").per_layer}


def test_moe_flops_hand_counts():
    assert flops_moe.gmm_flops(10, 4, 6) == 2 * 10 * 4 * 6
    for kernel in flops_moe.KERNELS:
        assert flops_moe.gmm_bytes(kernel, 10, 4, 6, 3, 2) \
            == (10 * 4 + 10 * 6 + 3 * 4 * 6) * 2
    with pytest.raises(ValueError, match="no grouped-matmul kernel"):
        flops_moe.gmm_bytes("dy", 1, 1, 1, 1, 2)
    peaks = flops.peaks_for("TPU v5 lite")
    # the cell's gate-and-up product at even routing is compute-bound
    seconds, bound = flops_moe.gmm_least_seconds(
        "fwd", 16384, 2048, 3584, 8, 2, peaks)
    assert bound == "flops"
    assert seconds == pytest.approx(2 * 16384 * 2048 * 3584 / 197e12)
    # a handful of rows is bound by reading the experts' matrices
    assert flops_moe.gmm_least_seconds("fwd", 64, 2048, 3584, 8, 2,
                                       peaks)[1] == "bytes"


def test_the_cells_counts_at_full_size():
    cell = cells.Cell(CELL)
    family = cell.family
    assert family.expected_held_rows(cell.config, cell.traffic) == 16384
    total = family.train_flops(cell.config, cell.traffic)
    # 6 x (the parameters a token touches) x tokens, plus attention
    assert 20e12 < total < 23e12
    calls = family.attention_calls(cell.config, cell.traffic)
    assert [(c["kind"], c["count"]) for c in calls] \
        == [("forward", 2), ("backward", 1)]
    assert all((c["batch"], c["q_heads"], c["kv_heads"], c["seq"],
                c["d_qk"], c["d_v"], c["window"])
               == (2, 32, 8, 8192, 64, 64, None) for c in calls)
    gmm = family.gmm_calls(cell.config, cell.traffic)
    assert [(c["layer"], c["k"], c["n"]) for c in gmm] == [
        ("lfm_layer_%d" % i, k, n) for i in (1, 2, 3, 4)
        for k, n in ((2048, 3584), (1792, 2048))]
    assert all((c["groups"], c["fwd"], c["dx"], c["dw"]) == (8, 2, 1, 1)
               for c in gmm)


def _float32_against_the_reference(lfm, name):
    cell = cells.Cell(name, lfm)
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


@pytest.mark.parametrize("name", tiny_lfm.AT_REST)
def test_float32_program_equals_the_reference(lfm, name):
    """Loss and every leaf's gradient to 1e-4 relative, for a share of the
    experts and for all of them."""
    got, ref = _float32_against_the_reference(lfm, name)
    rows = harness.compare(got, ref, {"loss_gap": 1e-5, "grad_diff": 1e-4,
                                      "grad_norm_gap": 1e-4,
                                      "delta_norm_gap": 1e-2})
    assert all(r[3] for r in rows), rows
    for leaf, mine in got["first_gradient"].items():
        theirs = ref["first_gradient"][leaf]
        scale = max(float(np.max(np.abs(theirs))), 1e-6)
        assert float(np.max(np.abs(mine - theirs))) <= 1e-4 * scale, leaf


def test_a_moving_bias_leaves_the_first_step_and_hardly_moves_the_next(lfm):
    """The reference adds the bias's zeros on all three steps; the program
    moves it by `expert_bias_update_rate` after each. The first loss and
    gradient are the reference's to float32 rounding, and the later losses
    differ by the few picks a bias of a thousandth turns."""
    got, ref = _float32_against_the_reference(lfm, "tiny-lfm-bias.t32-b2")
    assert got["losses"][0] == pytest.approx(ref["losses"][0], rel=1e-6)
    rows = dict((r[0], r) for r in harness.compare(
        got, ref, {"loss_gap": 2e-4, "grad_diff": 1e-4,
                   "grad_norm_gap": 1e-4, "delta_norm_gap": 5e-2}))
    assert all(r[3] for r in rows.values()), rows


def test_blocking_the_reference_changes_no_value(lfm, monkeypatch):
    """The reference walks the MLPs, the experts, the head and the queries
    in blocks so that it fits the chip at the cell's size; here the same
    loss and gradient with blocks of 8 and with one block."""
    import jax
    from benchmark import reference, weights
    cell = cells.Cell("tiny-lfm-share.t32-b2", lfm)
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
    blocked_loss, blocked = loss_and_grad()
    assert float(blocked_loss) == pytest.approx(float(whole_loss), rel=1e-6)
    for leaf in whole:
        scale = max(float(np.max(np.abs(whole[leaf]))), 1e-8)
        assert float(np.max(np.abs(blocked[leaf] - whole[leaf]))) \
            <= 1e-4 * scale, leaf


def test_the_references_shares_add_up_to_its_uncut_layer():
    """`expert_ffn` is given the chip's share like the program: over four
    ranks of 8 the parts add up to the 32-expert layer."""
    import jax
    import jax.numpy as jnp
    from benchmark import reference
    family = cells.Cell(CELL).family
    s = {"routed": 32, "top_k": 4, "norm_topk": True, "scaling": 1,
         "held": (0, 32)}
    k = jax.random.split(jax.random.PRNGKey(4), 4)
    u = jax.random.normal(k[0], (24, 16))
    w_r = jax.random.normal(k[1], (16, 32))
    w13 = 0.5 * jax.random.normal(k[2], (32, 16, 16))
    w2 = 0.5 * jax.random.normal(k[3], (32, 8, 16))
    mm = reference.matmul_at("float32")
    whole = family.expert_ffn(u, w_r, w13, w2, s, mm)
    parts = [family.expert_ffn(u, w_r, w13[f:f + 8], w2[f:f + 8], s, mm,
                               held=(f, 8)) for f in (0, 8, 16, 24)]
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-5)
    assert all(float(jnp.max(jnp.abs(p))) > 1e-3 for p in parts)


@pytest.mark.parametrize("name", ["tiny-lfm-share.t32-b2",
                                  "tiny-lfm-bias.t32-b2"])
def test_a_tiny_cell_runs_through_run_cell_on_the_cpu(lfm, name):
    cell = cells.Cell(name, lfm)
    assert set(tiny_lfm.METRICS) <= {m["name"] for m in cell.per_layer}
    out = harness.run_cell(name, 2 ** 31 + 5, 0.3, 0,
                           platform="cpu", root=lfm)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s_per_chip", "peak_hbm_gib",
                                   "setup_s"}
    traced = harness.run_cell(name, 6, 0.3, 1, platform="cpu", root=lfm)
    assert traced["correct"] is True
    # no device plane off the TPU: the three trace readers find nothing
    # and the line leaves them out; the spans' reader reads
    assert set(tiny_lfm.METRICS) & set(traced["metrics"]) \
        == {"moe_load_max_over_mean"}
    assert traced["metrics"]["moe_load_max_over_mean"]["value"] >= 1.0
    assert traced["metrics"]["recompiles_in_window"]["value"] == 0


# ---------------------------------------------------------------------------
# the readers on records made by hand
# ---------------------------------------------------------------------------

def _spans(rows_by_layer, later=((1000, 0),) * 5):
    """`moe.load` spans of a window: three steps before the profiler starts
    (all rows on one expert: a reader that took them would show), the
    traced steps given, then `later` steps (the router has learnt to lean
    on the held experts)."""
    before = ((64, 0),) * harness.TRACE_WARM_STEPS
    return [{"name": "moe.load", "labels": {
        "layer": layer, "rows_held": sum(rows), "rows_max": max(rows),
        "rows_mean": sum(rows) / len(rows)}}
        for layer, steps in rows_by_layer.items()
        for rows in list(before) + list(steps) + list(later)] \
        + [{"name": "exec.step", "labels": {}}]


def _cell(trace_steps):
    return types.SimpleNamespace(traffic={"trace_steps": trace_steps})


def _read(metric, record):
    return cells.Cell(CELL).layer_reader(metric).read(record)


def test_load_max_over_mean_is_the_worst_layers_median_over_traced_steps():
    assert _moe.TRACE_WARM_STEPS == harness.TRACE_WARM_STEPS
    record = {"cell": _cell(3), "obs_spans": _spans({
        "a": [[10, 10], [12, 8], [14, 6]],      # 1.0, 1.2, 1.4
        "b": [[10, 10], [11, 9], [10, 10]]})}   # 1.0, 1.1, 1.0
    assert _read("moe_load_max_over_mean", record) == pytest.approx(1.2)
    # a layer that received nothing is left out, not divided by
    record = {"cell": _cell(1),
              "obs_spans": _spans({"a": [[0, 0]], "b": [[3, 1]]})}
    assert _read("moe_load_max_over_mean", record) == pytest.approx(1.5)
    # a window that ended before the profiler started holds nothing
    record = {"cell": _cell(4), "obs_spans": _spans({"a": []}, later=())}
    assert _read("moe_load_max_over_mean", record) is None


def test_the_gmm_roofline_counts_the_rows_the_step_counted():
    cell = cells.Cell(CELL)
    peaks = flops.peaks_for("TPU v5 lite")
    layers_ = ["lfm_layer_%d" % i for i in (1, 2, 3, 4)]

    def record(rows):
        return {"cell": cell, "peaks": peaks,
                "obs_spans": _spans({n: [[rows // 8] * 8] * 4
                                     for n in layers_}),
                "traced": {"op_seconds": {"custom-call:moe_gmm_fwd": 0.05,
                                          "custom-call:moe_gmm_dx": 0.03,
                                          "custom-call:moe_gmm_dw": 0.04,
                                          "custom-call:flash_fwd": 9.0},
                           "steps_seen": 4, "busy_s": 1.2}}

    even = _moe.gmm_least_seconds(record(16384))
    per_matrix = sum(
        4 * flops_moe.gmm_least_seconds("fwd", 16384, k, n, 8, 2, peaks)[0]
        for k, n in ((2048, 3584), (1792, 2048)))
    assert even == pytest.approx(4 * per_matrix)
    got = _read("moe_gmm_roofline_pct", record(16384))
    assert got == pytest.approx(100 * even * 4 / 0.12)
    assert 0 < got < 100
    # twice the rows on the held experts: twice the least time, not a
    # share read from the static expectation
    assert _moe.gmm_least_seconds(record(32768)) == pytest.approx(
        2 * even, rel=0.02)


@pytest.mark.parametrize("metric", tiny_lfm.METRICS)
def test_each_reader_is_left_out_where_there_is_nothing_to_read(metric):
    """A parent program has no `moe.load` span, no `moe_*` scope and no
    `moe_gmm_*` kernel: every reader returns None and does not raise."""
    cell = types.SimpleNamespace(root="/nonexistent", name="tiny.cell",
                                 family=types.SimpleNamespace(),
                                 config={"precision": "bfloat16"},
                                 traffic={})
    cell.traffic = {"trace_steps": 4}
    for record in ({"cell": cell, "traced": None},
                   {"cell": cell, "traced": None, "obs_spans": [],
                    "peaks": None},
                   {"cell": cell, "obs_spans": [{"name": "exec.step",
                                                 "labels": {}}],
                    "traced": {"op_seconds": {"custom-call:flash_fwd": 1.0},
                               "steps_seen": 4, "busy_s": 2.0,
                               "step_busy_ms": 100.0},
                    "peaks": flops.peaks_for("TPU v5 lite")}):
        if record.get("traced"):
            record["_scopes"] = {"trace": None}     # no device plane
        assert _read(metric, record) is None
