"""The `smallthinker` family: the configuration file against the catalog row
it was cut from, the new cell and its entries against the contract (present
and in order; a later PR appends its own behind them), the cell's counts at
full size, the plain reference against the program at a tiny size (float32
to rounding), the reference's blocking and its eight shares, `correct` under
the lower-precision controls and under a broken timed path, the new readers
on records made by hand, and a tiny cell through the unedited `run_cell` on
the CPU."""
import json
import os
import types

import numpy as np
import pytest

import tiny_root
import tiny_smallthinker as tiny_st
from benchmark import cells, flops, flops_hybrid, harness
from benchmark.layer_metrics import _swa

REPO = cells.ROOT
CELL = "smallthinker-21b-a3b.t16384-b2"
CONFIG = "smallthinker-21b-a3b"
SOURCE = ("https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
          "blob/main/config.json")
LAYOUT = [0, 1, 1, 1] * 13

# the catalog row's `config` (model-configs guide, architectures.jsonl,
# "SmallThinker-21BA3B-Instruct"), every key
CATALOG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": LAYOUT,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}
REDUCED = ["num_hidden_layers", "moe_num_primary_experts", "vocab_size"]


@pytest.fixture(scope="module")
def st(tmp_path_factory):
    return tiny_st.add(tiny_root.make(tmp_path_factory.mktemp("st")))


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
        assert cfg[key] == CATALOG[key]     # the two layouts whole
        assert key not in cfg["reduced"]


def test_the_cut_is_written_into_the_file():
    cfg = held()
    assert cfg["reduced"] == REDUCED
    # one whole period: the full position-free layer, then three windowed
    # rotary ones, by the layouts as published
    assert cfg["published_layer_index"] == [0, 1, 2, 3]
    assert cfg["num_hidden_layers"] == len(cfg["published_layer_index"])
    assert [cfg["sliding_window_layout"][i]
            for i in cfg["published_layer_index"]] == [0, 1, 1, 1]
    # the floors: 8 experts held of all 64 routed over, an eighth of the
    # vocabulary; no head count is cut
    assert cfg["moe_num_primary_experts"] == 8
    assert cfg["experts_held"] == [0, 8]
    # every pick is answered here, an absent expert's by the held expert
    # congruent to it: the rows a step lays out do not follow the router
    assert cfg["absent_experts"] == "folded"
    assert cfg["assumed"]["absent_experts"]
    assert any("ISSUE 38" in d and "folded" in d for d in cfg["departures"])
    assert cfg["num_experts_routed"] == CATALOG["moe_num_primary_experts"]
    assert cfg["vocab_size"] * 8 == CATALOG["vocab_size"]
    for key in ("published", "assumed", "departures", "reduced_why",
                "deployment"):
        assert cfg[key], key
    assert "8 chips share each layer" in cfg["deployment"]
    assert "48 layers" in cfg["deployment"]
    assert "24 bytes a parameter" in cfg["reduced_why"]
    for key in ("router_input", "router", "experts", "attention", "rotary",
                "norm", "head", "initializer_range"):
        assert cfg["assumed"][key], key
    assert any("ISSUE 38" in d and "router" in d for d in cfg["departures"])
    # the parameter list adds up to what the file says it holds
    cell = cells.Cell(CELL)
    specs = cell.family.param_specs(cell.config, cell.traffic)
    count = sum(int(np.prod(shape)) for shape, _d, _k in specs.values())
    assert count == 370547200 and "370.55M" in cfg["reduced_why"]
    per_layer = {}
    for name, (shape, _d, _k) in specs.items():
        if name.startswith("st_layer_"):
            i = int(name.split("_")[2])
            per_layer[i] = per_layer.get(i, 0) + int(np.prod(shape))
    assert per_layer == {i: 68326400 for i in range(4)}
    assert specs["st_layer_1_experts_gate_up"] == ((8, 2560, 1536),
                                                   "bfloat16", "normal")
    assert specs["st_layer_1_experts_down"][0] == (8, 768, 2560)
    assert specs["st_layer_0_router.w_0"] == ((2560, 64), "float32",
                                              "normal")
    assert specs["st_layer_0_qkv.w_0"][0] == (2560, (28 + 2 * 4) * 128)
    assert specs["st_layer_0_out.w_0"][0] == (28 * 128, 2560)
    assert specs["st_lm_head"] == specs["st_word_embedding"] \
        == ((18992, 2560), "float32", "normal")
    assert not [n for n in specs if "bias" in n or "q_norm" in n]


def test_the_new_entries_are_present_in_order_and_resolve():
    """Behind the entries that were there, in the order given; nothing here
    asks to be last."""
    b = bench()
    configs = [c["name"] for c in b["configs"]]
    assert configs.index(CONFIG) > configs.index("kimi-linear-48b-a3b")
    config = b["configs"][configs.index(CONFIG)]
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["source"] == held()["source"] == SOURCE
    assert len(SOURCE) <= 200 and len(config["why"]) <= 200
    assert config["reduced"] == REDUCED
    names = [w["name"] for w in b["workloads"]]
    assert names.index(CELL) > names.index("kimi-linear-48b-a3b.t8192-b2")
    entry = b["workloads"][names.index(CELL)]
    assert entry == dict(entry, config=CONFIG, traffic="t16384-b2", chips=1)
    assert len(entry["why"]) <= 200
    metrics = [m["name"] for m in b["per_layer"]]
    first = metrics.index(tiny_st.METRICS[0])
    assert first > metrics.index("expert_rows_in_use_pct")
    assert metrics[first:first + len(tiny_st.METRICS)] == tiny_st.METRICS
    cell = cells.Cell(CELL)
    t = cell.traffic
    assert (t["seq_len"], t["batch_per_chip"], t["global_batch"],
            t["tokens_per_step"], t["pool_batches"], t["warmup_steps"],
            t["trace_steps"], t["reference_block_rows"]) \
        == (16384, 2, 2, 32768, 8, 2, 4, 1)
    assert t["seq_len"] == CATALOG["max_position_embeddings"]
    assert set(tiny_st.METRICS) <= {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s_per_chip", "peak_hbm_gib", "setup_s"}
    assert set(cell.limits) >= set(harness.GAPS)
    assert all(cell.limits["readings"][gap] for gap in harness.GAPS)


@pytest.mark.parametrize("name", tiny_st.METRICS)
def test_every_new_entry_has_its_reader_and_lists_the_cell(name):
    entry = {m["name"]: m for m in bench()["per_layer"]}[name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "tokens_per_s_per_chip"
    assert entry["layer"] in ("Pallas kernels", "Step program")
    assert (entry["unit"] == "%") == name.endswith("_pct")
    assert callable(cells.Cell(CELL).layer_reader(name).read)
    # the old cells do not report it
    for old in ("lfm2-8b-a1b.t8192-b2", "phi4-mini-flash.t8192-b1"):
        assert name not in {m["name"] for m in cells.Cell(old).per_layer}


def test_the_cells_counts_at_full_size():
    cell = cells.Cell(CELL)
    family = cell.family
    # every pick is answered (absent experts folded onto the 8 held):
    # tokens x 6 picks, 24,576 rows a held expert if routing is even;
    # were they not, tokens x 6 picks x 8 of 64 experts: 3,072 rows each
    assert cell.config["absent_experts"] == "folded"
    assert family.expected_held_rows(cell.config, cell.traffic) == 196608
    assert family.expected_held_rows(
        dict(cell.config, absent_experts="nothing"), cell.traffic) == 24576
    # the pairs a head's queries see: all of them, and under the window
    full = flops_hybrid.visible_area(16384)
    window = flops_hybrid.visible_area(16384, 4096)
    assert full == 16384 * 16385 // 2
    assert window == 16384 * 4096 - 4096 * 4095 // 2
    assert 0.43 < window / full < 0.44
    calls = family.attention_calls(cell.config, cell.traffic)
    assert [(c["kind"], c["count"], c["window"]) for c in calls] == [
        ("forward", 2, None), ("backward", 1, None)] \
        + [("forward", 2, 4096), ("backward", 1, 4096)] * 3
    assert all((c["batch"], c["q_heads"], c["kv_heads"], c["seq"],
                c["d_qk"], c["d_v"]) == (2, 28, 4, 16384, 128, 128)
               for c in calls)
    gmm = family.gmm_calls(cell.config, cell.traffic)
    assert [(c["layer"], c["k"], c["n"]) for c in gmm] == [
        ("st_layer_%d" % i, k, n) for i in range(4)
        for k, n in ((2560, 1536), (768, 2560))]
    assert all((c["groups"], c["fwd"], c["dx"], c["dw"]) == (8, 2, 1, 1)
               for c in gmm)
    # by hand: projections, attention by the visible pairs, router, the
    # held experts' rows, the head; backward twice the forward
    tokens, d = 32768, 2560
    layer = 2 * tokens * d * 4608 + 2 * tokens * 3584 * d \
        + 2 * tokens * d * 64 + 2 * 196608 * 3 * d * 768
    attn = 2 * 28 * 2 * (full + 3 * window) * 2 * 128
    head = 2 * tokens * d * 18992
    assert family.train_flops(cell.config, cell.traffic) \
        == 3 * (4 * layer + attn + head)
    assert 0.30 < 3 * attn / family.train_flops(cell.config,
                                                cell.traffic) < 0.36


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


def test_float32_program_equals_the_reference(st):
    """Loss and every leaf's gradient to 1e-4 relative under the float32
    cell's own limits (`Runner` also holds the family's parameter list to
    the program's)."""
    cell, got, ref = _against_the_reference(st, tiny_st.F32)
    rows = harness.compare(got, ref, cell.limits)
    assert all(r[3] for r in rows), rows
    for leaf, mine in got["first_gradient"].items():
        theirs = ref["first_gradient"][leaf]
        scale = max(float(np.max(np.abs(theirs))), 1e-6)
        assert float(np.max(np.abs(mine - theirs))) <= 1e-4 * scale, leaf
    assert {leaf.split("_", 3)[-1] for leaf in got["first_gradient"]} >= {
        "qkv.w_0", "out.w_0", "router.w_0", "experts_gate_up",
        "experts_down", "attn_norm_s", "ffn_norm_s"}


@pytest.mark.parametrize("precision", ["bfloat16", "float8"])
def test_correct_fails_under_a_lower_precision_control(st, precision):
    """The reference computed in the precision below the float32 cell's,
    compared as a program is: outside the cell's limits by `grad_diff` at
    least, on both seeds."""
    from benchmark import read_control
    cell = cells.Cell(tiny_st.F32, st)
    got = read_control.read(tiny_st.F32, [11, 12], platform="cpu", root=st,
                            say=lambda _line: None,
                            bfloat16=precision == "bfloat16")
    kind = "bfloat16" if precision == "bfloat16" else "control_float8"
    for seed, gaps in got[kind].items():
        assert gaps["grad_diff"] > 10 * cell.limits["grad_diff"], seed
        assert gaps["loss_gap"] > cell.limits["loss_gap"], seed


def test_a_broken_timed_path_is_not_correct(st):
    from test_harness import _half_batch, _state_unchanged
    for broken, failing in ((_half_batch, "grad_diff"),
                            (_state_unchanged, "delta_norm_gap")):
        lines = []
        out = harness.run_cell(tiny_st.F32, 2 ** 31 + 5, 0.3, 0,
                               platform="cpu", root=st, say=lines.append,
                               broken=broken)
        assert out["correct"] is False
        failed = [ln for ln in lines if ln.startswith("check ")
                  and "FAILED" in ln]
        assert any(failing in ln for ln in failed), lines


def test_blocking_the_reference_changes_no_value(st, monkeypatch):
    """The reference walks the experts, the head and the queries in blocks
    so that it fits the chip at the cell's size; here the same loss and
    gradient with blocks of 8 and with one block."""
    import jax
    from benchmark import reference, weights
    from benchmark.families import lfm2moe
    cell = cells.Cell(tiny_st.F32, st)
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


def test_the_references_eight_shares_add_up_to_its_uncut_layer():
    """`expert_ffn` is given the chip's share like the program: over eight
    ranks of 8 the parts add up to the 64-expert layer, routed by the
    router's own input."""
    import jax
    import jax.numpy as jnp
    from benchmark import reference
    family = cells.Cell(CELL).family
    s = {"routed": 64, "top_k": 6, "norm_topk": True, "held": (0, 64)}
    k = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(k[0], (24, 16))
    r = jax.random.normal(k[1], (24, 16))
    w_r = jax.random.normal(k[2], (16, 64))
    w13 = 0.5 * jax.random.normal(k[3], (64, 16, 16))
    w2 = 0.5 * jax.random.normal(k[4], (64, 8, 16))
    mm = reference.matmul_at("float32")
    whole = family.expert_ffn(x, r, w_r, w13, w2, s, mm)
    parts = [family.expert_ffn(x, r, w_r, w13[f:f + 8], w2[f:f + 8], s, mm,
                               held=(f, 8)) for f in range(0, 64, 8)]
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-5)
    assert all(float(jnp.max(jnp.abs(p))) > 1e-3 for p in parts)
    # and it is the router's input that routes: another r, other picks
    picks, weights = family.route(r, w_r, s, mm)
    other, _w = family.route(x, w_r, s, mm)
    assert (np.asarray(picks) != np.asarray(other)).any()
    np.testing.assert_allclose(weights.sum(1), 1.0, rtol=1e-6)


def test_the_reference_masks_the_window_key_by_key():
    import jax.numpy as jnp
    family = cells.Cell(CELL).family
    pos = jnp.arange(6)
    seen = np.asarray(family.visible(pos, pos, 3))
    assert seen.sum(1).tolist() == [1, 2, 3, 3, 3, 3]
    assert seen[5].tolist() == [False, False, False, True, True, True]
    assert np.asarray(family.visible(pos, pos, None)).sum() == 21
    # the pairs it lets through are the count the readers go by
    pos = jnp.arange(64)
    assert int(np.asarray(family.visible(pos, pos, 16)).sum()) \
        == flops_hybrid.visible_area(64, 16) == 64 * 16 - 16 * 15 // 2


def test_a_tiny_cell_runs_through_run_cell_on_the_cpu(st):
    cell = cells.Cell(tiny_st.SHARE, st)
    assert set(tiny_st.METRICS) <= {m["name"] for m in cell.per_layer}
    out = harness.run_cell(tiny_st.SHARE, 2 ** 31 + 5, 0.3, 0,
                           platform="cpu", root=st)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s_per_chip", "peak_hbm_gib",
                                   "setup_s"}
    # long enough for the three steps before the profiler and the two
    # under it, on a loaded machine
    traced = harness.run_cell(tiny_st.SHARE, 6, 1.0, 1, platform="cpu",
                              root=st)
    assert traced["correct"] is True
    # no device plane off the TPU: the trace readers find nothing and the
    # line leaves them out; the spans' readers read
    assert set(tiny_st.METRICS) & set(traced["metrics"]) == {
        "st_load_max_over_mean", "st_expert_rows_in_use_pct"}
    assert traced["metrics"]["st_load_max_over_mean"]["value"] >= 1.0
    assert 0 < traced["metrics"]["st_expert_rows_in_use_pct"]["value"] <= 100
    assert traced["metrics"]["recompiles_in_window"]["value"] == 0


# ---------------------------------------------------------------------------
# the readers on records made by hand
# ---------------------------------------------------------------------------

def _read(metric, record):
    return cells.Cell(CELL).layer_reader(metric).read(record)


FWD = "jit(step)/forward/scaled_dot_product_attention/%sflash_fwd/pallas_call"
BWD = ("jit(step)/backward/remat_block/transpose(jvp(forward/remat_block))/"
       "jvp()/checkpoint/rematted_computation/forward/"
       "scaled_dot_product_attention/%s%s/pallas_call")


def _record(scoped=True):
    """A traced window of two steps on one chip: a full layer's three flash
    kernels, a window layer's three (under the scope where `scoped`), a
    grouped matmul and a fusion; times in ns."""
    scope = "window_attention/" if scoped else ""
    ops, at = [], [1000.0]

    def op(name, ns, tf_op):
        ops.append(("%%%s.1 = bf16[2]{0} %s" % (name, "custom-call(...)"
                    if "fusion" not in name else "fusion(...)"),
                    at[0], at[0] + ns, tf_op))
        at[0] += ns

    for _step in range(2):
        op("flash_fwd", 40e6, FWD % "")
        op("flash_fwd", 10e6, FWD % scope)
        op("fusion", 100e6, "jit(step)/forward/mul/dot_general")
        op("moe_gmm_fwd", 30e6, "jit(step)/forward/moe_experts/moe_gmm_fwd/"
           "pallas_call")
        op("flash_bwd_dkv", 50e6, BWD % ("", "flash_bwd_dkv"))
        op("flash_bwd_dq", 30e6, BWD % ("", "flash_bwd_dq"))
        op("flash_bwd_dkv", 15e6, BWD % (scope, "flash_bwd_dkv"))
        op("flash_bwd_dq", 5e6, BWD % (scope, "flash_bwd_dq"))
    trace = {"devices": {0: {"ops": ops, "modules": []}},
             "host": {"main": [("bench.traced", 0.0, at[0] + 1000.0)]}}
    return {"cell": cells.Cell(CELL), "peaks": flops.peaks_for("TPU v5 lite"),
            "traced": {"busy_s": 0.56, "steps_seen": 2,
                       "step_busy_ms": 280.0, "op_seconds": {}},
            "_scopes": {"trace": trace}}


def test_the_flash_kernels_time_splits_by_the_window_scope():
    record = _record()
    assert _swa.flash_seconds(record) == pytest.approx(
        {"window": 0.060, "full": 0.240})
    assert _read("swa_share_pct", record) == pytest.approx(
        100 * 0.060 / 0.56)
    assert _read("global_attn_share_pct", record) == pytest.approx(
        100 * 0.240 / 0.56)
    # the least seconds by hand: 2 rows x 28 heads over the visible pairs at
    # 128 and 128, forward twice (the replay) and backward once, compute-bound
    peak = record["peaks"]["bf16_flops_per_s"]
    for metric, area, layers_, seconds in (
            ("swa_roofline_pct", flops_hybrid.visible_area(16384, 4096), 3,
             0.060),
            ("global_attn_roofline_pct", flops_hybrid.visible_area(16384), 1,
             0.240)):
        one = 2 * 2 * 28 * area * 128
        least = layers_ * (2 * 2 * one + 5 * one) / peak
        got = _read(metric, record)
        assert got == pytest.approx(100 * least * 2 / seconds)
    # a step's window calls could take 25 ms at the least, its full call
    # 19: these made-up times are faster than the chip, and the readers say
    # so without a cap
    assert _read("swa_roofline_pct", record) > 100


def test_a_program_without_the_scope_gives_neither_split():
    """The parent's program lowers a windowed call under no scope of its
    own: all flash time would read as the full layer's, so both kinds are
    left out (the family says three layers are windowed)."""
    record = _record(scoped=False)
    assert _swa.flash_seconds(record) is None
    for metric in tiny_st.METRICS[:4]:
        assert _read(metric, record) is None


@pytest.mark.parametrize("metric", tiny_st.METRICS)
def test_each_reader_is_left_out_where_there_is_nothing_to_read(metric):
    """A parent program has no window scope, no `moe.load` span of these
    layers; a run off the chip no device plane: every reader returns None
    and does not raise."""
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
