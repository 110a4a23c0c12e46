"""The `phi4flash` family: the configuration file against the catalog row
it was cut from, `flops_hybrid`'s hand counts, the plain reference against
the program at a tiny size (float32 to rounding; bfloat16 inside the limits
and the float8 control outside them), the reference's blocking, and a tiny
cell through `run_cell` on the CPU."""
import json
import os

import numpy as np
import pytest

import tiny_phi
import tiny_root
from benchmark import cells, flops_hybrid, harness, read_limits

REPO = cells.ROOT
SEEDS = [11, 2 ** 31 + 12]
CONTROL_SEEDS = [11]

# the catalog row's `config` (model-configs guide, architectures.jsonl,
# "Phi-4-mini-flash-reasoning"), every key
CATALOG = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}


@pytest.fixture(scope="module")
def phi(tmp_path_factory):
    return tiny_phi.add(tiny_root.make(tmp_path_factory.mktemp("phi")))


def held():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "phi4-mini-flash.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_published_sizes_equal_the_catalog_row(key):
    cfg = held()
    if key in ("num_hidden_layers", "vocab_size"):
        assert key in cfg["reduced"]
        assert cfg["published"][key] == CATALOG[key]
        assert cfg[key] < CATALOG[key]
    else:
        assert cfg[key] == CATALOG[key]
        assert key not in cfg["reduced"]


def test_the_cut_is_written_into_the_file():
    cfg = held()
    assert cfg["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert cfg["layer_kinds"] == tiny_phi.CUT
    assert cfg["published_layer_index"] == [1, 16, 17, 18, 19]
    assert cfg["num_hidden_layers"] == len(cfg["layer_kinds"]) == 5
    assert cfg["vocab_size"] * 8 == CATALOG["vocab_size"]      # the floor
    for key in ("published", "assumed", "departures", "reduced_why",
                "deployment"):
        assert cfg[key], key
    assert "24 bytes a parameter" in cfg["reduced_why"]
    assert (cfg["ssm_expand"], cfg["ssm_state_size"], cfg["ssm_conv_width"],
            cfg["ssm_dt_rank"]) == (2, 16, 4, 160)
    # the parameter list adds up to what the file says it holds
    cell = cells.Cell("phi4-mini-flash.t8192-b1")
    specs = cell.family.param_specs(cell.config, cell.traffic)
    count = sum(int(np.prod(shape)) for shape, _d, _k in specs.values())
    assert round(count / 1e6) == 577
    per_layer = {}
    for name, (shape, _d, _k) in specs.items():
        if name.startswith("phi_layer_"):
            i = int(name.split("_")[2])
            per_layer[i] = per_layer.get(i, 0) + int(np.prod(shape))
    millions = [round(per_layer[i] / 1e6, 1) for i in range(5)]
    assert millions == [98.3, 119.9, 98.3, 104.9, 91.8]


def test_hybrid_flops_hand_counts():
    assert flops_hybrid.visible_area(8) == 36
    assert flops_hybrid.visible_area(8, 3) == 6 + 5 * 3
    assert flops_hybrid.visible_area(8, 100) == 36
    s = {"d": 4, "ff": 8, "hq": 4, "hkv": 2, "dh": 2, "e": 8, "n": 2,
         "r": 1, "window": 2, "vocab": 10, "kinds": ["mamba"]}
    tokens = 2 * 4
    mlp = 2 * tokens * 4 * 16 + 2 * tokens * 8 * 4
    mamba = 2 * tokens * (4 * 16 + 8 * 5 + 1 * 8 + 8 * 4)
    head = 2 * tokens * 4 * 10
    assert flops_hybrid.hybrid_train_flops(s, 2, 4) \
        == 3 * (mlp + mamba + head)
    gmu = 2 * tokens * (4 * 8 + 8 * 4)
    assert flops_hybrid.hybrid_train_flops(dict(s, kinds=["gmu"]), 2, 4) \
        == 3 * (mlp + gmu + head)
    # window 2 over 4 positions: 1 + 2 + 2 + 2 visible pairs a row
    proj = 2 * tokens * 4 * (8 + 2 * 4) + 2 * tokens * 8 * 4
    attn = 2 * 4 * (2 * 7) * (2 + 4)
    assert flops_hybrid.hybrid_train_flops(dict(s, kinds=["window"]), 2, 4) \
        == 3 * (mlp + proj + attn + head)
    cross = 2 * tokens * 4 * 8 + 2 * tokens * 8 * 4
    full_area = 2 * 4 * (2 * 10) * (2 + 4)
    assert flops_hybrid.hybrid_train_flops(dict(s, kinds=["cross"]), 2, 4) \
        == 3 * (mlp + cross + full_area + head)
    call = {"batch": 2, "q_heads": 4, "kv_heads": 2, "seq": 8, "d_qk": 2,
            "d_v": 4, "window": None}
    area = 2 * 4 * 36
    assert flops_hybrid.attention_call_flops(call) \
        == (2 * area * 2 + 2 * area * 4, 3 * 2 * area * 2 + 2 * 2 * area * 4)
    q, o, k, v = (2 * 8 * 4 * 2 * 2, 2 * 8 * 4 * 4 * 2, 2 * 8 * 2 * 2 * 2,
                  2 * 8 * 2 * 4 * 2)
    assert flops_hybrid.attention_call_bytes(call, 2) \
        == (q + k + v + o, 2 * (q + k + v + o))
    wide, narrow = 1 * 16 * 8 * 2, 1 * 16 * 4 * 2
    assert flops_hybrid.scan_call_bytes(1, 16, 8, 4, 2) \
        == (3 * wide + 2 * narrow, 5 * wide + 4 * narrow)


def test_the_cells_counts_at_full_size():
    cell = cells.Cell("phi4-mini-flash.t8192-b1")
    total = cell.family.train_flops(cell.config, cell.traffic)
    # 6 x params x tokens for the matrices, plus attention by visible area
    assert 28e12 < total < 32e12
    calls = cell.family.attention_calls(cell.config, cell.traffic)
    assert len(calls) == 6 and {c["window"] for c in calls} == {512, None}
    assert all((c["batch"], c["q_heads"], c["kv_heads"], c["d_qk"],
                c["d_v"]) == (2, 20, 10, 64, 128) for c in calls)
    assert cell.family.scan_calls(cell.config, cell.traffic) \
        == [(1, 8192, 5120, 16, 2, 1)]


@pytest.mark.parametrize("name", tiny_phi.CELLS)
def test_float32_program_equals_the_reference(phi, name):
    """Loss and every leaf's gradient to 1e-4 relative, for the cut's
    layout and for the published rule at L=8."""
    cell = cells.Cell(name, phi)
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
    rows = harness.compare(got, ref, {"loss_gap": 1e-5, "grad_diff": 1e-4,
                                      "grad_norm_gap": 1e-4,
                                      "delta_norm_gap": 1e-2})
    assert all(r[3] for r in rows), rows
    for leaf, mine in got["first_gradient"].items():
        theirs = ref["first_gradient"][leaf]
        scale = max(float(np.max(np.abs(theirs))), 1e-6)
        assert float(np.max(np.abs(mine - theirs))) <= 1e-4 * scale, leaf


@pytest.fixture(scope="module")
def readings(phi):
    return {name: read_limits.read(name, SEEDS, CONTROL_SEEDS,
                                   platform="cpu", root=phi)
            for name in tiny_phi.CELLS}


@pytest.mark.parametrize("name", tiny_phi.CELLS)
def test_bfloat16_program_is_inside_the_limits_on_every_seed(readings, name):
    for seed, gaps in readings[name]["program"].items():
        print(name, seed, gaps)
        for key in harness.GAPS:
            assert gaps[key] <= tiny_phi.LIMITS[key], (seed, key, gaps[key])


@pytest.mark.parametrize("name", tiny_phi.CELLS)
def test_the_float8_control_is_outside_them_on_every_seed(readings, name):
    sound = max(g["grad_diff"] for g in readings[name]["program"].values())
    for seed, gaps in readings[name]["control_float8"].items():
        print(name, seed, gaps)
        assert gaps["grad_diff"] > 2 * tiny_phi.LIMITS["grad_diff"], gaps
        assert tiny_phi.LIMITS["grad_diff"] > 1.5 * sound


def test_blocking_the_reference_changes_no_value(phi, monkeypatch):
    """The reference walks the MLP, the head, the queries and the Mamba
    layers in blocks so that it fits the chip at the cell's size; here the
    same loss and gradient with blocks of 8 (four a sequence, so the
    convolution's tail and the scan's state cross three borders) and with
    one block."""
    import jax
    from benchmark import reference, weights
    cell = cells.Cell("tiny-phi-cut.t32-b2", phi)
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
    monkeypatch.setattr(family, "SCAN_CHUNK", 4)
    blocked_loss, blocked = loss_and_grad()
    assert float(blocked_loss) == pytest.approx(float(whole_loss), rel=1e-6)
    for leaf in whole:
        scale = max(float(np.max(np.abs(whole[leaf]))), 1e-8)
        assert float(np.max(np.abs(blocked[leaf] - whole[leaf]))) \
            <= 1e-4 * scale, leaf


def test_a_tiny_cell_runs_through_run_cell_on_the_cpu(phi):
    cell = cells.Cell("tiny-phi-cut.t32-b2", phi)
    assert set(tiny_phi.METRICS) <= {m["name"] for m in cell.per_layer}
    out = harness.run_cell("tiny-phi-cut.t32-b2", 2 ** 31 + 5, 0.3, 0,
                           platform="cpu", root=phi)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s_per_chip", "peak_hbm_gib",
                                   "setup_s"}
    traced = harness.run_cell("tiny-phi-cut.t32-b2", 6, 0.3, 1,
                              platform="cpu", root=phi)
    assert traced["correct"] is True
    # no device plane off the TPU: the new readers find nothing and the
    # line leaves their metrics out
    assert not set(tiny_phi.METRICS) & set(traced["metrics"])
    assert "recompiles_in_window" in traced["metrics"]


def test_read_control_reads_what_read_limits_reads(phi, readings):
    """`read_control.py` follows `reference.follow`'s steps with the start
    weights on the host and no program on the device: the same gaps."""
    from benchmark import read_control
    name = tiny_phi.CELLS[0]
    got = read_control.read(name, [11], platform="cpu", root=phi,
                            say=lambda *_a: None)
    want = readings[name]["control_float8"]["11"]
    for key in harness.GAPS:
        assert got["control_float8"]["11"][key] == pytest.approx(
            want[key], rel=1e-4, abs=1e-9), key
