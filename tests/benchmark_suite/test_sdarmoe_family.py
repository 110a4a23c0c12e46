"""The `sdarmoe` family: the configuration file against the catalog row it was
cut from, the new cell and its entries against the contract (present and
resolving; nothing here asks for a place in a list), the cell's counts at
full size, the plain reference against the program at a tiny size (float32
to rounding, through the XLA attention and through the flash kernels), the
reference's blocking, its mask and its expert shares, `correct` under the
lower-precision controls and under a broken timed path, the new readers on
records made by hand, and a tiny cell through the unedited `run_cell` on the
CPU."""
import json
import os
import types

import numpy as np
import pytest

import tiny_root
import tiny_sdar
from benchmark import cells, flops, flops_bd, harness
from benchmark.layer_metrics import _bd
from test_smallthinker_family import _against_the_reference

REPO = cells.ROOT
CELL = "sdar-30b-a3b.bd4-t8192-b1"
CONFIG = "sdar-30b-a3b"
SOURCE = ("https://huggingface.co/JetLM/SDAR-30B-A3B-Chat/blob/main/"
          "config.json")

# the catalog row's `config` (model-configs guide, architectures.jsonl,
# "SDAR-30B-A3B-Chat"), every key
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]


@pytest.fixture(scope="module")
def sd(tmp_path_factory):
    return tiny_sdar.add(tiny_root.make(tmp_path_factory.mktemp("sdar")))


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
    # the floors: 8 experts held of all 128 routed over and folded, an
    # eighth of the vocabulary, six alike layers; no head and no width cut
    assert cfg["num_experts"] == 8 and cfg["experts_held"] == [0, 8]
    assert cfg["num_experts_routed"] == CATALOG["num_experts"]
    assert cfg["absent_experts"] == "folded"
    assert cfg["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert cfg["mask_token_id"] == cfg["vocab_size"] - 1
    assert cfg["num_hidden_layers"] == 6 \
        == len(cfg["published_layer_index"])
    for key in ("published", "assumed", "departures", "reduced_why",
                "deployment", "not_built"):
        assert cfg[key], key
    assert "16 chips share each layer" in cfg["deployment"]
    assert "24 bytes a parameter" in cfg["reduced_why"]
    for key in ("block_length", "noise_schedule", "label_shift",
                "mask_token_id", "loss_normalisation", "attention", "rotary",
                "router", "experts", "absent_experts", "norm", "head"):
        assert cfg["assumed"][key], key
    assert "U(0.45, 0.95)" in cfg["assumed"]["noise_schedule"]
    assert "generation loop" in cfg["not_built"]["generation"]
    # the parameter list adds up to what the file says it holds
    cell = cells.Cell(CELL)
    specs = cell.family.param_specs(cell.config, cell.traffic)
    count = sum(int(np.prod(shape)) for shape, _d, _k in specs.values())
    assert count == 419130880 and "419.1M" in cfg["reduced_why"]
    per_layer = {}
    for name, (shape, _d, _k) in specs.items():
        if name.startswith("sdar_layer_"):
            i = int(name.split("_")[2])
            per_layer[i] = per_layer.get(i, 0) + int(np.prod(shape))
    assert per_layer == {i: 56889600 for i in range(6)}
    assert specs["sdar_layer_1_experts_gate_up"] == ((8, 2048, 1536),
                                                     "bfloat16", "normal")
    assert specs["sdar_layer_1_experts_down"][0] == (8, 768, 2048)
    assert specs["sdar_layer_0_router.w_0"] == ((2048, 128), "float32",
                                                "normal")
    assert specs["sdar_layer_0_qkv.w_0"][0] == (2048, (32 + 2 * 4) * 128)
    assert specs["sdar_layer_0_out.w_0"][0] == (32 * 128, 2048)
    assert specs["sdar_layer_0_q_norm_s"] == specs["sdar_layer_0_k_norm_s"] \
        == ((128,), "float32", "ones")
    assert specs["sdar_lm_head"] == specs["sdar_word_embedding"] \
        == ((18992, 2048), "float32", "normal")
    assert not [n for n in specs if "bias" in n or "shared" in n]


def test_the_new_entries_are_present_and_resolve():
    b = bench()
    config = {c["name"]: c for c in b["configs"]}[CONFIG]
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert config["source"] == held()["source"] == SOURCE
    assert len(SOURCE) <= 200 and len(config["why"]) <= 200
    assert config["reduced"] == REDUCED
    entry = {w["name"]: w for w in b["workloads"]}[CELL]
    assert entry == dict(entry, config=CONFIG, traffic="bd4-t8192-b1",
                         chips=1)
    assert len(entry["why"]) <= 200 and "16x their share" in entry["why"]
    cell = cells.Cell(CELL)
    t = cell.traffic
    with open(os.path.join(REPO, "benchmark", "traffic",
                           "t8192-b1.json")) as f:
        plain = json.load(f)
    assert set(t) == set(plain) | {"block_length", "noise_range",
                                   "model_rows_per_step"}
    assert {k: t[k] for k in plain} == plain
    assert (t["block_length"], t["noise_range"], t["model_rows_per_step"]) \
        == (4, [0.45, 0.95], 16384)
    # what a user trains on: the document's tokens, not the doubled rows
    assert t["tokens_per_step"] == t["global_batch"] * t["seq_len"] == 8192 \
        == cell.family.tokens_per_step(t)
    assert cell.family.model_rows(t) == t["model_rows_per_step"]
    assert set(tiny_sdar.METRICS) <= {m["name"] for m in cell.per_layer}
    assert {"mfu_pct", "device_step_ms", "fwd_device_ms", "state_gib"} \
        <= {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {
        "tokens_per_s_per_chip", "peak_hbm_gib", "setup_s"}
    assert set(cell.limits) >= set(harness.GAPS)
    assert all(cell.limits["readings"][gap] for gap in harness.GAPS)


@pytest.mark.parametrize("name", tiny_sdar.METRICS)
def test_every_new_entry_has_its_reader_and_lists_the_cell(name):
    entry = {m["name"]: m for m in bench()["per_layer"]}[name]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "tokens_per_s_per_chip"
    assert entry["layer"] in ("Pallas kernels", "Step program")
    assert (entry["unit"] == "%") == name.endswith("_pct")
    assert callable(cells.Cell(CELL).layer_reader(name).read)
    # the old cells do not report it
    for old in ("lfm2-8b-a1b.t8192-b2", "smallthinker-21b-a3b.t16384-b2"):
        assert name not in {m["name"] for m in cells.Cell(old).per_layer}


def test_the_cells_counts_at_full_size():
    cell = cells.Cell(CELL)
    family = cell.family
    # every pick of the 16,384 rows is answered: rows x 8 picks, 16,384 a
    # held expert if routing is even; unfolded an eighth of a sixteenth
    assert family.expected_held_rows(cell.config, cell.traffic) == 131072
    assert family.expected_held_rows(
        dict(cell.config, absent_experts="nothing"), cell.traffic) == 8192
    area = flops_bd.visible_area_bd(8192, 4)
    assert area == 8192 * 8192 + 8192 * 4
    assert area / (4 * 8192 ** 2) == pytest.approx(0.25, abs=2e-4)
    with pytest.raises(ValueError, match="do not divide"):
        flops_bd.visible_area_bd(8192, 3)
    calls = family.attention_calls(cell.config, cell.traffic)
    assert [(c["kind"], c["count"]) for c in calls] == [
        ("forward", 2), ("backward", 1)] * 6
    assert all((c["batch"], c["q_heads"], c["kv_heads"], c["seq"],
                c["block_length"], c["d_qk"], c["d_v"])
               == (1, 32, 4, 8192, 4, 128, 128) for c in calls)
    # 1.10 TFLOP a layer forward; q, k, v, o over 16,384 rows once
    fwd, bwd = flops_bd.attention_call_flops(calls[0])
    assert fwd == 2 * 2 * 32 * area * 128 and bwd == 5 * fwd // 2
    assert fwd / 1e12 == pytest.approx(1.10, abs=0.005)
    assert flops_bd.attention_call_bytes(calls[0], 2) == (
        16384 * 128 * 2 * (32 + 4 + 4 + 32),
        2 * 16384 * 128 * 2 * (32 + 4 + 4 + 32))
    gmm = family.gmm_calls(cell.config, cell.traffic)
    assert [(c["layer"], c["k"], c["n"]) for c in gmm] == [
        ("sdar_layer_%d" % i, k, n) for i in range(6)
        for k, n in ((2048, 1536), (768, 2048))]
    assert all((c["groups"], c["fwd"], c["dx"], c["dw"]) == (8, 2, 1, 1)
               for c in gmm)
    # by hand: projections and router over 16,384 rows, attention by the
    # visible pairs, the held experts' rows, the head over 8,192 rows;
    # backward twice the forward
    rows, d = 16384, 2048
    layer = 2 * rows * d * 5120 + 2 * rows * 4096 * d + 2 * rows * d * 128 \
        + 2 * 131072 * 3 * d * 768 + fwd
    head = 2 * 8192 * d * 18992
    assert family.train_flops(cell.config, cell.traffic) \
        == 3 * (6 * layer + head)
    assert 0.35 < fwd / layer < 0.39        # attention's share of a layer


@pytest.mark.parametrize("name", [tiny_sdar.F32, tiny_sdar.F32_FLASH])
def test_float32_program_equals_the_reference(sd, name):
    """Loss and every leaf's gradient to 1e-4 relative under the float32
    cell's own limits (`Runner` also holds the family's parameter list to
    the program's): at 64 rows a sequence through the op's XLA attention,
    at 768 through the flash kernels (interpret mode), three tiles a
    half."""
    cell, got, ref = _against_the_reference(sd, name)
    rows = harness.compare(got, ref, cell.limits)
    assert all(r[3] for r in rows), rows
    for leaf, mine in got["first_gradient"].items():
        theirs = ref["first_gradient"][leaf]
        scale = max(float(np.max(np.abs(theirs))), 1e-6)
        assert float(np.max(np.abs(mine - theirs))) <= 1e-4 * scale, leaf
    assert {leaf.split("_", 3)[-1] for leaf in got["first_gradient"]} >= {
        "qkv.w_0", "out.w_0", "q_norm_s", "k_norm_s", "router.w_0",
        "experts_gate_up", "experts_down", "attn_norm_s", "ffn_norm_s"}
    from paddle_tpu.ops.pallas import flash_attention as fa
    rows_ = 2 * cell.traffic["seq_len"]
    path = fa.attention_path((1, 8, rows_, 16), (1, 1, rows_, 16),
                             (1, 1, rows_, 16), "float32", False, None, True,
                             auto=True, block_diffusion=(4, rows_ // 2))
    assert path.path == ("flash" if name == tiny_sdar.F32_FLASH else "xla")


@pytest.mark.parametrize("precision", ["bfloat16", "float8"])
def test_correct_fails_under_a_lower_precision_control(sd, precision):
    """The reference computed in the precision below the float32 cell's,
    compared as a program is: outside the cell's limits by `grad_diff` at
    least."""
    from benchmark import read_control
    cell = cells.Cell(tiny_sdar.F32, sd)
    got = read_control.read(tiny_sdar.F32, [11], platform="cpu", root=sd,
                            say=lambda _line: None,
                            bfloat16=precision == "bfloat16")
    kind = "bfloat16" if precision == "bfloat16" else "control_float8"
    for seed, gaps in got[kind].items():
        assert gaps["grad_diff"] > 10 * cell.limits["grad_diff"], seed
        assert gaps["loss_gap"] > cell.limits["loss_gap"], seed


def _no_noise_weights(runner):
    """A step whose loss weighs every row alike: the masked-denoising loss
    without its 1/t and without its mask."""
    real = runner.step

    def step(batch):
        weight = np.full_like(batch["loss_weight"],
                              1.0 / batch["loss_weight"].size)
        return real(dict(batch, loss_weight=weight))
    runner.step = step


def test_a_broken_timed_path_is_not_correct(sd):
    from test_harness import _state_unchanged
    for broken, failing in ((_state_unchanged, "delta_norm_gap"),
                            (_no_noise_weights, "loss_gap")):
        lines = []
        out = harness.run_cell(tiny_sdar.F32, 2 ** 31 + 5, 0.3, 0,
                               platform="cpu", root=sd, say=lines.append,
                               broken=broken)
        assert out["correct"] is False
        failed = [ln for ln in lines if ln.startswith("check ")
                  and "FAILED" in ln]
        assert any(failing in ln for ln in failed), lines


def test_blocking_the_reference_changes_no_value(sd, monkeypatch):
    """The reference walks the experts, the head and the queries in blocks
    so that it fits the chip at the cell's size; here the same loss and
    gradient with blocks of 8 and with one block."""
    import jax
    from benchmark import reference, weights
    from benchmark.families import lfm2moe
    cell = cells.Cell(tiny_sdar.F32, sd)
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


def test_the_eight_expert_shares_add_up_to_the_uncut_layer():
    """The share is the model's: over two ranks of 8 the PROGRAM's unfolded
    parts (`layers.moe_ffn(experts_held=)` as `moe_decoder.expert_ffn`
    builds it for SDAR: softmax over the picks, silu gates) add up to the
    REFERENCE's uncut 16-expert layer, and so do the reference's own."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.models import moe_decoder, sdar_moe
    from benchmark import reference
    family = cells.Cell(CELL).family
    s = {"routed": 16, "top_k": 4, "norm_topk": True, "held": (0, 16)}
    k = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(k[0], (24, 16))
    w_r = jax.random.normal(k[1], (16, 16))
    w13 = 0.5 * jax.random.normal(k[2], (16, 16, 16))
    w2 = 0.5 * jax.random.normal(k[3], (16, 8, 16))
    mm = reference.matmul_at("float32")
    with jax.default_matmul_precision("highest"):
        whole = family.expert_ffn(x, w_r, w13, w2, s, mm)
        parts = [family.expert_ffn(x, w_r, w13[f:f + 8], w2[f:f + 8], s, mm,
                                   held=(f, 8)) for f in (0, 8)]
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-5)
    assert all(float(jnp.max(jnp.abs(p))) > 1e-3 for p in parts)
    picks, weights = family.route(x, w_r, s, mm)
    assert picks.shape == (24, 4)
    np.testing.assert_allclose(weights.sum(1), 1.0, rtol=1e-6)

    def program_share(first):
        cfg = sdar_moe.SdarMoeConfig(
            vocab_size=8, hidden_size=16, num_heads=2, num_kv_heads=1,
            head_dim=8, moe_ff_size=8, num_experts=16, top_k=4,
            num_layers=1, experts_held=(first, 8))
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            u = layers.data("u", [24, 16], dtype="float32")
            out, _load = moe_decoder.expert_ffn(u, cfg, "share")
        scope = Scope()
        exe = pt.Executor()
        exe.run(startup, scope=scope)
        # copies: the step is given its state for good
        scope.set_var("share_router.w_0", jnp.array(w_r))
        scope.set_var("share_experts_gate_up", jnp.array(w13[first:first + 8]))
        scope.set_var("share_experts_down", jnp.array(w2[first:first + 8]))
        got, = exe.run(main, feed={"u": np.asarray(x)[None]},
                       fetch_list=[out], scope=scope)
        return got[0]

    with jax.default_matmul_precision("highest"):
        total = program_share(0) + program_share(8)
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)


def test_the_reference_masks_key_by_key():
    import jax.numpy as jnp
    family = cells.Cell(CELL).family
    row = jnp.arange(16)
    seen = np.asarray(family.visible(row, row, 2, 8))     # T 8, L 2
    # clean query 8 + 5 (block 2): clean keys of blocks 0..2, no noisy key
    assert seen[13].tolist() == [False] * 8 + [True] * 6 + [False] * 2
    # noisy query 5 (block 2): its own block's noisy keys, the clean keys
    # of blocks 0 and 1
    assert seen[5].tolist() == [False] * 4 + [True] * 2 + [False] * 2 \
        + [True] * 4 + [False] * 4
    # the first block's noisy rows see their block alone
    assert seen[0].tolist() == [True] * 2 + [False] * 14
    # the pairs it lets through are the count the readers go by
    row = jnp.arange(128)
    assert int(np.asarray(family.visible(row, row, 4, 64)).sum()) \
        == flops_bd.visible_area_bd(64, 4) == 64 * 64 + 64 * 4
    # and the kernels' own mask is the same mask
    from paddle_tpu.ops.pallas import flash_attention as fa
    np.testing.assert_array_equal(
        fa.visible_mask(128, 128, block_diffusion=(4, 64)),
        family.visible(row, row, 4, 64))


def test_rope_qk_norm_turns_by_the_references_angles():
    """`rope_qk_norm(position_period=T)` with learned scales against the
    reference's per-head RMS norm and its two halves turned apart."""
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.framework.scope import Scope
    from benchmark.families import lfm2moe as lfm
    rng = np.random.RandomState(1)
    t, heads, dh = 8, 2, 8
    q = rng.randn(1, 2 * t, heads * dh).astype(np.float32)
    k = rng.randn(1, 2 * t, dh).astype(np.float32)
    gq, gk = (rng.rand(dh).astype(np.float32) + 0.5 for _ in range(2))
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        qv = layers.data("q", [2 * t, heads * dh], dtype="float32")
        kv = layers.data("k", [2 * t, dh], dtype="float32")
        outs = layers.rope_qk_norm(qv, kv, dh, theta=1e6, epsilon=1e-6,
                                   name="r", position_period=t)
    scope = Scope()
    exe = pt.Executor()
    exe.run(startup, scope=scope)
    scope.set_var("r_q_norm_s", jnp.asarray(gq))
    scope.set_var("r_k_norm_s", jnp.asarray(gk))
    got_q, got_k = exe.run(main, feed={"q": q, "k": k},
                           fetch_list=list(outs), scope=scope)

    def want(x, count, scale):
        x = jnp.asarray(x).reshape(1, 2 * t, count, dh).transpose(2, 0, 1, 3)
        x = lfm.rms_norm(x, scale, 1e-6)
        halves = lfm.rotate_half(x.reshape(count, 1, 2, t, dh), 1e6)
        return np.asarray(halves.reshape(count, 1, 2 * t, dh)).transpose(
            1, 0, 2, 3)

    np.testing.assert_allclose(got_q, want(q, heads, gq), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(got_k, want(k, 1, gk), rtol=1e-5, atol=1e-5)


def test_the_batch_generator_makes_the_noise_the_traffic_states():
    from benchmark import weights
    cell = cells.Cell(CELL)
    batch = cell.family.make_batch(cell.config, cell.traffic,
                                   weights.host_rng(2 ** 31 + 9, 1))
    tok, noisy, weight = (batch[k][..., 0] for k in (
        "token_ids", "noisy_ids", "loss_weight"))
    assert tok.shape == noisy.shape == weight.shape == (1, 8192)
    assert tok.dtype == noisy.dtype == np.int64
    assert weight.dtype == np.float32
    mask_id = cell.config["mask_token_id"]
    assert tok.max() < mask_id and tok.min() >= 0
    masked = noisy == mask_id
    np.testing.assert_array_equal(noisy[~masked], tok[~masked])
    np.testing.assert_array_equal(weight > 0, masked)
    # about the mean of U(0.45, 0.95) of the rows carry a weight, and the
    # weight is 1 / (t B T) with one t a block of 4, t in the range
    assert 0.67 < masked.mean() < 0.73
    level = 1.0 / (weight[masked] * 8192)
    assert 0.45 <= level.min() and level.max() <= 0.95
    per_block = np.where(masked, 1.0 / np.maximum(weight * 8192, 1e-9),
                         np.nan).reshape(-1, 4)
    per_block = per_block[masked.reshape(-1, 4).any(1)]
    assert np.max(np.nanmax(per_block, 1) - np.nanmin(per_block, 1)) < 1e-3
    # E[m / t] = 1: the weights sum to about 1
    assert weight.sum() == pytest.approx(1.0, abs=0.03)
    blk = cell.family.block_of(batch, 0, 1)
    assert set(blk) == {"noisy", "tok", "weight"}
    assert cell.family.batch_rows(cell.traffic) == 1


def test_a_tiny_cell_runs_through_run_cell_on_the_cpu(sd):
    cell = cells.Cell(tiny_sdar.SHARE, sd)
    assert set(tiny_sdar.METRICS) <= {m["name"] for m in cell.per_layer}
    out = harness.run_cell(tiny_sdar.SHARE, 2 ** 31 + 5, 0.3, 0,
                           platform="cpu", root=sd)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"tokens_per_s_per_chip", "peak_hbm_gib",
                                   "setup_s"}
    # long enough for the three steps before the profiler and the two
    # under it, on a loaded machine
    traced = harness.run_cell(tiny_sdar.SHARE, 6, 1.0, 1, platform="cpu",
                              root=sd)
    assert traced["correct"] is True
    # no device plane off the TPU: the trace readers find nothing and the
    # line leaves them out; the span's reader reads
    assert set(tiny_sdar.METRICS) & set(traced["metrics"]) == {
        "bd_masked_rows_pct"}
    assert 45 < traced["metrics"]["bd_masked_rows_pct"]["value"] < 95
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
    """A traced window of two steps on one chip: a layer's four flash
    kernels (under the scope where `scoped`), a grouped matmul and a
    fusion; times in ns. With the `bd.noise` spans of a window of six
    steps."""
    scope = "block_diffusion_attention/" if scoped else ""
    ops, at = [], [1000.0]

    def op(name, ns, tf_op):
        ops.append(("%%%s.1 = bf16[2]{0} %s" % (name, "custom-call(...)"
                    if "fusion" not in name else "fusion(...)"),
                    at[0], at[0] + ns, tf_op))
        at[0] += ns

    for _step in range(2):
        op("flash_fwd", 40e6, FWD % scope)
        op("fusion", 100e6, "jit(step)/forward/mul/dot_general")
        op("moe_gmm_fwd", 30e6, "jit(step)/forward/moe_experts/moe_gmm_fwd/"
           "pallas_call")
        op("flash_fwd", 40e6, BWD % (scope, "flash_fwd"))
        op("flash_bwd_dkv", 60e6, BWD % (scope, "flash_bwd_dkv"))
        op("flash_bwd_dq", 40e6, BWD % (scope, "flash_bwd_dq"))
    trace = {"devices": {0: {"ops": ops, "modules": []}},
             "host": {"main": [("bench.traced", 0.0, at[0] + 1000.0)]}}
    spans = [{"name": "bd.noise", "labels": {
        "masked_rows": m, "rows": 8192, "weight_sum": 1.0}}
        for m in (1, 2, 3, 5600, 5800, 5700, 5900, 7)]
    return {"cell": cells.Cell(CELL), "peaks": flops.peaks_for("TPU v5 lite"),
            "traced": {"busy_s": 0.62, "steps_seen": 2,
                       "step_busy_ms": 310.0, "op_seconds": {}},
            "obs_spans": spans + [{"name": "exec.step", "labels": {}}],
            "_scopes": {"trace": trace}}


def test_the_flash_kernels_time_is_read_under_the_scope():
    record = _record()
    assert _bd.flash_seconds(record) == pytest.approx(0.360)
    assert _read("bd_attn_share_pct", record) == pytest.approx(
        100 * 0.360 / 0.62)
    # the least seconds by hand: six layers x 32 heads over the visible
    # pairs at 128 and 128, forward twice (the replay) and backward once,
    # compute-bound
    peak = record["peaks"]["bf16_flops_per_s"]
    one = 2 * 2 * 32 * (8192 * 8192 + 8192 * 4) * 128
    least = 6 * (2 * one + 5 * one // 2) / peak
    assert _bd.least_seconds(record) == pytest.approx(least)
    assert _read("bd_attn_roofline_pct", record) == pytest.approx(
        100 * least * 2 / 0.360)
    # the traced steps are window steps 3..6 of the eight: their spans alone
    assert _read("bd_masked_rows_pct", record) == pytest.approx(
        100 * (5600 + 5800 + 5700 + 5900) / (4 * 8192))


def test_a_program_without_the_scope_gives_no_attention_reading():
    """A program that lowers the call under no scope of its own (the parent
    has no such op at all) leaves both attention readings out."""
    record = _record(scoped=False)
    assert _bd.flash_seconds(record) is None
    assert _read("bd_attn_share_pct", record) is None
    assert _read("bd_attn_roofline_pct", record) is None


@pytest.mark.parametrize("metric", tiny_sdar.METRICS)
def test_each_reader_is_left_out_where_there_is_nothing_to_read(metric):
    """A parent program has no block-diffusion scope and no `bd.noise` or
    `moe.load` span of these layers; a run off the chip no device plane:
    every reader returns None and does not raise."""
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
