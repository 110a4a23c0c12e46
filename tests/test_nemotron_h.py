"""The `nemotron_h` stack through the normal path at a tiny size: the program
trains as one jitted step, its loss, every leaf's first gradient and three
Adam steps follow the plain reference (`benchmark/families/nemotronh.py`,
which imports nothing of paddle_tpu), the sixteen `experts_held` shares of an
expert layer add up to the uncut 128-expert reference with the shared expert
counted once, and the four older expert programs are op for op what they
were. The ops it brought (`mamba2_scan`, `mamba2_gate_norm`, the grouped
matmuls off the 128-lane grid, relu^2 experts) are in
`test_nemotron_h_ops.py`."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, optimizer
from paddle_tpu.framework.scope import Scope
from paddle_tpu.models import moe_decoder
from paddle_tpu.models import nemotron_h as nh
from paddle_tpu.ops import moe_ops
from _moe_cases import _op_digest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CONFIG = {      # the benchmark's keys, at a tiny size
    "family": "nemotronh", "precision": "float32", "hidden_size": 64,
    "hybrid_override_pattern": "MEM*E", "num_hidden_layers": 5,
    "mamba_num_heads": 4, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16,
    "num_attention_heads": 4, "num_key_value_heads": 1, "head_dim": 16,
    "mlp_hidden_act": "relu2", "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 48, "n_shared_experts": 1,
    "n_routed_experts": 4, "num_experts_routed": 8, "experts_held": [4, 4],
    "absent_experts": "folded", "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5, "n_group": 1,
    "topk_group": 1, "layer_norm_epsilon": 1e-5, "vocab_size": 64,
    "initializer_range": 0.02,
    "optimizer": {"name": "adam", "learning_rate": 1e-3, "beta1": 0.9,
                  "beta2": 0.999, "epsilon": 1e-8}}
TRAFFIC = {"seq_len": 40, "batch_per_chip": 2, "global_batch": 2,
           "tokens_per_step": 80, "reference_block_rows": 1}


def _family():
    from benchmark import cells
    return cells._load_module(
        os.path.join(REPO, "benchmark", "families", "nemotronh.py"),
        "benchmark_family_nemotronh_for_the_model_test")



# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def test_the_published_sizes_are_the_default_and_are_read_from_the_keys():
    cfg = nh.NemotronHConfig()
    assert (cfg.hidden_size, cfg.mamba_heads, cfg.mamba_head_dim,
            cfg.mamba_groups, cfg.ssm_state, cfg.conv_width, cfg.chunk_size,
            cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.moe_ff_size,
            cfg.shared_ff_size, cfg.num_experts, cfg.top_k,
            cfg.routed_scaling_factor, cfg.vocab_size, cfg.num_layers) == (
        2688, 64, 64, 8, 128, 4, 128, 32, 2, 128, 1856, 3712, 128, 6, 2.5,
        131072, 52)
    kinds = [cfg.kind(i) for i in range(52)]
    assert (kinds.count("mamba"), kinds.count("experts"),
            kinds.count("attention")) == (23, 23, 6)
    assert cfg.pattern[:7] == "MEMEM*E"
    read = nh.NemotronHConfig.from_published(CONFIG)
    assert (read.pattern, read.num_experts, read.experts_held,
            read.absent_picks, read.expert_act) == (
        "MEM*E", 8, (4, 4), "folded", "relu2")


@pytest.mark.parametrize("key,value", [
    ("mlp_hidden_act", "silu"), ("n_group", 2), ("use_conv_bias", False),
    ("sliding_window", 4096), ("hybrid_override_pattern", "ME-*E")])
def test_a_published_key_the_program_does_not_build_is_refused(key, value):
    with pytest.raises(ValueError, match="builds"):
        nh.NemotronHConfig.from_published(dict(CONFIG, **{key: value}))


def test_param_specs_equal_the_programs_parameters():
    family = _family()
    main, startup, _loss = family.build(CONFIG, TRAFFIC,
                                        optimizer.Adam(1e-3).minimize)
    scope = Scope()
    pt.Executor().run(startup, scope=scope)
    specs = family.param_specs(CONFIG, TRAFFIC)
    assert {p.name for p in main.global_block().all_parameters()} \
        == set(specs)
    for name, (shape, dtype, kind) in specs.items():
        have = scope.find_var(name)
        assert tuple(have.shape) == tuple(shape), name
        assert str(have.dtype) == dtype, name
        if kind != "normal":    # the program's own start is the family's
            assert float(jnp.max(jnp.abs(
                have - (1.0 if kind == "ones" else 0.0)))) == 0.0, name
    # one norm a layer, and each layer one block alone
    ops = [op.type for op in main.global_block().ops]
    assert ops.count("remat_block") == 5
    ops = [op.type for blk in main.blocks[1:] for op in blk.ops]
    assert ops.count("rms_norm") == 5
    assert ops.count("mamba2_scan") == ops.count("mamba2_gate_norm") == 2
    assert ops.count("moe_experts") == 2


def _reference_stepper(family):
    """(params, batch) -> the reference's loss and gradient, row by row;
    `reference_loss` is traced once here, and every row of every step runs
    the one compiled walk."""
    from benchmark import reference
    mm = reference.matmul_at("float32")
    value_and_grad = jax.jit(jax.value_and_grad(
        lambda p, blk: family.reference_loss(p, blk, CONFIG, TRAFFIC, mm)))

    def step(params, batch):
        with jax.default_matmul_precision("highest"):
            want, grads = 0.0, None
            for lo in range(2):
                part, g = value_and_grad(
                    params, family.block_of(batch, lo, lo + 1))
                want += float(part)
                grads = g if grads is None else jax.tree_util.tree_map(
                    jnp.add, grads, g)
        return want, grads
    return step


def test_loss_first_gradient_and_three_adam_steps_follow_the_reference():
    """The program in float32 through Executor (recompute on, as the cell
    runs) against `reference_loss` + `reference.adam_update` from the same
    seeded weights on the same batches: each loss to 1e-5, every leaf's
    first gradient (read back from Adam's first moment after one step, as
    the harness reads it) to 1e-4 of its largest entry, the parameters
    after three steps to 2% of what they moved."""
    from benchmark import reference, weights
    family = _family()
    opt = CONFIG["optimizer"]
    main, startup, loss = family.build(
        CONFIG, TRAFFIC, optimizer.Adam(
            opt["learning_rate"], beta1=opt["beta1"], beta2=opt["beta2"],
            epsilon=opt["epsilon"]).minimize)
    scope, exe = Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    start = weights.weight_maker(family.param_specs(CONFIG, TRAFFIC),
                                 0.02)(17)
    params = weights.as_float32(start)
    first = {k: np.asarray(v) for k, v in params.items()}
    for name, value in start.items():   # the step donates what it is given
        scope.set_var(name, value)
    m1 = jax.tree_util.tree_map(jnp.zeros_like, params)
    m2 = jax.tree_util.tree_map(jnp.zeros_like, params)
    reference_step = _reference_stepper(family)
    rng = weights.host_rng(17, 1)
    for step in range(3):
        batch = family.make_batch(CONFIG, TRAFFIC, rng)
        got = float(exe.run(main, feed=batch, fetch_list=[loss],
                            scope=scope)[0].reshape(-1)[0])
        want, grads = reference_step(params, batch)
        assert got == pytest.approx(want, rel=1e-5), step
        if step == 0:
            moments = {n.rpartition("_moment1_")[0]: scope.find_var(n)
                       for n in scope.keys() if "_moment1_" in n}
            assert set(moments) == set(grads)
            for leaf, theirs in grads.items():
                mine = np.asarray(moments[leaf]) / (1.0 - opt["beta1"])
                scale = max(float(jnp.max(jnp.abs(theirs))), 1e-8)
                assert float(np.max(np.abs(mine - np.asarray(theirs)))) \
                    <= 1e-4 * scale, leaf
        params, m1, m2 = reference.adam_update(params, grads, m1, m2,
                                               step + 1, opt)
    for name, want in params.items():
        have = np.asarray(scope.find_var(name))
        moved = np.asarray(want) - first[name]
        assert np.max(np.abs(have - np.asarray(want))) \
            <= 2e-2 * np.max(np.abs(moved)) + 1e-7, name


@pytest.mark.parametrize("dtype,recompute", [("float32", True),
                                             ("bfloat16", True),
                                             ("float32", False)])
def test_the_program_trains_as_one_jitted_step(dtype, recompute):
    cfg = nh.NemotronHConfig.from_published(CONFIG, dtype=dtype,
                                            recompute=recompute)
    main, startup, _feeds, fetch = nh.nemotron_h_pretrain_program(
        cfg, 2, 40, optimizer_fn=optimizer.Adam(3e-3).minimize)
    assert len(fetch["expert_load"]) == 2
    scope, exe = Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    misses = exe.cache_misses
    toks = np.random.RandomState(0).randint(0, 64, (2, 41)).astype(np.int64)
    feed = {"token_ids": toks[:, :-1, None], "labels": toks[:, 1:, None],
            "loss_mask": np.ones((2, 40, 1), np.float32)}
    losses = [float(exe.run(main, feed=feed, fetch_list=[fetch["loss"]],
                            scope=scope)[0].reshape(-1)[0])
              for _ in range(30)]
    assert exe.cache_misses == misses + 1       # one compiled step
    assert np.isfinite(losses).all() and losses[-1] < 0.8 * losses[0]
    load = np.asarray(scope.find_var("nh_layer_1_expert_load"))
    assert load.shape == (8,) and load[4:].sum() == load.sum() == 80 * 2


def test_moving_the_first_token_changes_a_later_positions_loss():
    """The Mamba-2 layers carry position and history: with the attention
    layer position-free, the loss at the last position still depends on
    WHERE an early token stood (swap tokens 0 and 1)."""
    cfg = nh.NemotronHConfig.from_published(dict(
        CONFIG, hybrid_override_pattern="M*", num_hidden_layers=2))
    main, startup, _feeds, fetch = nh.nemotron_h_pretrain_program(cfg, 1, 24)
    scope, exe = Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    toks = np.random.RandomState(4).randint(0, 64, (1, 25)).astype(np.int64)
    toks[0, 0], toks[0, 1] = 3, 9

    def last_loss(tokens):
        mask = np.zeros((1, 24, 1), np.float32)
        mask[0, -1] = 1.0
        return float(exe.run(main, feed={
            "token_ids": tokens[:, :-1, None], "labels": tokens[:, 1:, None],
            "loss_mask": mask}, fetch_list=[fetch["loss"]],
            scope=scope)[0].reshape(-1)[0])

    swapped = toks.copy()
    swapped[0, 0], swapped[0, 1] = 9, 3
    assert abs(last_loss(toks) - last_loss(swapped)) > 1e-6


# ---------------------------------------------------------------------------
# the share test
# ---------------------------------------------------------------------------

SIZES = {"d": 64, "moe_ff": 24, "shared_ff": 48, "routed": 128, "top_k": 6,
         "shared": 1, "eps": 1e-5, "norm_topk": True, "scaling": 2.5,
         "held": (0, 128), "absent": "nothing"}


def _expert_weights(seed=3):
    rng = np.random.default_rng(seed)

    def w(*shape, scale=0.2):
        return (scale * rng.standard_normal(shape)).astype(np.float32)

    return {"router.w_0": w(64, 128, scale=1.0),
            "experts_up": w(128, 64, 24), "experts_down": w(128, 24, 64),
            "shared_up.w_0": w(64, 48), "shared_down.w_0": w(48, 64)}


def _program_share(weights, experts_held, x, absent="nothing"):
    """One forward Program: the model's expert block
    (`moe_decoder.expert_ffn` under `expert_act` "relu2": router + `moe_ffn`
    + the shared expert) for the given share, through `Executor`."""
    cfg = nh.NemotronHConfig.from_published(dict(
        CONFIG, num_experts_routed=128, num_experts_per_tok=6,
        n_routed_experts=experts_held[1], experts_held=list(experts_held),
        absent_experts=absent))
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        u = layers.data("u", list(x.shape), dtype="float32",
                        append_batch_size=False)
        out, load = moe_decoder.expert_ffn(u, cfg, "ffn")
    scope, exe = Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    first, count = experts_held
    named = {"ffn_" + k: (v[first:first + count]
                          if k.startswith("experts_") else v)
             for k, v in weights.items()}
    for p in main.global_block().all_parameters():
        assert tuple(scope.find_var(p.name).shape) \
            == tuple(named[p.name].shape), p.name
        scope.set_var(p.name, jnp.asarray(named[p.name]))
    got = exe.run(main, feed={"u": x}, fetch_list=[out, load], scope=scope)
    return np.asarray(got[0]), np.asarray(got[1])


def test_the_sixteen_shares_add_up_to_the_uncut_128_expert_reference():
    """Sixteen ranks of 8 experts, a pick on an absent expert adding
    nothing: their parts, with the shared expert (which every rank computes
    alike) counted ONCE, are what the reference gives for the uncut
    layer."""
    from benchmark import reference
    family = _family()
    mm = reference.matmul_at("float32")
    whole = _expert_weights()
    x = np.random.default_rng(8).standard_normal((2, 16, 64)).astype(
        np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(family.ffn_part(x, whole, SIZES, mm))
        shared = np.asarray(family._relu2_mlp(
            x, whole["shared_up.w_0"], whole["shared_down.w_0"], mm))
    parts = [_program_share(whole, (first, 8), x)
             for first in range(0, 128, 8)]
    loads = np.stack([load for _out, load in parts])
    assert (loads == loads[0]).all()        # every rank counts all 128
    assert loads[0].sum() == 32 * 6
    total = sum(out for out, _load in parts) - 15 * shared
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)
    assert float(np.max(np.abs(shared))) > 1e-3
    assert float(np.max(np.abs(want - shared))) > 1e-3
    # folded, one share answers every pick
    out, load = _program_share(whole, (8, 8), x, absent="folded")
    assert load[8:16].sum() == load.sum() == 32 * 6
    with jax.default_matmul_precision("highest"):
        folded = np.asarray(family.ffn_part(
            x, dict(whole, experts_up=whole["experts_up"][8:16],
                    experts_down=whole["experts_down"][8:16]),
            dict(SIZES, absent="folded"), mm, held=(8, 8)))
    np.testing.assert_allclose(out, folded, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# the programs that were there
# ---------------------------------------------------------------------------

def _older_program(model):
    from paddle_tpu.models import (kimi_linear, kimi_vl, lfm2moe,
                                   smallthinker)
    adam = optimizer.Adam(1e-3).minimize
    if model == "lfm2moe":
        return lfm2moe.lfm2moe_pretrain_program(lfm2moe.Lfm2MoeConfig(
            vocab_size=96, hidden_size=64, num_heads=4, num_kv_heads=2,
            head_dim=16, ff_size=128, moe_ff_size=32, num_experts=8, top_k=2,
            experts_held=(4, 4), layer_kinds=["conv", "attention", "conv"],
            published_layer_index=[0, 2, 3], recompute=True,
            dtype="bfloat16", expert_bias_update_rate=0.001), 2, 32,
            optimizer_fn=adam)[0]
    if model == "kimi_linear":
        return kimi_linear.kimi_linear_pretrain_program(
            kimi_linear.KimiLinearConfig(
                vocab_size=96, hidden_size=64, num_heads=4, kda_head_dim=16,
                gate_rank=8, qk_nope_dim=16, qk_rope_dim=8, v_dim=16,
                kv_rank=24, ff_size=128, moe_ff_size=32, num_experts=16,
                top_k=2, experts_held=(8, 8), heads_held=(2, 2),
                layer_kinds=["kda", "kda", "mla"],
                published_layer_index=[1, 2, 4], recompute=True,
                dtype="bfloat16"), 2, 64, optimizer_fn=adam)[0]
    if model == "nemotron_h":
        return nh.nemotron_h_pretrain_program(
            nh.NemotronHConfig.from_published(CONFIG, dtype="bfloat16",
                                              recompute=True), 2, 32,
            optimizer_fn=adam)[0]
    if model == "kimi_vl":
        return kimi_vl.kimi_vl_pretrain_program(kimi_vl.KimiVLConfig(
            vocab_size=64, hidden_size=64, num_heads=4, qk_nope_dim=16,
            qk_rope_dim=8, v_dim=16, kv_rank=32, ff_size=128, moe_ff_size=32,
            num_experts=8, top_k=2, num_shared_experts=2, num_layers=3,
            experts_held=(4, 4), absent_picks="folded", recompute=True,
            dtype="bfloat16"), 2, 32, optimizer_fn=adam)[0]
    return smallthinker.smallthinker_pretrain_program(
        smallthinker.SmallThinkerConfig(
            vocab_size=64, hidden_size=64, num_heads=4, num_kv_heads=2,
            head_dim=16, moe_ff_size=32, num_experts=8, top_k=2,
            experts_held=(4, 4), window=8, window_layout=[0, 1, 1],
            absent_picks="folded", recompute=True, dtype="bfloat16"), 2, 32,
        optimizer_fn=adam)[0]


#: computed with `_op_digest` on the tree before this one (commit 8295fcc)
OLDER = {"lfm2moe": (104, "6bca53fa810a0b5b"),
         "kimi_linear": (166, "157f9636a25dbdfc"),
         "kimi_vl": (159, "99e8e6789283cd68"),
         "smallthinker": (113, "ed43b7735b898bfb")}


@pytest.mark.parametrize("model", sorted(OLDER))
def test_the_four_older_expert_programs_are_op_for_op_what_they_were(model):
    """The non-gated form, the second frame and `mamba2_mixer` at their
    defaults add no op, no slot and no attr to LFM2's, both Kimis' and
    SmallThinker's programs."""
    main = _older_program(model)
    assert _op_digest(main) == OLDER[model]
    experts = [op for blk in main.blocks for op in blk.ops
               if op.type == "moe_experts"]
    assert experts and all(op.attrs.get("gate", "silu") in moe_ops.GATES
                           for op in experts)
    assert not [op for blk in main.blocks for op in blk.ops
                if op.type.startswith("mamba2_")]


#: Nemotron's own program, by `_op_digest` on the tree before `sdar_moe`
#: (commit fac9114)
NEMOTRON = (133, "5b058e75c3ac556d")


@pytest.mark.parametrize("model", sorted(OLDER) + ["nemotron_h"])
def test_the_older_programs_carry_nothing_of_the_block_diffusion_model(
        model):
    """`moe_decoder.expert_ffn`'s `scoring` read, `rope_qk_norm`'s
    `position_period` and `fused_attention`'s `block_diffusion` at their
    defaults add no op, no slot and no attr: an op without them serialises
    none, and Nemotron's program (through `moe_decoder`) is op for op what
    it was."""
    main = _older_program(model)
    if model == "nemotron_h":
        assert _op_digest(main) == NEMOTRON
    ops = [op for blk in main.blocks for op in blk.ops]
    assert not [op.type for op in ops
                if "block_diffusion" in op.attrs
                or "position_period" in op.attrs]
    routes = [op for op in ops if op.type == "moe_route"]
    assert routes and all(
        ("scoring" in op.attrs) == (model == "smallthinker")
        for op in routes)
