"""LFM2-8B-A1B through the normal path at a tiny size: the program trains
as one jitted step, three Adam steps follow the plain reference
(`benchmark/families/lfm2moe.py:reference_loss`, which imports nothing of
paddle_tpu), the family's parameter list is the program's, and each expert
layer's load reaches `obs` as a `moe.load` span a step without moving
`Executor.cache_misses`."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import optimizer
from paddle_tpu.framework import obs
from paddle_tpu.framework.scope import Scope
from paddle_tpu.models import lfm2moe as lm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

KINDS = ["conv", "attention", "conv", "conv", "conv"]
PUBLISHED = [0, 2, 3, 4, 5]
CONFIG = {      # the benchmark's keys, at a tiny size
    "family": "lfm2moe", "precision": "float32", "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 96,
    "num_experts": 4, "num_experts_routed": 8, "experts_held": [4, 4],
    "num_experts_per_tok": 2, "num_dense_layers": 2, "conv_L_cache": 3,
    "norm_eps": 1e-5, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "use_expert_bias": True, "rope_theta": 1000000,
    "initializer_range": 0.02, "layer_kinds": KINDS,
    "published_layer_index": PUBLISHED,
    "optimizer": {"name": "adam", "learning_rate": 1e-3, "beta1": 0.9,
                  "beta2": 0.999, "epsilon": 1e-8}}
TRAFFIC = {"seq_len": 32, "batch_per_chip": 2, "global_batch": 2,
           "tokens_per_step": 64, "reference_block_rows": 1}


def tiny(**kw):
    base = dict(vocab_size=96, hidden_size=64, num_heads=4, num_kv_heads=2,
                head_dim=16, ff_size=128, moe_ff_size=32, num_experts=8,
                top_k=2, experts_held=(4, 4), layer_kinds=KINDS,
                published_layer_index=PUBLISHED)
    base.update(kw)
    return lm.Lfm2MoeConfig(**base)


def _feed(seed=0):
    toks = np.random.RandomState(seed).randint(0, 96, (2, 33)).astype(
        np.int64)
    return {"token_ids": toks[:, :-1, None], "labels": toks[:, 1:, None],
            "loss_mask": np.ones((2, 32, 1), np.float32)}


def test_the_published_pattern_is_the_default():
    cfg = lm.Lfm2MoeConfig()
    assert cfg.num_layers == 24
    assert [i for i, k in enumerate(cfg.layer_kinds) if k == "attention"] \
        == [2, 6, 10, 14, 18, 21]
    assert [cfg.is_dense(i) for i in range(4)] == [True, True, False, False]
    assert (cfg.hidden_size, cfg.ff_size, cfg.moe_ff_size, cfg.num_experts,
            cfg.top_k, cfg.conv_width, cfg.rope_theta) \
        == (2048, 7168, 1792, 32, 4, 3, 1e6)
    assert cfg.experts_held == (0, 32)


@pytest.mark.parametrize("kw,match", [
    (dict(layer_kinds=["conv", "mamba"]), "unknown layer kinds"),
    (dict(published_layer_index=[0, 2]), "one entry a layer"),
    (dict(num_heads=4, num_kv_heads=3), "do not group")])
def test_a_config_that_cannot_run_is_refused_by_name(kw, match):
    with pytest.raises(ValueError, match=match):
        tiny(**kw)


@pytest.mark.parametrize("dtype,recompute", [("float32", True),
                                             ("bfloat16", True),
                                             ("float32", False)])
def test_the_program_trains_as_one_jitted_step(dtype, recompute):
    cfg = tiny(dtype=dtype, recompute=recompute)
    main, startup, feeds, fetch = lm.lfm2moe_pretrain_program(
        cfg, 2, 32, optimizer_fn=optimizer.Adam(2e-3).minimize)
    assert feeds == ["token_ids", "labels", "loss_mask"]
    types = [op.type for blk in main.blocks for op in blk.ops]
    assert ([op.type for op in main.global_block().ops].count("remat_block")
            == 5) == recompute
    for op_type, count in (("moe_route", 4), ("moe_dispatch", 4),
                           ("moe_experts", 4), ("moe_combine", 4),
                           ("rope_qk_norm", 1), ("causal_conv1d", 4)):
        assert types.count(op_type) == count, op_type
    scope, exe = Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    misses = exe.cache_misses
    feed = _feed()
    losses = [float(exe.run(main, feed=feed, fetch_list=[fetch["loss"]],
                            scope=scope)[0].reshape(-1)[0])
              for _ in range(25)]
    assert exe.cache_misses == misses + 1       # one compiled step
    assert losses[0] == pytest.approx(np.log(96), rel=0.05)
    assert losses[-1] < 0.75 * losses[0]
    names = {p.name for p in main.global_block().all_parameters()}
    assert {"lfm_layer_0_mlp_gate_up.w_0", "lfm_layer_1_qkv.w_0",
            "lfm_layer_1_q_norm_s", "lfm_layer_1_router.w_0",
            "lfm_layer_2_experts_gate_up", "lfm_layer_4_experts_down",
            "lfm_layer_3_conv.w_0", "lfm_norm_f_s"} <= names
    # the dense layer has no router, an expert layer no dense MLP, and the
    # expert bias and the load counters are state, not parameters
    assert "lfm_layer_0_router.w_0" not in names
    assert "lfm_layer_1_mlp_down.w_0" not in names
    assert not {n for n in names if "expert_bias" in n or "expert_load" in n}
    bias = np.asarray(scope.find_var("lfm_layer_1_expert_bias"))
    assert bias.shape == (8,) and (bias == 0).all()     # no update rate
    load = np.asarray(scope.find_var("lfm_layer_3_expert_load"))
    assert load.shape == (8,) and load.dtype == np.int32
    assert load.sum() == 64 * 2 and 0 < load[4:].sum() <= 64 * 2


def _family():
    from benchmark import cells
    return cells._load_module(
        os.path.join(REPO, "benchmark", "families", "lfm2moe.py"),
        "benchmark_family_lfm2moe_for_the_model_test")


def test_param_specs_equal_the_programs_parameters():
    family = _family()
    main, startup, loss = family.build(CONFIG, TRAFFIC,
                                       optimizer.Adam(1e-3).minimize)
    scope = Scope()
    pt.Executor().run(startup, scope=scope)
    specs = family.param_specs(CONFIG, TRAFFIC)
    params = {p.name for p in main.global_block().all_parameters()}
    assert params == set(specs)
    for name, (shape, dtype, _kind) in specs.items():
        have = scope.find_var(name)
        assert tuple(have.shape) == tuple(shape), name
        assert str(have.dtype) == dtype, name


def test_three_adam_steps_follow_the_plain_reference():
    """The program in float32 through Executor against `reference_loss` +
    `reference.adam_update` from the same seeded weights on the same
    batches: each loss to 1e-5, the parameters after three steps to
    rounding."""
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
    mm = reference.matmul_at("float32")
    # jitted once: every row of every step runs the one compiled walk
    value_and_grad = jax.jit(jax.value_and_grad(
        lambda p, blk: family.reference_loss(p, blk, CONFIG, TRAFFIC, mm)))
    rng = weights.host_rng(17, 1)
    for step in range(3):
        batch = family.make_batch(CONFIG, TRAFFIC, rng)
        got = float(exe.run(main, feed=batch, fetch_list=[loss],
                            scope=scope)[0].reshape(-1)[0])
        with jax.default_matmul_precision("highest"):
            want, grads = 0.0, None
            for lo in range(2):
                part, g = value_and_grad(
                    params, family.block_of(batch, lo, lo + 1))
                want += float(part)
                grads = g if grads is None else jax.tree_util.tree_map(
                    jnp.add, grads, g)
            params, m1, m2 = reference.adam_update(params, grads, m1, m2,
                                                   step + 1, opt)
        assert got == pytest.approx(want, rel=1e-5), step
    for name, want in params.items():
        have = np.asarray(scope.find_var(name))
        moved = np.asarray(want) - first[name]
        assert np.max(np.abs(have - np.asarray(want))) \
            <= 2e-2 * np.max(np.abs(moved)) + 1e-7, name


def test_moe_load_spans_appear_with_obs_on_and_cache_misses_stay():
    cfg = tiny(recompute=True)
    main, startup, _feeds, fetch = lm.lfm2moe_pretrain_program(
        cfg, 2, 32, optimizer_fn=optimizer.Adam(1e-3).minimize)
    scope, exe = Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    feed = _feed(3)

    def step():
        return exe.run(main, feed=feed, fetch_list=[fetch["loss"]],
                       scope=scope)

    obs.disable()
    obs.clear()
    step()
    assert obs.spans(name="moe.load") == []     # obs off: nothing is read
    misses = exe.cache_misses
    obs.enable()
    try:
        for _ in range(3):
            step()
        spans = obs.spans(name="moe.load")
    finally:
        obs.disable()
        obs.clear()
    assert exe.cache_misses == misses       # reading Scope arrays compiles
    assert len(spans) == 3 * 4              # nothing: one a step and layer
    layers_seen = [s["labels"]["layer"] for s in spans[:4]]
    assert layers_seen == ["lfm_layer_%d" % i for i in (1, 2, 3, 4)]
    for s in spans:
        lab = s["labels"]
        assert set(lab) == {"layer", "rows_held", "rows_max", "rows_mean",
                            "rows_in_use", "rows_buffer", "bounded"}
        assert lab["rows_mean"] == pytest.approx(lab["rows_held"] / 4)
        assert lab["rows_max"] >= lab["rows_mean"]
        # 2 x 32 tokens x 2 picks: tiles of 8 rows, 4 held experts
        assert lab["rows_buffer"] == 128 + 4 * 8
        assert lab["rows_held"] <= lab["rows_in_use"] <= lab["rows_buffer"]
        assert lab["rows_in_use"] % 8 == 0
        assert lab["bounded"] == int(2 * lab["rows_in_use"] <= 160)
    # the last step's spans are the state the step left
    for s in spans[-4:]:
        kept = np.asarray(scope.find_var(s["labels"]["layer"]
                                         + "_expert_load"))[4:]
        assert s["labels"]["rows_held"] == int(kept.sum())
        assert s["labels"]["rows_max"] == int(kept.max())
    # a clone keeps the counters' registry (an eval program reports too)
    assert main.clone().step_records == main.step_records
    assert [r[:3] for r in main.step_records] == [
        ("moe.load", "lfm_layer_%d_expert_load" % i,
         {"layer": "lfm_layer_%d" % i}) for i in (1, 2, 3, 4)]


def test_any_layer_may_register_a_counter_and_the_executor_names_none():
    """`Program.record_step_state` is the one hook: a span a step from a
    persistable the step writes, with the labels given and, by default, the
    array as `value`."""
    from paddle_tpu import layers
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [3], dtype="float32", append_batch_size=False)
        kept = layers.create_global_var([3], 0.0, "float32",
                                        persistable=True, name="seen_last")
        layers.assign(x, output=kept)
        total = layers.reduce_sum(x)
    main.record_step_state("test.seen", "seen_last", {"who": "me"})
    main.record_step_state("test.absent", "no_such_var")
    scope, exe = Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    obs.clear()
    obs.enable()
    try:
        exe.run(main, feed={"x": np.asarray([1, 2, 3], np.float32)},
                fetch_list=[total], scope=scope)
        spans = obs.spans(name="test.seen")
        absent = obs.spans(name="test.absent")
    finally:
        obs.disable()
        obs.clear()
    assert [s["labels"] for s in spans] == [{"who": "me",
                                             "value": [1.0, 2.0, 3.0]}]
    assert absent == []


def test_the_bias_update_holds_the_held_experts_load_where_it_drifts():
    """Only the held experts answer, so a router that trains on batches it
    sees again sends them more and more picks; with
    `expert_bias_update_rate` the loss-free balance step holds their share
    near held/routed."""
    def rows_after(rate, steps=100):
        cfg = tiny(recompute=True, experts_held=(2, 2),
                   expert_bias_update_rate=rate)
        main, startup, _f, fetch = lm.lfm2moe_pretrain_program(
            cfg, 8, 32, optimizer_fn=optimizer.Adam(1e-3).minimize)
        scope, exe = Scope(), pt.Executor()
        exe.run(startup, scope=scope)
        rng = np.random.RandomState(0)
        pool = [rng.randint(0, 96, (8, 33)).astype(np.int64)
                for _ in range(4)]
        seen = []
        for i in range(steps):
            toks = pool[i % 4]
            exe.run(main, feed={"token_ids": toks[:, :-1, None],
                                "labels": toks[:, 1:, None],
                                "loss_mask": np.ones((8, 32, 1),
                                                     np.float32)},
                    fetch_list=[fetch["loss"]], scope=scope)
            if i >= steps - 20:
                seen.append(sum(int(np.asarray(scope.find_var(
                    "lfm_layer_%d_expert_load" % j))[2:4].sum())
                    for j in (1, 2, 3, 4)))
        assert exe.cache_misses == 1
        return float(np.mean(seen)) / (4 * 8 * 32 * 2)   # share of the picks

    drifting, held = rows_after(0.0), rows_after(0.01)
    assert drifting > 0.35          # even routing is 2 of 8: 0.25
    assert abs(held - 0.25) < 0.05


def test_the_load_is_written_by_the_forward_pass_alone():
    """The count leaves its `recompute_segment` as a result and is assigned
    outside: no op of a sub-block writes a persistable `*_expert_load`,
    and the bias moves once, in the main block, after the layer."""
    cfg = tiny(recompute=True, expert_bias_update_rate=0.001)
    main, _s, _f, _fetch = lm.lfm2moe_pretrain_program(
        cfg, 2, 32, optimizer_fn=optimizer.Adam(1e-3).minimize)
    writers = [(blk.idx, op.type, op.attrs.get("op_role", "forward"))
               for blk in main.blocks for op in blk.ops
               for n in op.output_names() if n.endswith("_expert_load")]
    assert writers == [(0, "assign", "forward")] * 4
    movers = [(blk.idx, op.type) for blk in main.blocks for op in blk.ops
              for n in op.output_names() if n.endswith("_expert_bias")]
    assert movers == [(0, "moe_bias_update")] * 4
