"""SmallThinker-21BA3B-Instruct through the normal path at a tiny size: the
program trains as one jitted step, the loss, every leaf's first gradient and
three Adam steps follow the plain reference
(`benchmark/families/smallthinker.py:reference_loss`, which imports nothing
of paddle_tpu), the family's parameter list is the program's, five programs
with one mechanism wrong each do NOT follow it, and each expert layer's load
reaches `obs` as a `moe.load` span a step."""
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
from paddle_tpu.models import smallthinker as st

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

LAYOUT = [0, 1, 1, 1]
CONFIG = {      # the benchmark's keys, at a tiny size
    "family": "smallthinker", "precision": "float32", "hidden_size": 64,
    "head_dim": 16, "moe_ffn_hidden_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "vocab_size": 96, "moe_num_primary_experts": 4,
    "num_experts_routed": 8, "experts_held": [4, 4],
    "moe_num_active_primary_experts": 2,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "rope_theta": 1500000, "rope_layout": LAYOUT,
    "sliding_window_layout": LAYOUT, "sliding_window_size": 8,
    "published_layer_index": [0, 1, 2, 3], "initializer_range": 0.02,
    "absent_experts": "folded",
    "optimizer": {"name": "adam", "learning_rate": 1e-3, "beta1": 0.9,
                  "beta2": 0.999, "epsilon": 1e-8}}
TRAFFIC = {"seq_len": 32, "batch_per_chip": 2, "global_batch": 2,
           "tokens_per_step": 64, "reference_block_rows": 1}


def tiny(**kw):
    base = dict(vocab_size=96, hidden_size=64, num_heads=4, num_kv_heads=2,
                head_dim=16, moe_ff_size=32, num_experts=8, top_k=2,
                experts_held=(4, 4), window=8, window_layout=LAYOUT)
    base.update(kw)
    return st.SmallThinkerConfig(**base)


def _feed(seed=0):
    toks = np.random.RandomState(seed).randint(0, 96, (2, 33)).astype(
        np.int64)
    return {"token_ids": toks[:, :-1, None], "labels": toks[:, 1:, None],
            "loss_mask": np.ones((2, 32, 1), np.float32)}


def _family():
    from benchmark import cells
    return cells._load_module(
        os.path.join(REPO, "benchmark", "families", "smallthinker.py"),
        "benchmark_family_smallthinker_for_the_model_test")


def test_the_published_pattern_is_the_default():
    cfg = st.SmallThinkerConfig()
    assert cfg.num_layers == 52
    assert cfg.window_layout == cfg.rope_layout == [0, 1, 1, 1] * 13
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.moe_ff_size, cfg.num_experts, cfg.top_k, cfg.window,
            cfg.rope_theta, cfg.norm_eps, cfg.vocab_size) \
        == (2560, 28, 4, 128, 768, 64, 6, 4096, 1.5e6, 1e-6, 151936)
    assert cfg.experts_held == (0, 64)


@pytest.mark.parametrize("kw,match", [
    (dict(rope_layout=[0, 1]), "one entry a layer"),
    (dict(num_heads=4, num_kv_heads=3), "do not group"),
    (dict(head_dim=15), "odd")])
def test_a_config_that_cannot_run_is_refused_by_name(kw, match):
    with pytest.raises(ValueError, match=match):
        tiny(**kw)


@pytest.mark.parametrize("dtype,recompute", [("float32", True),
                                             ("bfloat16", True),
                                             ("float32", False)])
def test_the_program_trains_as_one_jitted_step(dtype, recompute):
    cfg = tiny(dtype=dtype, recompute=recompute)
    main, startup, feeds, fetch = st.smallthinker_pretrain_program(
        cfg, 2, 32, optimizer_fn=optimizer.Adam(2e-3).minimize)
    assert feeds == ["token_ids", "labels", "loss_mask"]
    ops = [op for blk in main.blocks for op in blk.ops
           if op.attrs.get("op_role", "forward") == "forward"]
    types = [op.type for op in ops]
    assert ([op.type for op in main.global_block().ops].count("remat_block")
            == 4) == recompute
    # rotary on the three window layers alone; no q/k norm anywhere
    for op_type, count in (("moe_route", 4), ("moe_dispatch", 4),
                           ("moe_experts", 4), ("moe_combine", 4),
                           ("rope_qk_norm", 3),
                           ("scaled_dot_product_attention", 4)):
        assert types.count(op_type) == count, op_type
    assert [op.attrs["window"] for op in ops
            if op.type == "scaled_dot_product_attention"] == [None, 8, 8, 8]
    for op in ops:
        if op.type == "rope_qk_norm":
            assert set(op.inputs) == {"Q", "K"}     # no norm scales
        if op.type == "moe_route":
            assert set(op.inputs) == {"X", "W"}     # no expert bias
            assert op.attrs["scoring"] == "softmax"
        if op.type == "moe_experts":
            assert op.attrs["gate"] == "relu"
    # the router reads another tensor than the experts do
    routed = [op.inputs["X"] for op in ops if op.type == "moe_route"]
    fed = [op.inputs["X"] for op in ops if op.type == "moe_dispatch"]
    assert all(r != f for r, f in zip(routed, fed))
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
    assert {"st_layer_0_qkv.w_0", "st_layer_0_router.w_0", "st_lm_head",
            "st_layer_2_experts_gate_up", "st_layer_3_experts_down",
            "st_word_embedding", "st_norm_f_s"} <= names
    assert not {n for n in names if "norm_s" in n and "_q_" in n}
    assert not [n for n in scope.keys() if "expert_bias" in n]
    load = np.asarray(scope.find_var("st_layer_3_expert_load"))
    assert load.shape == (8,) and load.dtype == np.int32
    assert load.sum() == 64 * 2 and 0 < load[4:].sum() <= 64 * 2


def test_param_specs_equal_the_programs_parameters():
    family = _family()
    main, startup, _loss = family.build(CONFIG, TRAFFIC,
                                        optimizer.Adam(1e-3).minimize)
    scope = Scope()
    pt.Executor().run(startup, scope=scope)
    specs = family.param_specs(CONFIG, TRAFFIC)
    assert {p.name for p in main.global_block().all_parameters()} \
        == set(specs)
    for name, (shape, dtype, _kind) in specs.items():
        have = scope.find_var(name)
        assert tuple(have.shape) == tuple(shape), name
        assert str(have.dtype) == dtype, name


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
    """The program in float32 through Executor against `reference_loss` +
    `reference.adam_update` from the same seeded weights on the same
    batches: each loss to 1e-5, every leaf's first gradient (read back
    from Adam's first moment after one step, as the harness reads it) to
    1e-4 of its largest entry, the parameters after three steps to
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


# ---------------------------------------------------------------------------
# one mechanism wrong at a time: the program no longer follows the reference
# ---------------------------------------------------------------------------

def _moe_ffn_with(monkeypatch, **forced):
    real = st.layers.moe_ffn

    def wrong(*args, **kw):
        kw.update(forced)
        return real(*args, **kw)

    monkeypatch.setattr(st.layers, "moe_ffn", wrong)


def _router_reads_x(monkeypatch):
    _moe_ffn_with(monkeypatch, router_input=None)


def _sigmoid_scoring(monkeypatch):
    _moe_ffn_with(monkeypatch, scoring="sigmoid")


def _silu_gate(monkeypatch):
    _moe_ffn_with(monkeypatch, gate="silu")


WRONG = {
    "nothing": (None, {}),
    "the router reads x, not the block's input": (_router_reads_x, {}),
    "sigmoid scoring": (_sigmoid_scoring, {}),
    "a SiLU gate": (_silu_gate, {}),
    "the global layer gets rotary": (None, {"rope_layout": [1, 1, 1, 1]}),
    "a window layer loses rotary": (None, {"rope_layout": [0, 1, 0, 1]}),
    "the window sees one key more": (None, {"sliding_window_size": 9}),
    "the window sees one key fewer": (None, {"sliding_window_size": 7}),
    "absent picks add nothing": (None, {"absent_experts": "nothing"}),
}


_WANT = []      # the reference's loss for the cases below, computed once


def _wide_start_and_batch(family):
    from benchmark import reference, weights
    start = weights.weight_maker(family.param_specs(CONFIG, TRAFFIC), 0.3)(5)
    batch = family.make_batch(CONFIG, TRAFFIC, weights.host_rng(5, 1))
    if not _WANT:
        mm = reference.matmul_at("float32")
        with jax.default_matmul_precision("highest"):
            _WANT.append(float(jax.jit(lambda p, blk: family.reference_loss(
                p, blk, dict(CONFIG), TRAFFIC, mm))(
                    weights.as_float32(start), family.block_of(batch, 0, 2))))
    return start, batch, _WANT[0]


@pytest.mark.parametrize("case", sorted(WRONG))
def test_a_program_with_one_mechanism_wrong_leaves_the_reference(
        case, monkeypatch):
    """Weights of scale 0.3, so that every mechanism moves the loss: the
    forward program as built reads the reference's loss to 1e-5; each wrong
    one is off by more than a hundred times that."""
    family = _family()
    patch, changed = WRONG[case]
    if patch is not None:
        patch(monkeypatch)
    main, startup, loss = family.build(dict(CONFIG, **changed), TRAFFIC,
                                       lambda _loss: None)
    scope, exe = Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    start, batch, want = _wide_start_and_batch(family)
    for name, value in start.items():
        scope.set_var(name, value)
    got = float(exe.run(main, feed=batch, fetch_list=[loss],
                        scope=scope)[0].reshape(-1)[0])
    gap = abs(got - want) / abs(want)
    if case == "nothing":
        assert gap < 1e-5
    else:
        assert gap > 1e-3, (case, gap)


def test_moe_load_spans_appear_with_obs_on_and_cache_misses_stay():
    cfg = tiny(recompute=True)
    main, startup, _feeds, fetch = st.smallthinker_pretrain_program(
        cfg, 2, 32, optimizer_fn=optimizer.Adam(1e-3).minimize)
    scope, exe = Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    feed = _feed(3)
    obs.disable()
    obs.clear()
    exe.run(main, feed=feed, fetch_list=[fetch["loss"]], scope=scope)
    assert obs.spans(name="moe.load") == []     # obs off: nothing is read
    misses = exe.cache_misses
    obs.enable()
    try:
        for _ in range(3):
            exe.run(main, feed=feed, fetch_list=[fetch["loss"]], scope=scope)
        spans = obs.spans(name="moe.load")
    finally:
        obs.disable()
        obs.clear()
    assert exe.cache_misses == misses
    assert [s["labels"]["layer"] for s in spans] \
        == ["st_layer_%d" % i for i in range(4)] * 3
    for s in spans:
        lab = s["labels"]
        assert set(lab) == {"layer", "rows_held", "rows_max", "rows_mean",
                            "rows_in_use", "rows_buffer", "bounded"}
        assert lab["rows_mean"] == pytest.approx(lab["rows_held"] / 4)
        # 2 x 32 tokens x 2 picks: tiles of 8 rows, 4 held experts
        assert lab["rows_buffer"] == 128 + 4 * 8
        assert lab["rows_held"] <= lab["rows_in_use"] <= lab["rows_buffer"]
    for s in spans[-4:]:
        kept = np.asarray(scope.find_var(s["labels"]["layer"]
                                         + "_expert_load"))[4:]
        assert s["labels"]["rows_held"] == int(kept.sum())


def test_the_picks_wait_for_nothing_attention_computes():
    """In a layer's block the router, the plan and the row gather's indices
    depend on the block's input alone: no op between the block's first and
    `moe_dispatch`'s plan inputs reads an attention result."""
    cfg = tiny(recompute=True)
    main, _s, _f, _fetch = st.smallthinker_pretrain_program(cfg, 2, 32)
    block = main.blocks[1]
    made_by = {n: op for op in block.ops for n in op.output_names()}

    def ancestors(name, seen):
        op = made_by.get(name)
        if op is None or id(op) in seen:
            return seen
        seen[id(op)] = op.type
        for n in op.input_names():
            ancestors(n, seen)
        return seen

    route = [op for op in block.ops if op.type == "moe_route"][0]
    before = set(ancestors(route.outputs["TopE"][0], {}).values())
    assert "scaled_dot_product_attention" not in before
    assert before <= {"moe_route", "reshape2"}


def test_the_window_scope_is_metadata_only():
    """A windowed `scaled_dot_product_attention` lowers to the very
    operations a direct `flash_attention` call with that window gives
    (Phi's, LFM2's and Kimi's steps keep their operations and kernel
    names); only the operations' names gain the `window_attention` scope,
    and a call without a window gains nothing."""
    from paddle_tpu.ops import attention_ops
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.registry import get_op
    q = jnp.zeros((1, 4, 512, 16), jnp.float32)
    kv = jnp.zeros((1, 2, 512, 16), jnp.float32)

    def lowered(fn):
        low = jax.jit(jax.grad(lambda q_, k_, v_: jnp.sum(fn(q_, k_, v_)),
                               (0, 1, 2))).lower(q, kv, kv)
        return low.as_text(), low.as_text(debug_info=True)

    def through_the_op(window):
        return lambda q_, k_, v_: get_op("scaled_dot_product_attention").fn(
            None, {"Q": [q_], "K": [k_], "V": [v_]},
            {"scale": 0.25, "causal": True, "window": window})["Out"]

    def direct(window):
        return lambda q_, k_, v_: flash_attention(
            q_, k_, v_, scale=0.25, causal=True, window=window)

    for window in (128, None):
        op_text, op_names = lowered(through_the_op(window))
        text, names = lowered(direct(window))
        assert op_text == text
        # a window keeps the split pair; the group alone runs `flash_bwd`
        assert "flash_fwd" in op_names
        assert ("flash_bwd_dkv" in op_names) == (window is not None)
        assert attention_ops.WINDOW_SCOPE not in names
        assert (attention_ops.WINDOW_SCOPE in op_names) == (window is not None)
