"""Kimi-VL-A3B-Instruct's language model through the normal path at a tiny
size: the `partial_rope` op against a complex-number rotation (three
layouts, forward and pullback, float32 and bfloat16 in), `mla_attention`
without `rope_theta` op for op what it was, the program trains as one jitted
step, its loss, every leaf's first gradient and three Adam steps follow the
plain reference (`benchmark/families/kimivl.py`, which imports nothing of
paddle_tpu), positions are live, the eight `experts_held` shares of an
expert layer add up to the uncut 64-expert reference with the shared expert
counted once, and folded picks lay out tokens x top_k rows whatever the
router does."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, optimizer
from paddle_tpu.framework.scope import Scope
from paddle_tpu.models import kimi_vl as kv
from paddle_tpu.models import moe_decoder
from paddle_tpu.ops import attention_ops
from paddle_tpu.ops.registry import get_op

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CONFIG = {      # the benchmark's keys, at a tiny size
    "family": "kimivl", "precision": "float32", "hidden_size": 64,
    "num_attention_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "kv_lora_rank": 32, "q_lora_rank": None,
    "rope_theta": 800000, "rope_scaling": None, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 4,
    "num_experts_routed": 8, "experts_held": [4, 4],
    "absent_experts": "folded", "num_experts_per_tok": 2,
    "n_shared_experts": 2, "first_k_dense_replace": 1,
    "num_hidden_layers": 3, "published_layer_index": [0, 1, 2],
    "norm_topk_prob": True, "routed_scaling_factor": 2.446,
    "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
    "rms_norm_eps": 1e-5, "vocab_size": 64, "initializer_range": 0.02,
    "optimizer": {"name": "adam", "learning_rate": 1e-3, "beta1": 0.9,
                  "beta2": 0.999, "epsilon": 1e-8}}
TRAFFIC = {"seq_len": 32, "batch_per_chip": 2, "global_batch": 2,
           "tokens_per_step": 64, "reference_block_rows": 1}


def tiny(**kw):
    base = dict(vocab_size=64, hidden_size=64, num_heads=4, qk_nope_dim=16,
                qk_rope_dim=8, v_dim=16, kv_rank=32, ff_size=128,
                moe_ff_size=32, num_experts=8, top_k=2, num_shared_experts=2,
                num_layers=3, experts_held=(4, 4), absent_picks="folded")
    base.update(kw)
    return kv.KimiVLConfig(**base)


def _feed(seed=0, t=32):
    toks = np.random.RandomState(seed).randint(0, 64, (2, t + 1)).astype(
        np.int64)
    return {"token_ids": toks[:, :-1, None], "labels": toks[:, 1:, None],
            "loss_mask": np.ones((2, t, 1), np.float32)}


def _family():
    from benchmark import cells
    return cells._load_module(
        os.path.join(REPO, "benchmark", "families", "kimivl.py"),
        "benchmark_family_kimivl_for_the_model_test")


# ---------------------------------------------------------------------------
# the op
# ---------------------------------------------------------------------------

B, T, THETA = 2, 24, 800000.0
#: (heads, nope, rope): a suffix of every head; a whole head (nothing
#: handed through); a part wider than what is handed through
LAYOUTS = [(3, 8, 8), (2, 0, 16), (4, 4, 12)]


def _complex_turn(x):
    """x (..., T, rope) float64: every neighbouring pair (2i, 2i + 1) as
    one complex number times exp(i t theta^(-2i/rope))."""
    rope = x.shape[-1]
    i = np.arange(rope // 2)
    z = np.exp(1j * np.arange(T)[:, None] * THETA ** (-2.0 * i / rope))
    out = np.empty_like(x)
    c = (x[..., 0::2] + 1j * x[..., 1::2]) * z
    out[..., 0::2], out[..., 1::2] = c.real, c.imag
    return out


def _want(q, k_pe, layout):
    h, nope, rope = layout
    qh = q.astype(np.float64).reshape(B, T, h, nope + rope)
    turned = _complex_turn(qh[..., nope:].transpose(0, 2, 1, 3)
                           ).transpose(0, 2, 1, 3)
    return (np.concatenate([qh[..., :nope], turned], -1).reshape(q.shape),
            _complex_turn(k_pe.astype(np.float64)))


def _op(q, k_pe, layout):
    out = get_op("partial_rope").fn(
        None, {"Q": [q], "KPe": [k_pe]},
        {"nope_dim": layout[1], "rope_dim": layout[2], "theta": THETA})
    return out["QOut"], out["KPeOut"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_op_is_a_complex_rotation_forward_and_pullback(layout, dtype):
    """Forward: each head's last `rope` numbers and the shared key part
    equal the neighbouring pairs read as complex numbers times
    exp(i t theta^(-2i/R)), the first `nope` numbers come through
    untouched. Pullback (`jax.vjp`, as the trace takes every op's): a
    rotation's transpose is the rotation back, so the cotangent of y = R x
    is R^-1 dy: the complex product with the conjugate. float32 to 1e-5
    (cos and sin of angles up to 23 in float32); bfloat16 in gives bfloat16
    out, one rounding of the float32 result (the pullback rounds its two
    terms apart and their sum: three)."""
    h, nope, rope = layout
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((B, T, h * (nope + rope))), dtype)
    k_pe = jnp.asarray(rng.standard_normal((B, T, rope)), dtype)
    (q_out, k_out), pull = jax.vjp(lambda a, b: _op(a, b, layout), q, k_pe)
    assert q_out.dtype == q.dtype and q_out.shape == q.shape
    assert k_out.dtype == k_pe.dtype and k_out.shape == k_pe.shape
    tol = dict(rtol=1e-5, atol=1e-5) if dtype == "float32" \
        else dict(rtol=8e-3, atol=8e-3)
    want_q, want_k = _want(np.asarray(q, np.float32),
                           np.asarray(k_pe, np.float32), layout)
    np.testing.assert_allclose(np.asarray(q_out, np.float32), want_q, **tol)
    np.testing.assert_allclose(np.asarray(k_out, np.float32), want_k, **tol)
    handed = np.asarray(q_out, np.float32).reshape(B, T, h, -1)[..., :nope]
    np.testing.assert_array_equal(
        handed, np.asarray(q, np.float32).reshape(B, T, h, -1)[..., :nope])
    # the pullback turns the cotangent back: turning it forward again
    # returns it
    dq = jnp.asarray(rng.standard_normal(q.shape), dtype)
    dk = jnp.asarray(rng.standard_normal(k_pe.shape), dtype)
    back_q, back_k = pull((dq, dk))
    assert back_q.dtype == q.dtype and back_k.dtype == k_pe.dtype
    again_q, again_k = _want(np.asarray(back_q, np.float32),
                             np.asarray(back_k, np.float32), layout)
    loose = dict(rtol=2e-5, atol=2e-5) if dtype == "float32" \
        else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(again_q, np.asarray(dq, np.float32), **loose)
    np.testing.assert_allclose(again_k, np.asarray(dk, np.float32), **loose)
    # a rotation keeps every pair's length
    np.testing.assert_allclose(
        np.sum(np.square(want_k), -1),
        np.sum(np.square(np.asarray(k_pe, np.float64)), -1), rtol=1e-6)


def test_over_a_whole_head_it_is_rotate_half_of_the_reordered_numbers():
    """The published code reorders a part to (0, 2, 4, .., 1, 3, 5, ..) and
    turns half-split pairs (i, i + D/2), which is what `rotate_half` (the
    whole-head op's rotation) turns: the same rotation of the same
    numbers."""
    x = jnp.asarray(np.random.default_rng(5).standard_normal((2, 3, 16, 8)),
                    jnp.float32)
    order = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
    inter = np.asarray(attention_ops.rotate_part(x, 0, 8, 1e4))
    half = np.asarray(attention_ops.rotate_half(x[..., order], 1e4))
    np.testing.assert_allclose(inter[..., order], half, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("attrs,k_width,match", [
    (dict(rope_dim=7), 7, "pair numbers"), (dict(nope_dim=9), 8,
                                            "no multiple"),
    (dict(), 12, "no multiple")])
def test_the_op_refuses_what_it_cannot_turn(attrs, k_width, match):
    q, k_pe = jnp.zeros((1, 4, 32)), jnp.zeros((1, 4, k_width))
    base = {"nope_dim": 8, "rope_dim": 8, "theta": 1e4}
    with pytest.raises(ValueError, match=match):
        get_op("partial_rope").fn(None, {"Q": [q], "KPe": [k_pe]},
                                  dict(base, **attrs))


def test_the_shape_rule_hands_both_shapes_through():
    from paddle_tpu.ops.registry import get_shape_rule
    from paddle_tpu.ops.shape_rules import TensorMeta
    out = get_shape_rule("partial_rope")(
        None, {"Q": [TensorMeta((None, 32, 96), "bfloat16")],
               "KPe": [TensorMeta((None, 32, 8), "bfloat16")]},
        {"nope_dim": 16, "rope_dim": 8})
    assert (out["QOut"][0].shape, out["QOut"][0].dtype) \
        == ((None, 32, 96), "bfloat16")
    assert (out["KPeOut"][0].shape, out["KPeOut"][0].dtype) \
        == ((None, 32, 8), "bfloat16")


def test_the_partners_come_off_one_lane_aligned_run_of_heads():
    """At the cell's widths (16 heads of 128 + 64) the op splits q's 3072
    numbers into runs of two heads, 384 = three 128-lane tiles, and the
    key part's 64 stay one run: one (384, 384) and one (64, 64) signed
    permutation, each column with at most one entry, +-1, and none over the
    part that is not turned."""
    q = jnp.zeros((1, 8, 16 * 192), jnp.bfloat16)
    k_pe = jnp.zeros((1, 8, 64), jnp.bfloat16)
    text = jax.make_jaxpr(lambda a, b: _cell_op(a, b))(q, k_pe)
    dots = [e for e in text.jaxpr.eqns if e.primitive.name == "dot_general"]
    assert sorted(e.invars[1].aval.shape for e in dots) \
        == [(64, 64), (384, 384)]
    assert all(e.outvars[0].aval.dtype == jnp.float32 for e in dots)
    consts = {id(v): c for v, c in zip(text.jaxpr.constvars, text.consts)}
    for e in dots:
        swap = np.asarray(consts[id(e.invars[1])], np.float32) \
            if id(e.invars[1]) in consts else None
        if swap is None:
            continue
        assert set(np.unique(swap)) <= {-1.0, 0.0, 1.0}
        assert (np.abs(swap).sum(0) <= 1).all()


def _cell_op(q, k_pe):
    out = get_op("partial_rope").fn(
        None, {"Q": [q], "KPe": [k_pe]},
        {"nope_dim": 128, "rope_dim": 64, "theta": 800000.0})
    return out["QOut"], out["KPeOut"]


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

#: what `mla_attention` built before it learnt `rope_theta` (the parent's
#: op types, in order), and what it builds without it still
MLA_OPS = ["mul", "reshape2", "transpose2", "mul", "split", "rms_norm",
           "mul", "reshape2", "split", "unsqueeze2", "expand", "concat",
           "transpose2", "transpose2", "scaled_dot_product_attention",
           "transpose2", "reshape2", "mul"]


def _mla_program(**kw):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        u = layers.data("u", [2, 32, 64], dtype="float32",
                        append_batch_size=False)
        out = layers.mla_attention(u, 4, 16, 8, 16, 32, name="mla", **kw)
    return main, startup, out


def test_mla_attention_without_rope_theta_is_op_for_op_what_it_was():
    main, _startup, _out = _mla_program()
    assert [op.type for op in main.global_block().ops] == MLA_OPS
    turned, _s, _o = _mla_program(rope_theta=800000.0)
    types = [op.type for op in turned.global_block().ops]
    assert types.count("partial_rope") == 1
    # between the projections and the key's concat, ahead of every layout
    # change of q and of the key part's broadcast
    at = types.index("partial_rope")
    assert types[:at] == ["mul", "mul", "split"]
    assert sorted(types) == sorted(MLA_OPS + ["partial_rope"])
    op = turned.global_block().ops[at]
    assert op.attrs["nope_dim"] == 16 and op.attrs["rope_dim"] == 8
    assert op.attrs["theta"] == 800000.0
    made = {n: o.type for o in turned.global_block().ops
            for n in o.output_names()}
    expand = [o for o in turned.global_block().ops if o.type == "expand"][0]
    unsq = [o for o in turned.global_block().ops
            if o.type == "unsqueeze2"][0]
    assert made[unsq.inputs["X"][0]] == "partial_rope"   # turned ONCE,
    assert made[expand.inputs["X"][0]] == "unsqueeze2"   # then broadcast


def _run_mla(x, weights=None, **kw):
    main, startup, out = _mla_program(**kw)
    scope, exe = Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    names = sorted(p.name for p in main.global_block().all_parameters())
    if weights is None:
        rng = np.random.default_rng(11)
        weights = {n: (1.0 if n.endswith("_s") else 0.0) + 0.3
                   * rng.standard_normal(scope.find_var(n).shape)
                   .astype(np.float32) for n in names}
    for n in names:
        scope.set_var(n, jnp.asarray(weights[n]))
    return np.asarray(exe.run(main, feed={"u": x}, fetch_list=[out],
                              scope=scope)[0]), weights


def test_positions_are_live_and_the_no_position_form_still_forgets_order():
    """Causal attention without positions reads a query's prefix as a SET:
    swapping two earlier tokens leaves a later position's output as it was.
    With the rotary part turned the same swap moves it; and the layer is the
    family's reference layer either way."""
    from benchmark import reference
    family = _family()
    x = np.random.default_rng(2).standard_normal((2, 32, 64)).astype(
        np.float32)
    swapped = x.copy()
    swapped[:, [0, 5]] = x[:, [5, 0]]
    plain, weights = _run_mla(x)
    plain_swapped, _w = _run_mla(swapped, weights)
    np.testing.assert_allclose(plain_swapped[:, 6:], plain[:, 6:],
                               rtol=1e-4, atol=1e-5)
    turned, _w = _run_mla(x, weights, rope_theta=800000.0)
    turned_swapped, _w = _run_mla(swapped, weights, rope_theta=800000.0)
    assert np.max(np.abs(turned_swapped[:, 6:] - turned[:, 6:])) > 1e-2
    assert np.max(np.abs(turned - plain)) > 1e-2
    s = {"nope": 16, "rope": 8, "dv": 16, "kv_rank": 32, "theta": 800000,
         "eps": 1e-5}
    w = weights     # the layer's names are the family's suffixes
    mm = reference.matmul_at("float32")
    with jax.default_matmul_precision("highest"):
        for got, positions in ((turned, True), (plain, False)):
            want = np.asarray(family.mla(jnp.asarray(x), w, s, mm,
                                         positions=positions))
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def test_the_published_sizes_are_the_default_and_are_read_from_the_keys():
    cfg = kv.KimiVLConfig()
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads, cfg.qk_nope_dim,
            cfg.qk_rope_dim, cfg.v_dim, cfg.kv_rank, cfg.rope_theta,
            cfg.ff_size, cfg.moe_ff_size, cfg.num_experts, cfg.top_k,
            cfg.num_shared_experts, cfg.first_k_dense, cfg.vocab_size,
            cfg.routed_scaling_factor, cfg.norm_eps) \
        == (27, 2048, 16, 128, 64, 128, 512, 800000.0, 11264, 1408, 64, 6,
            2, 1, 163840, 2.446, 1e-5)
    assert cfg.experts_held == (0, 64) and cfg.absent_picks == "nothing"
    assert [cfg.is_dense(i) for i in range(3)] == [True, False, False]
    got = kv.KimiVLConfig.from_published(CONFIG, dtype="bfloat16")
    assert (got.num_layers, got.num_experts, got.experts_held, got.top_k,
            got.absent_picks, got.rope_theta, got.dtype) \
        == (3, 8, (4, 4), 2, "folded", 800000.0, "bfloat16")


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("scoring_func", "softmax"), ("n_group", 8)])
def test_a_published_key_the_program_does_not_build_is_refused(key, value):
    with pytest.raises(ValueError, match=key):
        kv.KimiVLConfig.from_published(dict(CONFIG, **{key: value}))


@pytest.mark.parametrize("dtype,recompute", [("float32", True),
                                             ("bfloat16", True),
                                             ("float32", False)])
def test_the_program_trains_as_one_jitted_step(dtype, recompute):
    cfg = tiny(dtype=dtype, recompute=recompute)
    main, startup, feeds, fetch = kv.kimi_vl_pretrain_program(
        cfg, 2, 32, optimizer_fn=optimizer.Adam(2e-3).minimize)
    assert feeds == ["token_ids", "labels", "loss_mask"]
    ops = [op for blk in main.blocks for op in blk.ops
           if op.attrs.get("op_role", "forward") == "forward"]
    types = [op.type for op in ops]
    assert ([op.type for op in main.global_block().ops].count("remat_block")
            == 3) == recompute
    # every layer turns its rotary part; one dense layer, two expert layers
    for op_type, count in (("partial_rope", 3), ("moe_route", 2),
                           ("scaled_dot_product_attention", 3),
                           ("moe_experts", 2), ("silu", 1 + 2)):
        assert types.count(op_type) == count, op_type
    for op in ops:
        if op.type == "moe_route":
            assert set(op.inputs) == {"X", "W", "Bias"}
            assert op.attrs["fold_onto"] == [4, 4]
            assert op.attrs["routed_scaling_factor"] == 2.446
    scope, exe = Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    misses = exe.cache_misses
    feed = _feed()
    got = [exe.run(main, feed=feed, scope=scope,
                   fetch_list=[fetch["loss"]] + fetch["expert_load"])
           for _ in range(25)]
    losses = [float(g[0].reshape(-1)[0]) for g in got]
    assert exe.cache_misses == misses + 1       # one compiled step
    assert losses[0] == pytest.approx(np.log(64), rel=0.05)
    assert losses[-1] < 0.75 * losses[0]
    # folded: every pick lands on a held expert, whatever the router does
    assert len(fetch["expert_load"]) == 2
    for load in got[-1][1:]:
        load = np.asarray(load)
        assert load.shape == (8,) and load[:4].sum() == 0
        assert load[4:].sum() == 64 * 2
    names = {p.name for p in main.global_block().all_parameters()}
    assert {"kvl_layer_0_mla_q.w_0", "kvl_layer_0_mlp_gate_up.w_0",
            "kvl_layer_1_router.w_0", "kvl_layer_2_experts_down",
            "kvl_layer_1_shared_gate_up.w_0", "kvl_lm_head",
            "kvl_word_embedding", "kvl_norm_f_s"} <= names
    assert tuple(scope.find_var("kvl_layer_1_shared_gate_up.w_0").shape) \
        == (64, 2 * 2 * 32)     # two shared experts side by side


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
    batches: each loss to 1e-5 (float32 sums in another order), every
    leaf's first gradient (read back from Adam's first moment after one
    step, as the harness reads it) to 1e-4 of its largest entry, the
    parameters after three steps to 2% of what they moved (Adam divides by
    sqrt(v) + 1e-8: where a gradient entry is ~1e-8 the step's size
    follows rounding)."""
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


def _forward_loss(start, batch, config=CONFIG, **cfg_kw):
    """The forward program's loss from the weights `start` (copied in: a
    step donates what it holds)."""
    cfg = kv.KimiVLConfig.from_published(config, **cfg_kw)
    main, startup, _feeds, fetch = kv.kimi_vl_pretrain_program(cfg, 2, 32)
    scope, exe = Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    for name, value in start.items():
        scope.set_var(name, jnp.array(value))
    return float(exe.run(main, feed=batch, fetch_list=[fetch["loss"]],
                         scope=scope)[0].reshape(-1)[0])


@pytest.mark.parametrize("case,cfg_kw,changed", [
    ("as built", {}, {}),
    ("nothing is turned", {"rope_theta": None}, {}),
    ("weights stored by half-split pairs", {}, {"rope": "half-split"}),
    ("another base", {"rope_theta": 10000.0}, {}),
    ("absent picks add nothing", {"absent_picks": "nothing"}, {}),
    ("one shared expert", {"num_shared_experts": 1},
     {"shared": "halved"}),
    ("no scaling of the picks", {"routed_scaling_factor": 1.0}, {})])
def test_a_program_with_one_mechanism_wrong_leaves_the_reference(
        case, cfg_kw, changed):
    """Weights of scale 0.3, so that every mechanism moves the loss: the
    forward program as built reads the reference's loss to 1e-5; each wrong
    one is off by more than a hundred times that."""
    from benchmark import reference, weights
    family = _family()
    start = weights.weight_maker(family.param_specs(CONFIG, TRAFFIC), 0.3)(5)
    batch = family.make_batch(CONFIG, TRAFFIC, weights.host_rng(5, 1))
    mm = reference.matmul_at("float32")
    with jax.default_matmul_precision("highest"):
        want = float(jax.jit(lambda p, blk: family.reference_loss(
            p, blk, dict(CONFIG), TRAFFIC, mm))(
                weights.as_float32(start), family.block_of(batch, 0, 2)))
    if changed.get("rope"):     # each rotary part's columns (0, 2, .., 1, 3, ..)
        start = dict(start)
        order = np.concatenate([np.arange(0, 8, 2), np.arange(1, 8, 2)])
        for i in range(3):
            w_q = np.asarray(start["kvl_layer_%d_mla_q.w_0" % i]).reshape(
                64, 4, 24)
            w_q = np.concatenate([w_q[..., :16], w_q[..., 16 + order]], -1)
            start["kvl_layer_%d_mla_q.w_0" % i] = jnp.asarray(
                w_q.reshape(64, 96))
            w_kva = np.asarray(start["kvl_layer_%d_mla_kv_a.w_0" % i])
            start["kvl_layer_%d_mla_kv_a.w_0" % i] = jnp.asarray(
                np.concatenate([w_kva[:, :32], w_kva[:, 32 + order]], -1))
    if changed.get("shared"):   # a shared expert of half the width: its
        start = dict(start)     # first half
        for i in (1, 2):
            gu = np.asarray(start["kvl_layer_%d_shared_gate_up.w_0" % i])
            start["kvl_layer_%d_shared_gate_up.w_0" % i] = jnp.asarray(
                np.concatenate([gu[:, :32], gu[:, 64:96]], axis=1))
            down = start["kvl_layer_%d_shared_down.w_0" % i]
            start["kvl_layer_%d_shared_down.w_0" % i] = down[:32]
    got = _forward_loss(start, batch, **cfg_kw)
    gap = abs(got - want) / abs(want)
    if case == "as built":
        assert gap < 1e-5
    else:
        assert gap > 1e-3, (case, gap)


def test_moving_the_first_token_changes_a_later_positions_loss():
    """One layer, the loss read at the last position alone, tokens 0 and 5
    swapped: without positions that position's prefix is the same set and
    the loss stands; with the rotary part turned it moves."""
    from benchmark import weights
    family = _family()
    config = dict(CONFIG, num_hidden_layers=1, published_layer_index=[0])
    start = {k: np.asarray(v) for k, v in weights.weight_maker(
        family.param_specs(config, TRAFFIC), 0.3)(9).items()}
    batch = family.make_batch(config, TRAFFIC, weights.host_rng(9, 1))
    batch["loss_mask"] = np.zeros_like(batch["loss_mask"])
    batch["loss_mask"][:, -1] = 1.0
    moved = dict(batch, token_ids=batch["token_ids"].copy())
    moved["token_ids"][:, [0, 5]] = batch["token_ids"][:, [5, 0]]
    assert (moved["token_ids"] != batch["token_ids"]).any()

    def loss(feed, **kw):
        return _forward_loss(start, feed, config=config, **kw)

    assert loss(moved, rope_theta=None) == pytest.approx(
        loss(batch, rope_theta=None), rel=1e-5)
    assert abs(loss(moved) - loss(batch)) > 1e-3 * abs(loss(batch))


# ---------------------------------------------------------------------------
# the share test
# ---------------------------------------------------------------------------

SIZES = {"d": 64, "moe_ff": 32, "routed": 64, "top_k": 6, "shared": 2,
         "eps": 1e-5, "norm_topk": True, "scaling": 2.446, "held": (0, 64),
         "absent": "nothing"}


def _expert_weights(seed=3):
    rng = np.random.default_rng(seed)

    def w(*shape, scale=0.2):
        return (scale * rng.standard_normal(shape)).astype(np.float32)

    return {"router.w_0": w(64, 64, scale=1.0),
            "experts_gate_up": w(64, 64, 64), "experts_down": w(64, 32, 64),
            "shared_gate_up.w_0": w(64, 128), "shared_down.w_0": w(64, 64)}


def _program_share(weights, experts_held, x, absent="nothing"):
    """One forward Program: the model's expert block (`moe_decoder.expert_ffn`:
    router + `moe_ffn` + the shared expert) for the given share, through
    `Executor`; (out, load)."""
    cfg = tiny(num_experts=64, top_k=6, experts_held=experts_held,
               absent_picks=absent)
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


def test_the_eight_shares_add_up_to_the_uncut_64_expert_reference():
    """Eight ranks of 8 experts, a pick on an absent expert adding nothing:
    their parts, with the shared expert (which every rank computes alike)
    counted ONCE, are what the reference gives for the uncut layer."""
    from benchmark import reference
    family = _family()
    mm = reference.matmul_at("float32")
    whole = _expert_weights()
    x = np.random.default_rng(8).standard_normal((2, 16, 64)).astype(
        np.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(family.ffn_part(x, whole, SIZES, mm))
        shared = np.asarray(family._gated_mlp(
            x, whole["shared_gate_up.w_0"], whole["shared_down.w_0"], mm))
    parts = [_program_share(whole, (first, 8), x)
             for first in range(0, 64, 8)]
    loads = np.stack([load for _out, load in parts])
    assert (loads == loads[0]).all()        # every rank counts all 64
    assert loads[0].sum() == 32 * 6
    for out, _load in parts:    # every share gives something of its own
        assert float(np.max(np.abs(out - shared))) > 1e-3
    total = sum(out for out, _load in parts) - 7 * shared
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)
    assert float(np.max(np.abs(shared))) > 1e-3
    # and the reference's own shares add up as well
    with jax.default_matmul_precision("highest"):
        ref_parts = [np.asarray(family.ffn_part(
            x, dict(whole,
                    experts_gate_up=whole["experts_gate_up"][f:f + 8],
                    experts_down=whole["experts_down"][f:f + 8]),
            SIZES, mm, held=(f, 8), shared=f == 0))
            for f in range(0, 64, 8)]
    np.testing.assert_allclose(sum(ref_parts), want, rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("router", ["seeded", "all on absent experts",
                                    "all on held experts"])
def test_folded_picks_lay_out_tokens_times_six_rows_whatever_the_router_does(
        router):
    from benchmark import reference
    family = _family()
    mm = reference.matmul_at("float32")
    whole = _expert_weights(seed=4)
    x = np.random.default_rng(9).standard_normal((2, 16, 64)).astype(
        np.float32)
    if router != "seeded":      # a router that ignores the token
        pushed = np.zeros((64, 64), np.float32)
        at = slice(40, 46) if "absent" in router else slice(8, 14)
        whole["router.w_0"] = pushed
        x[..., 0] = 4.0
        pushed[0, at] = 2.0
    out, load = _program_share(whole, (8, 8), x, absent="folded")
    assert load.shape == (64,)
    assert load[8:16].sum() == 32 * 6 and load.sum() == 32 * 6
    if router != "seeded":      # 40..45 fold onto 8..13 (e mod 8)
        assert load[8:14].tolist() == [32] * 6
    with jax.default_matmul_precision("highest"):
        want = np.asarray(family.ffn_part(
            x, dict(whole, experts_gate_up=whole["experts_gate_up"][8:16],
                    experts_down=whole["experts_down"][8:16]),
            dict(SIZES, absent="folded"), mm, held=(8, 8)))
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
