"""Kimi-Linear through the normal path at a tiny size: the configuration
reads the published keys, the program trains as one jitted step (KDA and
MLA mixers, a dense layer, expert layers with a shared expert), its
lowerings say what they planned (`kda.plan`, `flash.plan`), each expert
layer's load reaches `obs`, and the SHARE test: the parts that the two
halves of the heads and the two halves of the experts give through
`layers.kda_attention`, `layers.mla_attention` and `layers.moe_ffn`, with
the shared expert counted once, add up to what the plain reference
(`benchmark/families/kimilinear.py`) gives for the uncut layer."""
import json
import os
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, optimizer
from paddle_tpu.framework import obs
from paddle_tpu.framework.scope import Scope
from paddle_tpu.models import kimi_linear as km

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

KINDS = ["kda", "mla", "kda"]
PUBLISHED = [1, 4, 5]


def tiny(**kw):
    base = dict(vocab_size=96, hidden_size=64, num_heads=4, kda_head_dim=16,
                gate_rank=8, qk_nope_dim=16, qk_rope_dim=8, v_dim=16,
                kv_rank=24, ff_size=128, moe_ff_size=32, num_experts=16,
                top_k=2, experts_held=(8, 8), heads_held=(2, 2),
                layer_kinds=KINDS, published_layer_index=PUBLISHED)
    base.update(kw)
    return km.KimiLinearConfig(**base)


def _feed(seed=0, t=64):
    toks = np.random.RandomState(seed).randint(0, 96, (2, t + 1)).astype(
        np.int64)
    return {"token_ids": toks[:, :-1, None], "labels": toks[:, 1:, None],
            "loss_mask": np.ones((2, t, 1), np.float32)}


def test_the_published_pattern_is_the_default():
    cfg = km.KimiLinearConfig()
    assert cfg.num_layers == 27
    assert [i + 1 for i, k in enumerate(cfg.layer_kinds) if k == "mla"] \
        == [4, 8, 12, 16, 20, 24, 27]
    assert [cfg.is_dense(i) for i in range(3)] == [True, False, False]
    assert (cfg.hidden_size, cfg.ff_size, cfg.moe_ff_size, cfg.num_experts,
            cfg.top_k, cfg.conv_width, cfg.kda_head_dim, cfg.gate_rank,
            cfg.kv_rank, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_dim) \
        == (2304, 9216, 1024, 256, 8, 4, 128, 128, 512, 128, 64, 128)
    assert cfg.experts_held == (0, 256) and cfg.heads_held == (0, 32)


def test_the_configuration_reads_the_published_keys_and_the_share():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "kimi-linear-48b-a3b.json")) as f:
        held = json.load(f)
    cfg = km.KimiLinearConfig.from_published(held, dtype="bfloat16",
                                             recompute=True)
    assert cfg.layer_kinds == ["kda", "kda", "kda", "mla", "kda"]
    assert [cfg.is_dense(i) for i in range(5)] == [True] + [False] * 4
    assert (cfg.num_heads, cfg.heads_held, cfg.num_experts,
            cfg.experts_held, cfg.vocab_size) \
        == (32, (0, 16), 256, (0, 8), 20480)
    assert cfg.routed_scaling_factor == 2.446 and cfg.norm_topk_prob
    # the published file alone: every layer, every head, every expert
    published = dict(held, **held["published"])
    for key in ("experts_held", "heads_held", "layer_kinds",
                "published_layer_index", "num_experts_routed"):
        del published[key]
    whole = km.KimiLinearConfig.from_published(published)
    assert whole.layer_kinds == km.KimiLinearConfig().layer_kinds
    assert whole.heads_held == (0, 32) and whole.experts_held == (0, 256)


@pytest.mark.parametrize("kw,match", [
    (dict(layer_kinds=["kda", "conv"]), "unknown layer kinds"),
    (dict(published_layer_index=[1, 2]), "one entry a layer")])
def test_a_config_that_cannot_run_is_refused_by_name(kw, match):
    with pytest.raises(ValueError, match=match):
        tiny(**kw)


@pytest.mark.parametrize("layer,kw", [
    (layers.kda_attention, dict(num_heads=4, head_dim=16)),
    (layers.mla_attention, dict(num_heads=4, qk_nope_dim=16, qk_rope_dim=8,
                                v_dim=16, kv_rank=24))])
def test_heads_held_outside_the_heads_is_refused(layer, kw):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [2, 64, 32], dtype="float32",
                        append_batch_size=False)
        with pytest.raises(ValueError, match="is no range of 4 heads"):
            layer(x, heads_held=(3, 2), **kw)


def test_the_program_trains_as_one_jitted_step_and_says_what_it_planned():
    cfg = tiny(dtype="bfloat16", recompute=True)
    main, startup, feeds, fetch = km.kimi_linear_pretrain_program(
        cfg, 2, 64, optimizer_fn=optimizer.Adam(2e-3).minimize)
    assert feeds == ["token_ids", "labels", "loss_mask"]
    types = [op.type for blk in main.blocks for op in blk.ops]
    assert [op.type for op in main.global_block().ops].count(
        "remat_block") == 3
    for op_type, count in (("kda_attention", 2), ("kda_gate", 2),
                           ("kda_out_norm", 2), ("head_l2_norm", 4),
                           ("causal_conv1d", 2), ("moe_route", 2),
                           ("moe_combine", 2),
                           ("scaled_dot_product_attention", 1)):
        assert types.count(op_type) == count, op_type
    scope, exe = Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    misses = exe.cache_misses
    feed = _feed()
    obs.clear()
    obs.enable()
    try:
        losses = [float(exe.run(main, feed=feed, fetch_list=[fetch["loss"]],
                                scope=scope)[0].reshape(-1)[0])
                  for _ in range(25)]
        plans = obs.spans(name="kda.plan")
        loads = obs.spans(name="moe.load")
    finally:
        obs.disable()
        obs.clear()
    assert exe.cache_misses == misses + 1       # one compiled step
    assert losses[0] == pytest.approx(np.log(96), rel=0.05)
    assert losses[-1] < 0.8 * losses[0]
    # a lowering records its plan: the forward, the replay and the pullback
    # of each of the two KDA layers trace the op
    assert plans and all(
        (p["labels"]["chunk"], p["labels"]["sub_block"], p["labels"]["seq"],
         p["labels"]["heads"], p["labels"]["d_k"]) == (64, 16, 64, 2, 16)
        for p in plans)
    assert len(loads) == 25 * 2
    assert {s["labels"]["layer"] for s in loads} \
        == {"kimi_layer_1", "kimi_layer_2"}
    names = {p.name for p in main.global_block().all_parameters()}
    assert {"kimi_word_embedding", "kimi_lm_head", "kimi_norm_f_s",
            "kimi_layer_0_kda_qkv.w_0", "kimi_layer_0_kda_A_log",
            "kimi_layer_0_mlp_gate_up.w_0", "kimi_layer_1_mla_kv_b.w_0",
            "kimi_layer_1_mla_kv_a_norm_s", "kimi_layer_1_router.w_0",
            "kimi_layer_1_shared_gate_up.w_0", "kimi_layer_2_experts_down",
            "kimi_layer_2_kda_dt_bias"} <= names
    # the dense layer has no router or shared expert, an expert layer no
    # dense MLP; the head is its own matrix
    assert "kimi_layer_0_router.w_0" not in names
    assert "kimi_layer_0_shared_down.w_0" not in names
    assert "kimi_layer_1_mlp_down.w_0" not in names
    held = scope.find_var("kimi_layer_2_kda_qkv.w_0")
    assert tuple(held.shape) == (64, 3 * 2 * 16)    # two of four heads
    assert tuple(scope.find_var("kimi_lm_head").shape) == (96, 64)


# ---------------------------------------------------------------------------
# the share test
# ---------------------------------------------------------------------------

def _family():
    from benchmark import cells
    return cells._load_module(
        os.path.join(REPO, "benchmark", "families", "kimilinear.py"),
        "benchmark_family_kimilinear_for_the_model_test")


SIZES = {"d": 64, "dk": 16, "k": 4, "rank": 8, "nope": 16, "rope": 8,
         "dv": 16, "kv_rank": 24, "moe_ff": 32, "routed": 16, "top_k": 2,
         "shared": 1, "eps": 1e-5, "norm_topk": True, "scaling": 2.446,
         "held": (0, 16)}
HEADS, EXPERTS = 4, 16


def _whole_weights(seed=3):
    """The uncut layer's weights, by the family's suffixes."""
    rng = np.random.default_rng(seed)
    d, hk = 64, HEADS * 16

    def w(*shape, scale=0.2):
        return (scale * rng.standard_normal(shape)).astype(np.float32)

    return {
        "kda_qkv.w_0": w(d, 3 * hk), "kda_qkv_conv.w_0": w(4, 3 * hk, scale=.5),
        "kda_f_a.w_0": w(d, 8), "kda_f_b.w_0": w(8, hk),
        "kda_A_log": w(HEADS, scale=0.5), "kda_dt_bias": w(hk, scale=0.5),
        "kda_beta.w_0": w(d, HEADS), "kda_g_a.w_0": w(d, 8),
        "kda_g_b.w_0": w(8, hk),
        "kda_o_norm_s": 1.0 + w(16, scale=0.1), "kda_out.w_0": w(hk, d),
        "mla_q.w_0": w(d, HEADS * 24), "mla_kv_a.w_0": w(d, 24 + 8),
        "mla_kv_a_norm_s": 1.0 + w(24, scale=0.1),
        "mla_kv_b.w_0": w(24, HEADS * 32), "mla_out.w_0": w(HEADS * 16, d),
        "router.w_0": w(d, EXPERTS, scale=1.0),
        "experts_gate_up": w(EXPERTS, d, 64), "experts_down": w(EXPERTS, 32, d),
        "shared_gate_up.w_0": w(d, 64), "shared_down.w_0": w(32, d)}


def _cols(m, first, count, width, groups=1):
    """The columns of heads [first, first + count) of a matrix whose
    columns are `groups` runs of HEADS heads of `width` each."""
    per = m.shape[-1] // groups
    return np.concatenate([
        m[..., g * per + first * width:g * per + (first + count) * width]
        for g in range(groups)], axis=-1)


def _head_share(w, first, count):
    """What the rank holding heads [first, first + count) holds of the two
    mixers: those heads' columns and rows; W_fa, W_ga, W_kva and the norms
    whole."""
    out = dict(w)
    out["kda_qkv.w_0"] = _cols(w["kda_qkv.w_0"], first, count, 16, groups=3)
    out["kda_qkv_conv.w_0"] = _cols(w["kda_qkv_conv.w_0"], first, count, 16,
                                    groups=3)
    for name in ("kda_f_b.w_0", "kda_g_b.w_0", "kda_dt_bias"):
        out[name] = _cols(w[name], first, count, 16)
    out["kda_A_log"] = w["kda_A_log"][first:first + count]
    out["kda_beta.w_0"] = w["kda_beta.w_0"][:, first:first + count]
    out["kda_out.w_0"] = w["kda_out.w_0"][first * 16:(first + count) * 16]
    out["mla_q.w_0"] = _cols(w["mla_q.w_0"], first, count, 24)
    out["mla_kv_b.w_0"] = _cols(w["mla_kv_b.w_0"], first, count, 32)
    out["mla_out.w_0"] = w["mla_out.w_0"][first * 16:(first + count) * 16]
    return out


def _program_parts(weights, heads_held, experts_held, x):
    """One forward Program: the KDA mixer, the MLA mixer and the expert
    layer (WITHOUT the shared expert) for the given share, through
    `Executor`, from `weights` under the layers' own parameter names."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        u = layers.data("u", list(x.shape), dtype="float32",
                        append_batch_size=False)
        kda = layers.kda_attention(u, HEADS, 16, gate_rank=8,
                                   heads_held=heads_held, name="kda")
        mla = layers.mla_attention(u, HEADS, 16, 8, 16, 24,
                                   heads_held=heads_held, name="mla")
        moe, _load = layers.moe_ffn(
            layers.reshape(u, [-1, 64]), EXPERTS, 2, 32,
            experts_held=experts_held, routed_scaling_factor=2.446,
            name="ffn")
    scope, exe = Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    first, count = experts_held
    named = {"ffn_router.w_0": weights["router.w_0"],
             "ffn_experts_gate_up":
                 weights["experts_gate_up"][first:first + count],
             "ffn_experts_down": weights["experts_down"][first:first + count]}
    named.update({k: v for k, v in weights.items()
                  if k.startswith(("kda_", "mla_"))})
    for p in main.global_block().all_parameters():
        assert tuple(scope.find_var(p.name).shape) \
            == tuple(named[p.name].shape), p.name
        scope.set_var(p.name, jax.numpy.asarray(named[p.name]))
    got = exe.run(main, feed={"u": x}, fetch_list=[kda, mla, moe],
                  scope=scope)
    return [np.asarray(g) for g in got]


def test_the_shares_of_heads_and_experts_add_up_to_the_uncut_reference():
    from benchmark import reference
    family = _family()
    mm = reference.matmul_at("float32")
    whole = _whole_weights()
    x = np.random.default_rng(8).standard_normal((2, 64, 64)).astype(
        np.float32)
    with jax.default_matmul_precision("highest"):
        want_kda = np.asarray(family.mixer_part(x, whole, "kda", SIZES, mm))
        want_mla = np.asarray(family.mixer_part(x, whole, "mla", SIZES, mm))
        want_ffn = np.asarray(family.ffn_part(x, whole, SIZES, mm))
        shared = np.asarray(family._gated_mlp(
            x, whole["shared_gate_up.w_0"], whole["shared_down.w_0"], mm))
    parts = [_program_parts(_head_share(whole, 2 * r, 2), (2 * r, 2),
                            (8 * r, 8), x) for r in (0, 1)]
    for r in (0, 1):        # every share gives something of its own
        assert all(float(np.max(np.abs(p))) > 1e-3 for p in parts[r])
    kda, mla, moe = (parts[0][i] + parts[1][i] for i in range(3))
    np.testing.assert_allclose(kda, want_kda, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(mla, want_mla, rtol=2e-4, atol=2e-5)
    # the experts' parts plus the shared expert ONCE (every rank computes
    # it alike; adding it on both would count it twice)
    np.testing.assert_allclose(moe.reshape(2, 64, 64) + shared, want_ffn,
                               rtol=2e-4, atol=2e-5)
    assert float(np.max(np.abs(shared))) > 1e-3
    # and the reference's own shares add up as well
    with jax.default_matmul_precision("highest"):
        ref_parts = [family.mixer_part(x, _head_share(whole, 2 * r, 2), kind,
                                       SIZES, mm)
                     for r in (0, 1) for kind in ("kda", "mla")]
    np.testing.assert_allclose(ref_parts[0] + ref_parts[2], want_kda,
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(ref_parts[1] + ref_parts[3], want_mla,
                               rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# latent attention's widths through the flash kernels
# ---------------------------------------------------------------------------

def test_flash_at_d192_dv128_is_the_fused_backward_and_agrees_with_xla():
    """`attention_path` sends the decompressed latent heads (D 192, Dv 128,
    one query head a key/value head, causal, no window) to the flash
    kernels with the fused backward at the cell's compiled shape (the
    split pair until PR 43: "split: widths"), and the interpret-mode
    kernels agree with XLA attention there in value and in the three
    gradients."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa
    big = (2, 16, 8192)
    path = fa.attention_path(big + (192,), big + (192,), big + (128,),
                             jnp.bfloat16, True, None, False, auto=True)
    assert path.path == "flash" and path.backward == "fused"
    assert len(path.blocks) == 2 and min(min(b) for b in path.blocks) >= 128
    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(keys[0], (1, 2, 256, 192))
    k = jax.random.normal(keys[1], (1, 2, 256, 192))
    v = jax.random.normal(keys[2], (1, 2, 256, 128))
    cot = jax.random.normal(keys[3], (1, 2, 256, 128))
    scale = 192 ** -0.5
    small = fa.attention_path(q.shape, k.shape, v.shape, q.dtype, True, None,
                              True, block_q=128, block_k=128)
    assert small.path == "flash" and small.backward == "fused"

    def flash(q_, k_, v_):
        return fa.flash_attention(q_, k_, v_, scale=scale, causal=True,
                                  block_q=128, block_k=128, interpret=True)

    def xla(q_, k_, v_):
        return fa._xla_attention(q_, k_, v_, None, scale, True)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(flash(q, k, v), xla(q, k, v), rtol=2e-4,
                                   atol=2e-5)
        got = jax.grad(lambda *a: jnp.sum(flash(*a) * cot), (0, 1, 2))(q, k, v)
        want = jax.grad(lambda *a: jnp.sum(xla(*a) * cot), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)
