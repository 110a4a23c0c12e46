"""The ops beside the expert layer, and the layer as SmallThinker says it:
`rope_qk_norm`; the gated width-3 convolution; the router's own input,
softmax over the picks, ReLU gates and folded absent experts against the
dense sum in value and gradient, as ops and through a Program; and LFM2's
and Kimi's programs op for op what they were. The grouped matmul is in
`test_moe_gmm.py`, the layer against the dense sum in `test_moe_layer.py`,
the rows in use in `test_moe_rows_in_use.py`, `moe_ffn` through a Program
in `test_moe_program.py`, the microbenchmarks in `test_moe_tools.py`."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.ops import moe_ops
from paddle_tpu.ops.pallas import grouped_matmul as gm

from _moe_cases import _close, _layer_weights, _op, _op_digest, _run


# ---------------------------------------------------------------------------
# rotary positions with per-head q/k norms; the gated short convolution
# ---------------------------------------------------------------------------

def _rope_reference(x, scale, heads, d, theta, eps):
    b, t, _ = x.shape
    x = x.reshape(b, t, heads, d).transpose(0, 2, 1, 3)
    if scale is not None:
        x = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale
    out = []
    for i in range(d // 2):         # pair (i, i + d/2), one angle a pair
        angle = jnp.arange(t) * theta ** (-2.0 * i / d)
        lo, hi = x[..., i], x[..., i + d // 2]
        out.append((lo * jnp.cos(angle) - hi * jnp.sin(angle),
                    lo * jnp.sin(angle) + hi * jnp.cos(angle)))
    return jnp.stack([p[0] for p in out] + [p[1] for p in out], axis=-1)


@pytest.mark.parametrize("norm", [True, False])
def test_rope_qk_norm_equals_the_pairwise_rotation(norm):
    rng = np.random.RandomState(1)
    q = rng.randn(2, 12, 4 * 8).astype(np.float32)
    k = rng.randn(2, 12, 2 * 8).astype(np.float32)
    qs = (1.0 + 0.1 * rng.randn(8)).astype(np.float32)
    ks = (1.0 + 0.1 * rng.randn(8)).astype(np.float32)

    def build():
        qv = layers.data("q", [2, 12, 32], dtype="float32",
                         append_batch_size=False)
        kv = layers.data("k", [2, 12, 16], dtype="float32",
                         append_batch_size=False)
        qv.stop_gradient = kv.stop_gradient = False
        attr = (lambda n, v: pt.ParamAttr(
            name=n, initializer=pt.initializer.NumpyArrayInitializer(v))) \
            if norm else (lambda n, v: False)
        qo, ko = layers.rope_qk_norm(qv, kv, 8, theta=100.0, epsilon=1e-5,
                                     q_norm_attr=attr("qs", qs),
                                     k_norm_attr=attr("ks", ks))
        loss = layers.elementwise_add(
            layers.reduce_sum(layers.elementwise_mul(qo, qo)),
            layers.reduce_sum(layers.scale(ko, scale=2.0)))
        dq, dk = pt.gradients([loss], [qv, kv])
        return {"qo": qo, "ko": ko, "dq": dq, "dk": dk}

    got, main = _run(build, {"q": q, "k": k})
    assert len(main.global_block().all_parameters()) == (2 if norm else 0)
    want_q = _rope_reference(jnp.asarray(q), qs if norm else None, 4, 8,
                             100.0, 1e-5)
    want_k = _rope_reference(jnp.asarray(k), ks if norm else None, 2, 8,
                             100.0, 1e-5)
    assert got["qo"].shape == (2, 4, 12, 8) and got["ko"].shape \
        == (2, 2, 12, 8)
    np.testing.assert_allclose(got["qo"], want_q, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got["ko"], want_k, rtol=1e-4, atol=1e-5)
    dq = jax.grad(lambda a: jnp.sum(_rope_reference(
        a, qs if norm else None, 4, 8, 100.0, 1e-5) ** 2))(jnp.asarray(q))
    dk = jax.grad(lambda a: 2.0 * jnp.sum(_rope_reference(
        a, ks if norm else None, 2, 8, 100.0, 1e-5)))(jnp.asarray(k))
    np.testing.assert_allclose(got["dq"], dq, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(got["dk"], dk, rtol=1e-4, atol=1e-4)
    # position 0 is not turned; a rotation keeps each pair's length
    if not norm:
        np.testing.assert_allclose(
            got["qo"][:, :, 0], q.reshape(2, 12, 4, 8)[:, 0], rtol=1e-6)
        np.testing.assert_allclose(
            (got["qo"] ** 2).sum(-1),
            (q.reshape(2, 12, 4, 8).transpose(0, 2, 1, 3) ** 2).sum(-1),
            rtol=1e-4)


def test_the_gated_width_3_convolution_without_bias():
    """The short-convolution mixer's middle: (C * conv3(B * x)), through
    `causal_conv1d(width=3, bias_attr=False)` and `elementwise_mul`."""
    rng = np.random.RandomState(2)
    b, c, x = (rng.randn(2, 9, 6).astype(np.float32) for _ in range(3))

    def build():
        vs = [layers.data(n, [2, 9, 6], dtype="float32",
                          append_batch_size=False) for n in "bcx"]
        for v in vs:
            v.stop_gradient = False
        conv = layers.causal_conv1d(
            layers.elementwise_mul(vs[0], vs[2]), 3,
            param_attr=pt.ParamAttr(name="cw"), bias_attr=False)
        out = layers.elementwise_mul(vs[1], conv)
        w = pt.default_main_program().global_block().var("cw")
        loss = layers.reduce_sum(layers.elementwise_mul(out, out))
        grads = pt.gradients([loss], vs + [w])
        return dict({"out": out, "w": w},
                    **{"g%d" % i: g for i, g in enumerate(grads)})

    got, main = _run(build, {"b": b, "c": c, "x": x})
    assert [p.name for p in main.global_block().all_parameters()] == ["cw"]
    assert got["w"].shape == (3, 6)

    def f(b_, c_, x_, w_):
        p = jnp.pad(b_ * x_, ((0, 0), (2, 0), (0, 0)))
        return c_ * sum(p[:, i:i + 9] * w_[i] for i in range(3))

    args = (b, c, x, got["w"])
    np.testing.assert_allclose(got["out"], f(*args), rtol=1e-5, atol=1e-6)
    ref = jax.grad(lambda *a: jnp.sum(f(*a) ** 2), (0, 1, 2, 3))(*args)
    for i, r in enumerate(ref):
        np.testing.assert_allclose(got["g%d" % i], r, rtol=1e-4, atol=1e-5)
    # position t reads t-2..t only
    later = x.copy()
    later[:, 5:] += 1.0
    np.testing.assert_allclose(f(b, c, later, got["w"])[:, :5],
                               f(*args)[:, :5], rtol=1e-6)


# ---------------------------------------------------------------------------
# what SmallThinker's layer says otherwise: the router's own input, softmax
# over the picks, ReLU gates; and the defaults left as they were
# ---------------------------------------------------------------------------

def st_by_ops(x, r, w_r, w13, w2, top_k, held, folded=False):
    """The ops as `layers.moe_ffn(router_input=r, scoring="softmax",
    gate="relu")` composes them (`folded`: with `absent="folded"`)."""
    route = _op("moe_route", {"X": r, "W": w_r},
                dict({"top_k": top_k, "scoring": "softmax"},
                     **({"fold_onto": list(held)} if folded else {})))
    d = _op("moe_dispatch", {"X": x, "TopE": route["TopE"]},
            {"experts_held": list(held)})
    y = _op("moe_experts", {"Rows": d["Rows"], "W13": w13, "W2": w2,
                            "GroupSizes": d["GroupSizes"],
                            "TileGroup": d["TileGroup"]},
            {"gate": "relu"})["Out"]
    out = _op("moe_combine", {"Y": y, "TopW": route["TopW"],
                              "Pos": d["Pos"], "RowPair": d["RowPair"],
                              "HeldPair": d["HeldPair"],
                              "GroupSizes": d["GroupSizes"]})
    return out["Out"], d["GroupSizes"], route


def st_dense(x, r, w_r, w13, w2, top_k, held, folded=False):
    """Top-k of the logits r W_r, softmax over the picks' own logits (then
    `norm_topk_prob` as the op states it: over their sum + 1e-6), every
    held expert over every token with a ReLU gate; `folded`: a pick counts
    for the held expert congruent to it modulo the count held."""
    logits = jnp.dot(r, w_r, precision="highest")
    top, picks = jax.lax.top_k(logits, top_k)
    weights = jax.nn.softmax(top, axis=-1)
    weights = weights / (weights.sum(1, keepdims=True) + 1e-6)
    if folded:
        picks = held[0] + (picks - held[0]) % held[1]
    out = jnp.zeros_like(x)
    for g in range(held[1]):
        gate = jnp.sum(weights * (picks == held[0] + g), axis=1)
        a, b = jnp.split(jnp.dot(x, w13[g], precision="highest"), 2, axis=1)
        out += gate[:, None] * jnp.dot(jax.nn.relu(a) * b, w2[g],
                                       precision="highest")
    return out


def _st_args(p, held, seed=9):
    lo, hi = held[0], held[0] + held[1]
    r = jax.random.normal(jax.random.PRNGKey(seed), p["x"].shape)
    return p["x"], r, p["w_r"], p["w13"][lo:hi], p["w2"][lo:hi]


@pytest.mark.parametrize("top_k", [1, 2, 6])
@pytest.mark.parametrize("held", [(0, 8), (2, 2), (5, 3)])
def test_softmax_over_the_picks_and_relu_gates_equal_the_dense_sum(held,
                                                                   top_k):
    """Float32, to 1e-6: the output and the gradients of x (through the
    experts alone), of the router's own input r and of its matrix (through
    TopW alone), of W13 and of W2."""
    p = _layer_weights(seed=13)
    args = _st_args(p, held)
    got, sizes, route = st_by_ops(*args, top_k, held)
    _close(got, st_dense(*args, top_k, held), 1e-6, "out")
    # the weights of a token's picks are a softmax: they add up to 1 (over
    # 1 + 1e-6, the op's `norm_topk_prob`), and the picks are the logits'
    logits = jnp.dot(args[1], args[2], precision="highest")
    np.testing.assert_allclose(route["TopW"].sum(1), 1.0, rtol=1e-5)
    assert (np.asarray(route["TopE"])
            == np.asarray(jax.lax.top_k(logits, top_k)[1])).all()
    assert int(route["Load"].sum()) == 48 * top_k
    cot = jax.random.normal(jax.random.PRNGKey(4), got.shape)
    mine = jax.grad(lambda *a: jnp.sum(st_by_ops(*a, top_k, held)[0] * cot),
                    (0, 1, 2, 3, 4))(*args)
    ref = jax.grad(lambda *a: jnp.sum(st_dense(*a, top_k, held) * cot),
                   (0, 1, 2, 3, 4))(*args)
    for name, g, want in zip(("x", "r", "router", "w13", "w2"), mine, ref):
        _close(g, want, 1e-6, name)
    if top_k > 1 and int(jnp.sum(sizes)):
        assert float(jnp.max(jnp.abs(mine[1]))) > 1e-3     # r moves TopW


def test_the_softmax_route_is_not_the_sigmoid_route_and_needs_no_bias():
    p = _layer_weights(seed=2)
    soft = _op("moe_route", {"X": p["x"], "W": p["w_r"]},
               {"top_k": 2, "scoring": "softmax"})
    sig = _op("moe_route", {"X": p["x"], "W": p["w_r"],
                            "Bias": jnp.zeros((8,))}, {"top_k": 2})
    # sigmoid is monotone: the same picks, other weights
    assert (np.asarray(soft["TopE"]) == np.asarray(sig["TopE"])).all()
    assert float(jnp.max(jnp.abs(soft["TopW"] - sig["TopW"]))) > 1e-2
    with pytest.raises(ValueError, match="scoring"):
        _op("moe_route", {"X": p["x"], "W": p["w_r"]},
            {"top_k": 2, "scoring": "tanh"})


@pytest.mark.parametrize("gate", ["silu", "relu"])
@pytest.mark.parametrize("sizes", [[5, 0, 17, 8], [0, 0, 0, 0],
                                   [30, 0, 0, 0]],
                         ids=lambda s: "-".join(map(str, s)))
def test_the_gates_pullback_over_the_rows_in_use_equals_jax_grad(gate,
                                                                 sizes):
    """`_gated` (chunked over the rows the plan laid out, its hand-written
    pullback landing where `both` lay) against `jax.grad` of the plain
    act(a) * b on those rows; rows past the rows in use hold anything and
    are left out of the comparison."""
    tm, width = 8, 12
    sizes = jnp.asarray(sizes, jnp.int32)
    rows = gm.buffer_rows(30, 4, tm)
    in_use = int(moe_ops.rows_laid_out(sizes, tm))
    kb, kc = jax.random.split(jax.random.PRNGKey(6))
    both = jax.random.normal(kb, (rows, 2 * width))
    # exact zeros on the gate's side, where ReLU's derivative is a choice
    both = both.at[::5, :width].set(0.0)
    cot = jax.random.normal(kc, (rows, width))
    act = {"silu": jax.nn.silu, "relu": jax.nn.relu}[gate]

    def plain(b):
        return act(b[:, :width]) * b[:, width:]

    got, vjp = jax.vjp(lambda b: moe_ops._gated(b, sizes, tm, gate), both)
    want, ref_vjp = jax.vjp(plain, both)
    np.testing.assert_allclose(got[:in_use], want[:in_use], rtol=1e-6,
                               atol=1e-6)
    np.testing.assert_allclose(vjp(cot)[0][:in_use],
                               ref_vjp(cot)[0][:in_use], rtol=1e-6,
                               atol=1e-6)
    with pytest.raises(ValueError, match="gate"):
        _op("moe_experts", {"Rows": both, "W13": jnp.zeros((4, 24, 24)),
                            "W2": jnp.zeros((4, 12, 24)), "GroupSizes": sizes,
                            "TileGroup": jnp.zeros((rows // tm,), jnp.int32)},
            {"gate": "gelu"})


def test_the_eight_shares_add_up_to_the_uncut_64_expert_layer():
    """SmallThinker's deployment: each of eight ranks holds 8 of 64
    experts, routes every token over all 64 by the router's own input and
    computes its own experts' part; the parts add up to the whole layer."""
    p = _layer_weights(tokens=64, experts=64, seed=21)
    whole = st_dense(*_st_args(p, (0, 64)), 6, (0, 64))
    parts, landed = [], 0
    for rank in range(8):
        out, sizes, _route = st_by_ops(*_st_args(p, (8 * rank, 8)), 6,
                                       (8 * rank, 8))
        parts.append(out)
        landed += int(jnp.sum(sizes))
    assert landed == 64 * 6         # every pick lands on exactly one rank
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-5)
    assert all(float(jnp.max(jnp.abs(part))) > 1e-3 for part in parts)


@pytest.mark.parametrize("top_k", [1, 2, 6])
@pytest.mark.parametrize("held", [(0, 8), (2, 2), (5, 3)])
def test_folded_absent_experts_answer_every_pick(held, top_k):
    """`fold_onto`: a pick on an absent expert goes, with the weight the
    router gave it, to the held expert congruent to it; every pick is laid
    out, whatever the router does; output and all five gradients equal the
    dense sum's to 1e-6; the weights are the unfolded route's."""
    p = _layer_weights(seed=15)
    args = _st_args(p, held)
    got, sizes, route = st_by_ops(*args, top_k, held, folded=True)
    _close(got, st_dense(*args, top_k, held, folded=True), 1e-6, "out")
    assert int(jnp.sum(sizes)) == 48 * top_k        # every pick is answered
    picks = np.asarray(route["TopE"])
    assert picks.min() >= held[0] and picks.max() < held[0] + held[1]
    plain = _op("moe_route", {"X": args[1], "W": args[2]},
                {"top_k": top_k, "scoring": "softmax"})
    np.testing.assert_array_equal(route["TopW"], plain["TopW"])
    np.testing.assert_array_equal(
        picks, held[0] + (np.asarray(plain["TopE"]) - held[0]) % held[1])
    load = np.asarray(route["Load"])
    assert load.sum() == load[held[0]:held[0] + held[1]].sum() == 48 * top_k
    np.testing.assert_array_equal(load[held[0]:held[0] + held[1]], sizes)
    cot = jax.random.normal(jax.random.PRNGKey(4), got.shape)
    mine = jax.grad(lambda *a: jnp.sum(
        st_by_ops(*a, top_k, held, folded=True)[0] * cot),
        (0, 1, 2, 3, 4))(*args)
    ref = jax.grad(lambda *a: jnp.sum(
        st_dense(*a, top_k, held, folded=True) * cot), (0, 1, 2, 3, 4))(*args)
    for name, g, want in zip(("x", "r", "router", "w13", "w2"), mine, ref):
        _close(g, want, 1e-6, name)


def test_the_rows_laid_out_do_not_follow_the_router_where_absent_is_folded():
    """Three routers over the same tokens (even, every token on the same
    picks, all on absent experts): unfolded, the held experts' rows run
    from nothing to every pair; folded, every one lays out tokens x top_k."""
    p = _layer_weights(tokens=64, experts=64, seed=3)
    x, r, _w_r, w13, w2 = _st_args(p, (0, 8))
    even = p["w_r"]
    same = jnp.zeros_like(even).at[:, jnp.array([1, 9, 17, 30, 41, 63])].set(
        jnp.abs(r).mean(0, keepdims=True).T * jnp.sign(r.mean(0))[:, None])
    away = jnp.zeros_like(even).at[:, 8:14].set(1.0) * jnp.sign(
        r.sum(1).mean())
    rows = {}
    for name, w_r in (("even", even), ("same", same), ("away", away)):
        for folded in (False, True):
            _out, sizes, _route = st_by_ops(x, r, w_r, w13, w2, 6, (0, 8),
                                            folded=folded)
            rows[name, folded] = int(jnp.sum(sizes))
    assert {rows[n, True] for n in ("even", "same", "away")} == {64 * 6}
    assert len({rows[n, False] for n in ("even", "same", "away")}) == 3
    assert min(rows[n, False] for n in ("even", "same", "away")) < 64


def test_moe_ffn_routes_by_its_router_input_through_a_program():
    """`layers.moe_ffn(router_input=, scoring="softmax", gate="relu")`
    through Executor against the dense sum, with the gradients that reach
    both inputs; and what it refuses."""
    from paddle_tpu.framework.scope import Scope
    held = (2, 4)
    p = _layer_weights(seed=8)
    x, r, w_r, w13, w2 = _st_args(p, held)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        xv = layers.data("x", list(x.shape), append_batch_size=False)
        rv = layers.data("r", list(r.shape), append_batch_size=False)
        xv.stop_gradient = rv.stop_gradient = False
        out, load = layers.moe_ffn(xv, 8, 2, 8, experts_held=held,
                                   router_input=rv, scoring="softmax",
                                   gate="relu", name="st")
        loss = layers.reduce_sum(layers.elementwise_mul(out, out))
        grads = pt.gradients([loss], [xv, rv])
    ops = {op.type: op for op in main.global_block().ops}
    assert ops["moe_route"].inputs["X"] == [rv.name]
    assert ops["moe_dispatch"].inputs["X"] == [xv.name]
    assert "Bias" not in ops["moe_route"].inputs
    assert not main.global_block().has_var("st_expert_bias")
    scope, exe = Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    for name, value in (("st_router.w_0", w_r), ("st_experts_gate_up", w13),
                        ("st_experts_down", w2)):
        scope.set_var(name, jnp.copy(value))    # the step donates its state
    got = exe.run(main, feed={"x": np.asarray(x), "r": np.asarray(r)},
                  fetch_list=[out, load] + list(grads), scope=scope)
    want = st_dense(x, r, w_r, w13, w2, 2, held)
    _close(jnp.asarray(got[0]), want, 1e-5, "out")
    assert int(got[1].sum()) == 48 * 2
    dx, dr = jax.grad(lambda a, b: jnp.sum(
        st_dense(a, b, w_r, w13, w2, 2, held) ** 2), (0, 1))(x, r)
    _close(jnp.asarray(got[2]), dx, 1e-5, "dx")
    _close(jnp.asarray(got[3]), dr, 1e-5, "dr")
    with pt.program_guard(pt.Program(), pt.Program()):
        xv = layers.data("x", [48, 16], append_batch_size=False)
        for kw in (dict(scoring="tanh"), dict(gate="gelu")):
            with pytest.raises(ValueError, match="scoring"):
                layers.moe_ffn(xv, 8, 2, 8, **kw)


def test_moe_ffn_folds_absent_experts_through_a_program():
    """`layers.moe_ffn(absent="folded")` through Executor against the dense
    sum with folded picks, the gradients of both inputs, the load over the
    held experts alone (every pick), and what it refuses."""
    from paddle_tpu.framework.scope import Scope
    held = (2, 4)
    p = _layer_weights(seed=8)
    x, r, w_r, w13, w2 = _st_args(p, held)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        xv = layers.data("x", list(x.shape), append_batch_size=False)
        rv = layers.data("r", list(r.shape), append_batch_size=False)
        xv.stop_gradient = rv.stop_gradient = False
        out, load = layers.moe_ffn(xv, 8, 2, 8, experts_held=held,
                                   router_input=rv, scoring="softmax",
                                   gate="relu", absent="folded", name="st")
        loss = layers.reduce_sum(layers.elementwise_mul(out, out))
        grads = pt.gradients([loss], [xv, rv])
    ops = {op.type: op for op in main.global_block().ops}
    assert ops["moe_route"].attrs["fold_onto"] == [2, 4]
    scope, exe = Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    for name, value in (("st_router.w_0", w_r), ("st_experts_gate_up", w13),
                        ("st_experts_down", w2)):
        scope.set_var(name, jnp.copy(value))    # the step donates its state
    got = exe.run(main, feed={"x": np.asarray(x), "r": np.asarray(r)},
                  fetch_list=[out, load] + list(grads), scope=scope)
    _close(jnp.asarray(got[0]),
           st_dense(x, r, w_r, w13, w2, 2, held, folded=True), 1e-5, "out")
    assert got[1][2:6].sum() == got[1].sum() == 48 * 2
    assert float(jnp.max(jnp.abs(jnp.asarray(got[0]) - st_dense(
        x, r, w_r, w13, w2, 2, held)))) > 1e-3     # not the unfolded layer
    dx, dr = jax.grad(lambda a, b: jnp.sum(st_dense(
        a, b, w_r, w13, w2, 2, held, folded=True) ** 2), (0, 1))(x, r)
    _close(jnp.asarray(got[2]), dx, 1e-5, "dx")
    _close(jnp.asarray(got[3]), dr, 1e-5, "dr")
    with pt.program_guard(pt.Program(), pt.Program()):
        xv = layers.data("x", [48, 16], append_batch_size=False)
        with pytest.raises(ValueError, match="absent"):
            layers.moe_ffn(xv, 8, 2, 8, absent="dropped")


@pytest.mark.parametrize("model", ["lfm2moe", "kimi_linear"])
def test_lfm2s_and_kimis_programs_are_op_for_op_what_they_were(model):
    """`moe_ffn`'s new arguments at their defaults add no op, no slot and
    no attr: the digests are the ones the tree before them gave (PR 37's,
    computed there with this function)."""
    from paddle_tpu import optimizer
    from paddle_tpu.models import kimi_linear, lfm2moe
    if model == "lfm2moe":
        cfg = lfm2moe.Lfm2MoeConfig(
            vocab_size=96, hidden_size=64, num_heads=4, num_kv_heads=2,
            head_dim=16, ff_size=128, moe_ff_size=32, num_experts=8, top_k=2,
            experts_held=(4, 4), layer_kinds=["conv", "attention", "conv"],
            published_layer_index=[0, 2, 3], recompute=True,
            dtype="bfloat16", expert_bias_update_rate=0.001)
        main = lfm2moe.lfm2moe_pretrain_program(
            cfg, 2, 32, optimizer_fn=optimizer.Adam(1e-3).minimize)[0]
        want = (104, "6bca53fa810a0b5b")
    else:
        cfg = kimi_linear.KimiLinearConfig(
            vocab_size=96, hidden_size=64, num_heads=4, kda_head_dim=16,
            gate_rank=8, qk_nope_dim=16, qk_rope_dim=8, v_dim=16, kv_rank=24,
            ff_size=128, moe_ff_size=32, num_experts=16, top_k=2,
            experts_held=(8, 8), heads_held=(2, 2),
            layer_kinds=["kda", "kda", "mla"], published_layer_index=[1, 2, 4],
            recompute=True, dtype="bfloat16")
        main = kimi_linear.kimi_linear_pretrain_program(
            cfg, 2, 64, optimizer_fn=optimizer.Adam(1e-3).minimize)[0]
        want = (166, "157f9636a25dbdfc")
    assert _op_digest(main) == want
    route = [op for blk in main.blocks for op in blk.ops
             if op.type == "moe_route"]
    assert route and all(
        set(op.inputs) == {"X", "W", "Bias"}
        and set(op.attrs) >= {"top_k", "norm_topk_prob",
                              "routed_scaling_factor"}
        and "scoring" not in op.attrs for op in route)
    assert all("gate" not in op.attrs for blk in main.blocks
               for op in blk.ops if op.type == "moe_experts")
