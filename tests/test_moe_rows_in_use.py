"""The expert layer's passes over the rows in use (gather, combine and their
pullbacks) against PR 32's whole-buffer spelling, to the bit in float32;
NaN past the rows in use reaching nothing; no scatter at any depth; the
rule that says which form a step takes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import moe_ops
from paddle_tpu.ops.pallas import grouped_matmul as gm

from _moe_cases import (_grouped_matmul_that_leaves_nan, _layer_weights, _op,
                        _share, moe_by_ops)


def _whole_rows_of_tokens(x, row_pair, k):
    """rows[r] = x[token of the pair in row r], over the whole buffer."""
    return jnp.take(x, jnp.maximum(row_pair, 0) // k, axis=0, mode="clip")


def _whole_sum_of_picks(buf, pos, weights=None):
    """One take of every token a pick, accumulated in float32."""
    rows = buf.shape[0]
    total = 0.0
    for j in range(pos.shape[1]):
        got = jnp.take(buf, pos[:, j], axis=0, mode="clip")
        part = jnp.where((pos[:, j] < rows)[:, None], got,
                         0).astype(jnp.float32)
        total = total + (part if weights is None
                         else part * weights[:, j, None])
    return total.astype(buf.dtype)


@jax.custom_vjp
def _whole_gather_rows(x, pos, row_pair):
    return _whole_rows_of_tokens(x, row_pair, pos.shape[1])


_whole_gather_rows.defvjp(
    lambda x, pos, row_pair: (_whole_gather_rows(x, pos, row_pair), pos),
    lambda pos, d_rows: (_whole_sum_of_picks(d_rows, pos), None, None))


@jax.custom_vjp
def _whole_combine(y, weights, pos, row_pair):
    return _whole_sum_of_picks(y, pos, weights)


def _whole_combine_bwd(res, d_out):
    y, weights, pos, row_pair = res
    rows, k = y.shape[0], pos.shape[1]
    w_row = jnp.where(row_pair >= 0, jnp.take(
        weights.reshape(-1), jnp.maximum(row_pair, 0), mode="clip"), 0.0)
    dy = (_whole_rows_of_tokens(d_out, row_pair, k).astype(jnp.float32)
          * w_row[:, None]).astype(y.dtype)
    dw = []
    for j in range(k):
        got = jnp.take(y, pos[:, j], axis=0, mode="clip")
        got = jnp.where((pos[:, j] < rows)[:, None], got, 0)
        dw.append(jnp.sum(got.astype(jnp.float32)
                          * d_out.astype(jnp.float32), axis=-1))
    return dy, jnp.stack(dw, axis=1), None, None


_whole_combine.defvjp(
    lambda y, w, pos, row_pair: (_whole_combine(y, w, pos, row_pair),
                                 (y, w, pos, row_pair)), _whole_combine_bwd)


def moe_whole_buffer(x, w_r, bias, w13, w2, top_k, held, routed_picks=None):
    """The layer as PR 32 spelt it (the plain reference of the passes that
    follow the rows in use): the same router, plan and grouped matmuls, and
    around them one take the length of the buffer, k takes of every token,
    the silu pass over every row."""
    route = _op("moe_route", {"X": x, "W": w_r, "Bias": bias},
                {"top_k": top_k})
    picks = route["TopE"] if routed_picks is None else routed_picks
    pos, row_pair, _held_pair, sizes, tile_group = moe_ops.dispatch_plan(
        picks, *held)
    rows = _whole_gather_rows(x, pos, row_pair)
    tm = rows.shape[0] // tile_group.shape[0]
    gate, up = jnp.split(gm.grouped_matmul(rows, w13, sizes, tm), 2, axis=1)
    act = (jax.nn.silu(gate.astype(jnp.float32))
           * up.astype(jnp.float32)).astype(rows.dtype)
    y = gm.grouped_matmul(act, w2, sizes, tm)
    return _whole_combine(y, route["TopW"], pos, row_pair)


def _routing(case):
    """(layer weights, top_k, held, forced picks or None, whether the walk
    over the held pairs runs)."""
    if case == "no pair held":
        p = _layer_weights(tokens=40, experts=8, seed=5)
        return p, 2, (2, 4), jnp.tile(jnp.asarray([[0, 7]], jnp.int32),
                                      (40, 1)), True
    if case == "one tile in use":
        p = _layer_weights(tokens=40, experts=8, seed=6)
        forced = np.tile(np.asarray([[0, 7]], np.int32), (40, 1))
        forced[[3, 4, 17, 30, 39], [1, 0, 1, 1, 0]] = 3
        return p, 2, (3, 1), jnp.asarray(forced), True
    if case == "even routing, 8 of 256, top-8":
        return _layer_weights(tokens=128, experts=256, seed=7), 8, (0, 8), \
            None, True
    if case == "a token with several held picks":
        p = _layer_weights(tokens=64, experts=16, seed=8)
        forced = np.tile(np.asarray([[4, 9, 15, 12]], np.int32), (64, 1))
        forced[5] = [0, 9, 2, 3]
        forced[6] = [3, 1, 0, 2]        # a neighbour: its run starts anew
        forced[17] = [12, 1, 15, 0]
        forced[63] = [9, 4, 15, 3]
        return p, 4, (0, 4), jnp.asarray(forced), True
    assert case == "every pick held: the fallback"
    p = _layer_weights(tokens=40, experts=8, seed=9)
    return p, 2, (2, 4), jnp.tile(jnp.asarray([[5, 2]], jnp.int32),
                                  (40, 1)), False


ROUTINGS = ["no pair held", "one tile in use",
            "even routing, 8 of 256, top-8",
            "a token with several held picks",
            "every pick held: the fallback"]


def _both_spellings(case, dtype):
    """((out, dX, d router, dW13, dW2) of the ops, the same of PR 32's
    spelling, group sizes) for one routing."""
    p, top_k, held, forced, _walks = _routing(case)
    x, w_r, bias, w13, w2 = _share(p, held)
    args = (x.astype(dtype), w_r, bias, w13.astype(dtype), w2.astype(dtype))
    cot = jax.random.normal(jax.random.PRNGKey(3), x.shape)

    def run(layer):     # jitted, as a step is: both sides fuse alike
        def loss(*a):
            out = layer(*a)
            return jnp.sum(out.astype(jnp.float32) * cot), out
        grads, out = jax.jit(jax.grad(loss, (0, 1, 3, 4), has_aux=True))(
            *args)
        return (out,) + grads

    mine = run(lambda *a: moe_by_ops(*a, top_k, held,
                                     routed_picks=forced)[0])
    ref = run(lambda *a: moe_whole_buffer(*a, top_k, held,
                                          routed_picks=forced))
    sizes = jax.jit(lambda *a: moe_by_ops(
        *a, top_k, held, routed_picks=forced)[1])(*args)
    return mine, ref, sizes


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", ROUTINGS)
def test_the_passes_over_the_rows_in_use_equal_the_whole_buffer_spelling(
        case, dtype):
    """Value and the gradients of X, the router (through TopW), W13 and W2:
    float32 equal to the last digit (the picks of a token are added in
    ascending pick as before, and what an absent pick added was an exact
    0.0), bfloat16 within one rounding. The worst case (every pick on a
    held expert) fills more than half the buffer and takes the takes."""
    p, top_k, held, _forced, walks = _routing(case)
    mine, ref, sizes = _both_spellings(case, jnp.dtype(dtype))
    pairs = p["x"].shape[0] * top_k
    tm = gm.row_tile(pairs)
    in_use = int(moe_ops.rows_laid_out(np.asarray(sizes), tm))
    assert in_use == int(gm.layout(sizes, gm.buffer_rows(
        pairs, held[1], tm), tm)["tiles"]) * tm
    assert bool(moe_ops.takes_bounded_form(
        in_use, gm.buffer_rows(pairs, held[1], tm))) == walks
    if case == "one tile in use":
        assert in_use == tm
    for name, got, want in zip(("out", "x", "router", "w13", "w2"), mine,
                               ref):
        assert got.dtype == want.dtype, name
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        assert np.isfinite(got).all(), name
        if dtype == "bfloat16":
            assert (np.abs(got - want) <= 2.0 ** -7 * np.abs(want)).all(), \
                name
        elif name in ("out", "router"):
            # a weighted sum and a dot: the CPU's compiler contracts a
            # product into the add that follows it (one rounding, not two)
            # in one spelling's loop and not in the other's; the order of
            # the terms, which is the program's, is held to the bit by
            # `test_the_walk_adds_a_tokens_picks_in_the_takes_order`
            assert (np.abs(got - want)
                    <= 4 * np.spacing(np.abs(want).max())).all(), name
        else:
            np.testing.assert_array_equal(got, want, name)
    held_any = int(np.asarray(sizes).sum()) > 0
    assert (float(jnp.max(jnp.abs(mine[0]))) > 1e-3) == held_any


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("weighted", [False, True],
                         ids=["plain", "weighted"])
def test_the_walk_adds_a_tokens_picks_in_the_takes_order(weighted, dtype):
    """The sums alone, on rows of every magnitude: the walk over the held
    pairs equals the k takes to the last digit, and the takes in descending
    pick do not. (The weights are powers of two, so that a product is exact
    and a contracted multiply-add rounds as the two operations do: what is
    left to differ is the order of the terms.)"""
    _p, top_k, held, forced, _walks = _routing(
        "a token with several held picks")
    pos, _row_pair, held_pair, sizes, _tg = moe_ops.dispatch_plan(
        forced, *held)
    rows = _row_pair.shape[0]
    k1, k2 = jax.random.split(jax.random.PRNGKey(12))
    buf = (jax.random.normal(k1, (rows, 16))
           * 10.0 ** jax.random.randint(k2, (rows, 1), -3, 4)).astype(dtype)
    w = 2.0 ** jax.random.randint(k2, pos.shape, -3, 2).astype(
        jnp.float32) if weighted else None
    takes = jax.jit(lambda b: moe_ops.sum_of_picks(b, pos, w))(buf)
    walk = jax.jit(lambda b: moe_ops.sum_of_held_picks(
        b, pos, held_pair, jnp.sum(sizes), w))(buf)
    assert walk.dtype == takes.dtype == jnp.dtype(dtype)
    np.testing.assert_array_equal(np.asarray(walk, np.float32),
                                  np.asarray(takes, np.float32))
    assert float(jnp.max(jnp.abs(takes.astype(jnp.float32)))) > 1.0
    if dtype == "float32":
        back = jax.jit(lambda b: moe_ops.sum_of_picks(
            b, pos[:, ::-1], None if w is None else w[:, ::-1]))(buf)
        assert (np.asarray(back) != np.asarray(takes)).any()


def _nan(shape, dtype, after):
    return jnp.full(shape, jnp.nan, dtype)


@pytest.mark.parametrize("case", ["a token with several held picks",
                                  "even routing, 8 of 256, top-8",
                                  "every pick held: the fallback"])
def test_nan_past_the_rows_in_use_reaches_nothing(case, monkeypatch):
    """Every buffer a pass starts from is NaN where the pass does not write
    (on the chip such rows hold what the allocator left), and both grouped
    matmuls leave NaN in the rows past each group's end and in the tail, in
    both directions: output and gradients are finite and equal the clean
    run's to the bit."""
    clean, _ref, sizes = _both_spellings(case, jnp.float32)
    assert 0 < int(np.asarray(sizes).sum())
    monkeypatch.setattr(moe_ops, "_anything", _nan)
    monkeypatch.setattr(gm, "grouped_matmul", _grouped_matmul_that_leaves_nan)
    dirty, _ref, _sizes = _both_spellings(case, jnp.float32)
    for name, a, b in zip(("out", "x", "router", "w13", "w2"), clean, dirty):
        assert bool(jnp.all(jnp.isfinite(b))), name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)


def _primitives(jaxpr, seen):
    for eqn in jaxpr.eqns:
        seen.add(eqn.primitive.name)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, seen)
    return seen


def test_the_layer_holds_no_scatter_at_any_depth():
    """Through every `while` body, `cond` branch and `custom_vjp` rule of
    the layer's value and gradients: the bounded passes write their chunks
    with `dynamic_update_slice`, nothing scatters."""
    p, top_k, held, forced, _walks = _routing(
        "a token with several held picks")
    args = _share(p, held)
    jaxpr = jax.make_jaxpr(jax.value_and_grad(lambda *a: jnp.sum(moe_by_ops(
        *a, top_k, held, routed_picks=forced)[0]), (0, 1, 3, 4)))(*args)
    seen = _primitives(jaxpr.jaxpr, set())
    assert {"while", "cond", "gather", "dynamic_update_slice"} <= seen
    assert not {name for name in seen if "scatter" in name}, seen


def test_the_rule_that_says_which_form_a_step_takes():
    """`rows_laid_out` is the layout's tiles in rows, on numpy counts (the
    `moe.load` record) and on traced ones (the ops) alike; the walk runs up
    to half the buffer."""
    for sizes, tm in (([5, 0, 17, 8], 8), ([0, 0, 0, 0], 8),
                      ([512, 513, 1, 0, 0, 0, 0, 4096], 512)):
        want = int(gm.layout(jnp.asarray(sizes, jnp.int32),
                             sum(sizes) // tm * tm + (len(sizes) + 1) * tm,
                             tm)["tiles"]) * tm
        assert int(moe_ops.rows_laid_out(np.asarray(sizes), tm)) == want
        assert int(jax.jit(lambda s: moe_ops.rows_laid_out(s, tm))(
            jnp.asarray(sizes, jnp.int32))) == want
    assert moe_ops.takes_bounded_form(67584, 135168)
    assert not moe_ops.takes_bounded_form(67584 + 512, 135168)
    assert bool(moe_ops.takes_bounded_form(jnp.int32(0), 16))
