"""The expert layer against `jax.numpy` in value and gradient: `moe_route`,
`moe_dispatch`, `moe_experts` and `moe_combine` composed as `layers.moe_ffn`
composes them, against the dense masked sum; the four shares of one layer
adding up to the uncut layer; routing that puts every pick, or no pick, on
the held experts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import moe_ops
from paddle_tpu.ops.pallas import grouped_matmul as gm

from _moe_cases import (_close, _grouped_matmul_that_leaves_nan,
                        _layer_weights, _op, _share, moe_by_ops, moe_dense)


@pytest.mark.parametrize("norm", [True, False],
                         ids=["norm_topk_prob", "scores as they are"])
@pytest.mark.parametrize("top_k", [1, 2, 4])
@pytest.mark.parametrize("held", [(0, 8), (0, 2), (2, 2), (5, 3)])
def test_the_expert_layer_equals_the_dense_masked_sum(held, top_k, norm):
    """Float32, to 1e-6: the output, and the gradients of X, of the
    router's matrix (through TopW: `moe_combine`'s row dot), of W13 and of
    W2."""
    p = _layer_weights(seed=11)
    args = _share(p, held)
    got, sizes, picks = jax.jit(lambda *a: moe_by_ops(
        *a, top_k, held, norm=norm))(*args)
    _close(got, jax.jit(lambda *a: moe_dense(
        *a, top_k, held, norm=norm))(*args), 1e-6, "out")
    # the load counts what landed on each held expert
    want = [(np.asarray(picks) == held[0] + g).sum() for g in range(held[1])]
    assert list(np.asarray(sizes)) == want
    cot = jax.random.normal(jax.random.PRNGKey(4), got.shape)
    which = (0, 1, 3, 4)            # x, the router, the experts' matrices
    mine = jax.jit(jax.grad(lambda *a: jnp.sum(moe_by_ops(
        *a, top_k, held, norm=norm)[0] * cot), which))(*args)
    ref = jax.jit(jax.grad(lambda *a: jnp.sum(moe_dense(
        *a, top_k, held, norm=norm) * cot), which))(*args)
    # a single pick renormalised is s / (s + 1e-6): its weight hardly moves
    # with s, and both sides form that gradient (~1e-5) by cancellation
    lone = top_k == 1 and norm
    for name, g, r in zip(("x", "router", "w13", "w2"), mine, ref):
        _close(g, r, 1e-5 if lone and name == "router" else 1e-6, name)
    # elsewhere, where a pick landed here, the router's gradient is no zero
    assert lone or not sum(want) or float(jnp.max(jnp.abs(mine[1]))) > 1e-2


def test_the_four_shares_add_up_to_the_uncut_layer():
    """What ties the chip's share to the model: each of four ranks holds 8
    of 32 experts, routes over all 32 and computes its own experts' part;
    the parts add up to the whole layer (nothing is counted twice: there
    is no shared expert)."""
    p = _layer_weights(tokens=64, experts=32, seed=3)
    whole = moe_dense(*_share(p, (0, 32)), 4, (0, 32))
    parts, landed = [], 0
    for first in (0, 8, 16, 24):
        out, sizes, _picks = moe_by_ops(*_share(p, (first, 8)), 4,
                                        (first, 8))
        parts.append(out)
        landed += int(jnp.sum(sizes))
    assert landed == 64 * 4         # every pick lands on exactly one rank
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-4, atol=1e-5)
    # and each share is no trivial part of it
    assert all(float(jnp.max(jnp.abs(part))) > 1e-3 for part in parts)


@pytest.mark.parametrize("case", ["every pick on one held expert",
                                  "no pick on any held expert",
                                  "one pair on a held expert"])
def test_imbalance_loses_no_row(case):
    """The buffer is sized for the worst case: with every token's picks
    forced onto held expert 3 (and one more held expert, picks being
    distinct), with every pick forced onto absent experts, and with one
    pair of all on a held expert (a buffer of one row), value and gradients
    still equal the dense masked sum."""
    p = _layer_weights(tokens=40, experts=8, seed=5)
    held = (2, 4)
    args = _share(p, held)
    if case.startswith("every"):
        forced = jnp.tile(jnp.asarray([[5, 2]], jnp.int32), (40, 1))
    else:
        forced = jnp.tile(jnp.asarray([[0, 7]], jnp.int32), (40, 1))
    if case.startswith("one"):
        forced = forced.at[17, 1].set(3)
    got, sizes, _ = jax.jit(lambda *a: moe_by_ops(
        *a, 2, held, routed_picks=forced))(*args)
    want = jax.jit(lambda *a: moe_dense(
        *a, 2, held, routed_picks=forced))(*args)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    if case.startswith("every"):
        assert list(np.asarray(sizes)) == [40, 0, 0, 40]
        assert float(jnp.max(jnp.abs(got))) > 1e-3
    elif case.startswith("one"):
        assert list(np.asarray(sizes)) == [0, 1, 0, 0]
        rows = np.flatnonzero(np.abs(np.asarray(got)).max(axis=1))
        assert list(rows) == [17]
    else:
        assert list(np.asarray(sizes)) == [0, 0, 0, 0]
        assert float(jnp.max(jnp.abs(got))) == 0.0
    cot = jax.random.normal(jax.random.PRNGKey(2), got.shape)
    which = (0, 1, 3, 4)
    mine = jax.jit(jax.grad(lambda *a: jnp.sum(moe_by_ops(
        *a, 2, held, routed_picks=forced)[0] * cot), which))(*args)
    ref = jax.jit(jax.grad(lambda *a: jnp.sum(moe_dense(
        *a, 2, held, routed_picks=forced) * cot), which))(*args)
    for name, g, r in zip(("x", "router", "w13", "w2"), mine, ref):
        assert bool(jnp.all(jnp.isfinite(g))), name
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("held", [(0, 8), (5, 3)])
def test_nan_in_the_rows_of_no_pair_reaches_nothing(held, monkeypatch):
    """With NaN in every buffer row that holds no pair, after both grouped
    matmuls and in both directions, the layer's output and its four
    gradients are finite and equal the clean run's to the bit: every read
    that leaves the ops goes through the `where` on the pick's own mask
    (a weight of 0 would not do: 0 * NaN is NaN)."""
    p = _layer_weights(seed=13)
    args = _share(p, held)
    cot = jax.random.normal(jax.random.PRNGKey(6), p["x"].shape)

    def run():
        out, sizes, _picks = moe_by_ops(*args, 4, held)
        grads = jax.grad(lambda *a: jnp.sum(moe_by_ops(*a, 4, held)[0]
                                            * cot), (0, 1, 3, 4))(*args)
        return (out,) + grads, sizes

    clean, sizes = run()
    tm = gm.row_tile(48 * 4)
    assert int(jnp.sum(sizes)) < gm.buffer_rows(48 * 4, held[1], tm)
    monkeypatch.setattr(gm, "grouped_matmul", _grouped_matmul_that_leaves_nan)
    dirty, _sizes = run()
    # the NaNs were there: the experts' own output holds them
    route = _op("moe_route", {"X": args[0], "W": args[1], "Bias": args[2]},
                {"top_k": 4})
    d = _op("moe_dispatch", {"X": args[0], "TopE": route["TopE"]},
            {"experts_held": list(held)})
    y = _op("moe_experts", {"Rows": d["Rows"],
                            "W13": args[3], "W2": args[4],
                            "GroupSizes": d["GroupSizes"],
                            "TileGroup": d["TileGroup"]})["Out"]
    padding = np.asarray(d["RowPair"]) < 0
    assert padding.any() and np.isnan(np.asarray(y)[padding]).all()
    assert not np.isnan(np.asarray(y)[~padding]).any()
    for name, a, b in zip(("out", "x", "router", "w13", "w2"), clean, dirty):
        assert bool(jnp.all(jnp.isfinite(b))), name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), name)


def test_every_crossing_lowers_to_plain_row_gathers():
    """The lowered (not optimised) module of the layer's four ops forward
    and backward in bfloat16: every gather that takes whole rows of width d
    yields a 2-D result in the rows' own dtype (none of rank 3, none in
    float32: the (tokens, top_k, d) float32 form cannot come back unseen by
    a CPU-only check): tokens -> buffer rows in one take the length of the
    buffer (X in the forward) or a chunk of tiles at a time (dOut in the
    backward), buffer rows -> held-pair order a chunk and the k - 1 places
    a block reads past its end, and the takes of
    `tokens` rows (the walk's one, the fallback's k; the module holds a
    shape's `_take` once, however often it is called); and no scatter is
    lowered."""
    import re
    tokens, d, top_k, held = 24, 32, 4, (2, 4)
    p = _layer_weights(tokens=tokens, d=d, ff=16)
    x, w_r, bias, w13, w2 = _share(p, held)
    args = (x.astype(jnp.bfloat16), w_r, bias, w13.astype(jnp.bfloat16),
            w2.astype(jnp.bfloat16))
    text = jax.jit(jax.grad(lambda *a: jnp.sum(moe_by_ops(
        *a, top_k, held)[0].astype(jnp.float32)), (0, 1, 3, 4))).lower(
            *args).as_text()
    assert "scatter" not in text
    row_gathers = []
    for line in text.splitlines():
        if "stablehlo.gather" not in line:
            continue
        sizes = re.search(r"slice_sizes = array<i64: ([\d, ]+)>", line)
        result = re.search(r"-> tensor<([^>]+)>\s*$", line)
        assert sizes and result, line
        if [int(n) for n in sizes.group(1).split(",")][-1] == d:
            row_gathers.append(result.group(1))
    tm = gm.row_tile(tokens * top_k)
    rows = gm.buffer_rows(tokens * top_k, held[1], tm)
    chunk = moe_ops._chunk_rows(rows, tm)
    assert chunk < rows
    assert set(row_gathers) == {"%dx%dxbf16" % (n, d) for n in (
        chunk, chunk + top_k - 1, tokens, rows)}, row_gathers


def test_the_dispatch_plan_is_a_permutation_of_the_held_pairs():
    picks = jnp.asarray([[0, 3], [3, 1], [2, 3], [1, 0], [3, 2]], jnp.int32)
    pos, row_pair, held_pair, sizes, tile_group = moe_ops.dispatch_plan(
        picks, 1, 2)
    rows = row_pair.shape[0]
    assert list(np.asarray(sizes)) == [2, 2]        # experts 1 and 2
    assert rows == gm.buffer_rows(10, 2, 8) and tile_group.shape == (4,)
    pos, row_pair = np.asarray(pos), np.asarray(row_pair)
    held = (np.asarray(picks) >= 1) & (np.asarray(picks) <= 2)
    assert (pos[~held] == rows).all() and (pos[held] < rows).all()
    # a held pair's row names that pair, and no other row does
    for t, j in zip(*np.nonzero(held)):
        assert row_pair[pos[t, j]] == t * 2 + j
    assert (row_pair >= 0).sum() == held.sum()
    # expert 1's rows come first, each group from the start of a tile
    assert sorted(pos[np.asarray(picks) == 1]) == [0, 1]
    assert sorted(pos[np.asarray(picks) == 2]) == [8, 9]
    # the held pairs in pair order (a token's picks side by side), then 10s
    assert list(np.asarray(held_pair)) == [3, 4, 6, 9] + [10] * 6


def test_weights_left_as_scores_and_scaled():
    """`norm_topk_prob=False` leaves the picks' scores as they are and
    `routed_scaling_factor` multiplies them (other routers of the family
    state other values than the benchmark's configuration)."""
    p = _layer_weights(tokens=16, experts=4, seed=8)
    ins = {"X": p["x"], "W": p["w_r"], "Bias": jnp.zeros((4,))}
    got = _op("moe_route", ins, {"top_k": 2, "norm_topk_prob": False,
                                 "routed_scaling_factor": 2.5})
    scores = np.asarray(jax.nn.sigmoid(p["x"] @ p["w_r"]))
    want = np.take_along_axis(scores, np.asarray(got["TopE"]), 1)
    np.testing.assert_allclose(got["TopW"], 2.5 * want, rtol=1e-5)
    same = _op("moe_route", ins, {"top_k": 2})
    np.testing.assert_allclose(
        same["TopW"], want / (want.sum(1, keepdims=True) + 1e-6), rtol=1e-5)


def test_the_bias_moves_the_choice_and_not_the_weights():
    p = _layer_weights(tokens=16, experts=4, seed=7)
    plain = _op("moe_route", {"X": p["x"], "W": p["w_r"],
                              "Bias": jnp.zeros((4,))}, {"top_k": 2})
    pushed = _op("moe_route", {"X": p["x"], "W": p["w_r"],
                               "Bias": jnp.asarray([0., 0., 0., 10.])},
                 {"top_k": 2})
    assert (np.asarray(pushed["TopE"])[:, 0] == 3).all()
    assert not (np.asarray(plain["TopE"])[:, 0] == 3).all()
    scores = jax.nn.sigmoid(p["x"] @ p["w_r"])
    got = np.asarray(pushed["TopW"])
    want = np.take_along_axis(np.asarray(scores),
                              np.asarray(pushed["TopE"]), 1)
    np.testing.assert_allclose(got, want / (want.sum(1, keepdims=True)
                                            + 1e-6), rtol=1e-5)
    assert pushed["TopW"].dtype == jnp.float32
