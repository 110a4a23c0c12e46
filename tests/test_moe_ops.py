"""The sparse-expert ops against `jax.numpy` in value and gradient: the
grouped matmul (Pallas kernels in interpret mode, and the XLA form) against a
dense per-row product; `moe_route`, `moe_dispatch`, `moe_experts` and
`moe_combine` composed as `layers.moe_ffn` composes them, and the layer
through a Program, against the dense masked sum; the four shares of one layer
adding up to the uncut layer; routing that puts every pick, or no pick, on
the held experts; `rope_qk_norm`; and the gated width-3 convolution."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.ops import moe_ops
from paddle_tpu.ops.pallas import grouped_matmul as gm
from paddle_tpu.ops.registry import get_op


# ---------------------------------------------------------------------------
# the grouped matmul
# ---------------------------------------------------------------------------

def _dense_grouped(x, w, sizes, tm):
    """out[r] = x[r] @ w[group(r)] with the rows of no group zeroed, and
    the mask of the rows that belong to a group."""
    lay = gm.layout(sizes, x.shape[0], tm)
    row = jnp.arange(x.shape[0])
    inside = (row % tm) < lay["tile_end"][row // tm]
    out = jnp.einsum("rk,rkn->rn", x, w[lay["tile_group"][row // tm]])
    return jnp.where(inside[:, None], out, 0.0), inside


SIZES = [[5, 0, 17, 8], [0, 0, 0, 0], [30, 0, 0, 0], [0, 0, 0, 30],
         [8, 8, 8, 6], [1, 1, 1, 1]]


@pytest.mark.parametrize("form", ["pallas-interpret", "xla"])
@pytest.mark.parametrize("sizes", SIZES, ids=lambda s: "-".join(map(str, s)))
def test_grouped_matmul_equals_the_dense_product_and_its_gradients(form,
                                                                   sizes):
    tm, groups, k, n = 8, 4, 16, 24
    sizes = jnp.asarray(sizes, jnp.int32)
    rows = gm.buffer_rows(30, groups, tm)
    kx, kw, kd = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (rows, k))
    w = jax.random.normal(kw, (groups, k, n))
    cot = jax.random.normal(kd, (rows, n))
    want, inside = _dense_grouped(x, w, sizes, tm)
    interpret = True if form == "pallas-interpret" else None

    def mine(x_, w_):
        out = gm.grouped_matmul(x_, w_, sizes, tm, interpret=interpret)
        return jnp.where(inside[:, None], out, 0.0)

    np.testing.assert_allclose(mine(x, w), want, rtol=1e-5, atol=1e-5)
    got = jax.grad(lambda a, b: jnp.sum(mine(a, b) * cot), (0, 1))(x, w)
    ref = jax.grad(lambda a, b: jnp.sum(
        _dense_grouped(a, b, sizes, tm)[0] * cot), (0, 1))(x, w)
    np.testing.assert_allclose(jnp.where(inside[:, None], got[0], 0.0),
                               ref[0], rtol=1e-4, atol=1e-4)
    # an empty group's weight gradient is written, as zeros
    np.testing.assert_allclose(got[1], ref[1], rtol=1e-4, atol=1e-4)


#: (K, N) -> the tiles at 128 rows a tile: `plan`'s, whole widths (at 1408 =
#: 11 x 128 too, which has no other 128-multiple divisor but 128), and 128
#: blocks by hand, which walk every grid axis in more than one step
MXU_TILES = {
    "plan-256x384": (256, 384, gm.Tiles(128, (384, 256), (256, 384),
                                        (256, 384))),
    "plan-1408x256": (1408, 256, gm.Tiles(128, (256, 1408), (1408, 256),
                                          (1408, 256))),
    "plan-256x1408": (256, 1408, gm.Tiles(128, (1408, 256), (256, 1408),
                                          (256, 1408))),
    "blocks-of-128": (256, 384, None),
}


@pytest.mark.parametrize("case", sorted(MXU_TILES))
def test_grouped_matmul_kernels_at_mxu_tiles_in_bfloat16(case):
    """128-row tiles and widths that `plan` tiles, groups of uneven size
    and an empty one: the Pallas path as the chip takes it, in interpret
    mode, forward, dX and dW against the dense product."""
    tm, groups = 128, 3
    k, n, what = MXU_TILES[case]
    sizes = jnp.asarray([130, 0, 255], jnp.int32)
    rows = gm.buffer_rows(512, groups, tm)
    if what is not None:
        assert gm.plan(rows, k, n, tm) == what
    else:
        what = gm.Tiles(tm, (128, 128), (128, 128), (128, 128))
    lay = gm.layout(sizes, rows, tm)
    kx, kw = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (rows, k)).astype(jnp.bfloat16)
    w = (0.1 * jax.random.normal(kw, (groups, k, n))).astype(jnp.bfloat16)
    want, inside = _dense_grouped(x.astype(jnp.float32),
                                  w.astype(jnp.float32), sizes, tm)

    def mine(x_, w_):
        out = gm._grouped(x_, w_, lay["tile_group"], lay["tile_end"],
                          lay["tiles"], what, True)
        return jnp.where(inside[:, None], out.astype(jnp.float32), 0.0)

    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(mine(x, w) - want))) <= 1e-2 * scale
    dx, dw = jax.grad(lambda a, b: jnp.sum(mine(a, b) ** 2), (0, 1))(x, w)
    rx, rw = jax.grad(lambda a, b: jnp.sum(
        _dense_grouped(a, b, sizes, tm)[0] ** 2), (0, 1))(
            x.astype(jnp.float32), w.astype(jnp.float32))
    assert dx.dtype == dw.dtype == jnp.bfloat16
    for got, ref in ((jnp.where(inside[:, None], dx, 0), rx), (dw, rw)):
        scale = float(jnp.max(jnp.abs(ref)))
        assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref))) \
            <= 3e-2 * scale
    # the empty group's weight gradient is written, as zeros
    assert not np.any(np.asarray(dw[1], np.float32))


def test_the_tile_rule_and_the_buffer():
    assert [gm.row_tile(p) for p in (64, 1023, 1024, 8191, 8192, 65536)] \
        == [8, 8, 128, 128, 512, 512]
    # the cell's call: 16,384 tokens x 4 picks over 8 held experts
    assert gm.buffer_rows(65536, 8, 512) == 69632
    assert gm.plan(96, 16, 24, 8) is None           # widths: the XLA form
    assert gm.plan(69632, 2048, 3584, 8) is None    # rows: the XLA form
    # any split of the pairs fits: one group takes all, or each a tile more
    for sizes in ([30, 0, 0, 0], [8, 8, 7, 7], [1, 1, 1, 27]):
        lay = gm.layout(jnp.asarray(sizes, jnp.int32), 64, 8)
        assert int(lay["tiles"]) * 8 <= 64
        assert int(jnp.sum(lay["tile_end"])) == 30
    with pytest.raises(ValueError, match="do not fit"):
        gm.grouped_matmul(jnp.zeros((16, 4)), jnp.zeros((2, 5, 4)),
                          jnp.zeros((2,), jnp.int32), 8)


#: the four expert cells' two calls a layer: (buffer rows, K, N) and the
#: tiles of forward, dX and dW that `plan` takes there (at 512 rows a tile)
CELL_CALLS = {
    "kimi-vl-w13": (102400, 2048, 2816,
                    ((1408, 2048), (1024, 2816), (2048, 1408))),
    "kimi-vl-w2": (102400, 1408, 2048,
                   ((2048, 1408), (1408, 2048), (1408, 2048))),
    "lfm2-w13": (69632, 2048, 3584,
                 ((1792, 2048), (1024, 3584), (1024, 1792))),
    "lfm2-w2": (69632, 1792, 2048,
                ((2048, 1792), (1792, 2048), (1792, 1024))),
    "smallthinker-w13": (200704, 2560, 1536,
                         ((1536, 2560), (2560, 1536), (1280, 1536))),
    "smallthinker-w2": (200704, 768, 2560,
                        ((2560, 768), (768, 2560), (768, 2560))),
    "kimi-w13": (135168, 2304, 2048,
                 ((2048, 2304), (2304, 2048), (1152, 2048))),
    "kimi-w2": (135168, 1024, 2304,
                ((2304, 1024), (1024, 2304), (1024, 2304))),
}


@pytest.mark.parametrize("call", sorted(CELL_CALLS))
def test_plan_takes_the_tiles_that_move_the_fewest_bytes(call):
    """Every tile a 128-multiple divisor of its width, the blocks inside
    the VMEM budget, never more modelled bytes than the capped divisors
    moved; and at an expert width of 1408, where five of the six kernels
    waited for re-read rows, the bytes now take less time than the matmul
    (8 held experts, bfloat16, a v5e's 819 GB/s and 197 TFLOP/s)."""
    rows, k, n, tiles = CELL_CALLS[call]
    tm, groups = 512, 8
    # the tiles `plan` took until PR 41 live in the tool alone
    from tools.mb_gmm_tiles import old_plan
    what, before = gm.plan(rows, k, n, tm), old_plan(k, n, tm)
    assert what == gm.Tiles(tm, *tiles)
    matmul_s = 2.0 * rows * k * n / 197e12
    for kernel in gm.KERNELS:
        tiles = getattr(what, kernel)
        for tile, width in zip(tiles, gm.tiled_widths(kernel, k, n)):
            assert tile % 128 == 0 and width % tile == 0, (kernel, tiles)
        assert gm.vmem_bytes(kernel, tm, tiles, 2) <= gm._VMEM_BUDGET
        moved = gm.hbm_bytes(kernel, rows, k, n, tm, tiles, groups, 2)
        assert gm.least_bytes(rows, k, n, groups, 2) <= moved \
            <= gm.hbm_bytes(kernel, rows, k, n, tm, getattr(before, kernel),
                            groups, 2), kernel
        if call.startswith("kimi-vl"):
            assert moved / 819e9 < matmul_s, (kernel, tiles)
    if call.startswith("kimi-vl"):      # what the caps left it with
        slow = [kernel for kernel in gm.KERNELS if gm.hbm_bytes(
            kernel, rows, k, n, tm, getattr(before, kernel), groups, 2)
            / 819e9 > matmul_s]
        assert slow == (["fwd", "dx", "dw"] if k == 2048 else ["dx", "dw"])


def test_the_byte_model_follows_the_grid_orders():
    """`hbm_bytes` by hand at W2 of the Kimi-VL cell (K 1408, N 2048,
    102,400 rows in 200 tiles, 8 groups), in elements."""
    rows, k, n, tm, g = 102400, 1408, 2048, 512, 8
    x, dy, w = rows * k, rows * n, g * k * n

    def moved(kernel, tiles):
        return gm.hbm_bytes(kernel, rows, k, n, tm, tiles, g, 1)

    # forward, grid (N/tn, tiles, K/tk): x once an N tile; the matrices
    # once while K is one block, else once a row tile
    assert moved("fwd", (512, 1408)) == 4 * x + w + dy
    assert moved("fwd", (2048, 128)) == x + 200 * k * n + dy
    # dX, grid (K/tn, tiles, N/tk): dy once a K tile
    assert moved("dx", (128, 2048)) == 11 * dy + w + x
    assert moved("dx", (1408, 2048)) == dy + w + x == gm.least_bytes(
        rows, k, n, g, 1)
    # dW, grid (K/tk, N/tn, tiles): x once an N tile, dy once a K tile
    assert moved("dw", (128, 512)) == 4 * x + 11 * dy + w
    assert gm.grid("dw", rows, k, n, tm, (128, 512)) == (11, 4, 200)
    assert gm.grid("dx", rows, k, n, tm, (1408, 1024)) == (1, 200, 2)
    # two buffers a block and the float32 sum
    assert gm.vmem_bytes("fwd", tm, (2048, 1408), 2) == 2 * 2 * (
        512 * 1408 + 1408 * 2048 + 512 * 2048) + 4 * 512 * 2048
    assert gm.vmem_bytes("dw", tm, (1408, 512), 2) == 2 * 2 * (
        512 * 1408 + 512 * 512 + 1408 * 512) + 4 * 1408 * 512


def test_a_lowering_records_one_moe_gmm_plan_while_obs_is_on():
    from paddle_tpu.framework import obs
    tm, groups, k, n = 128, 3, 256, 384
    rows = gm.buffer_rows(512, groups, tm)
    x = jnp.zeros((rows, k), jnp.bfloat16)
    w = jnp.zeros((groups, k, n), jnp.bfloat16)
    sizes = jnp.asarray([130, 0, 255], jnp.int32)

    def lower():
        jax.make_jaxpr(lambda x_, w_: gm.grouped_matmul(
            x_, w_, sizes, tm, interpret=True))(x, w)
        return [s["labels"] for s in obs.spans(name="moe_gmm.plan")]

    obs.clear()
    assert lower() == []            # obs off: nothing is recorded
    obs.enable()
    try:
        plans = lower()
    finally:
        obs.disable()
        obs.clear()
    assert len(plans) == 1
    plan = plans[0]
    assert (plan["rows"], plan["k"], plan["n"], plan["groups"], plan["tm"],
            plan["itemsize"]) == (rows, k, n, groups, tm, 2)
    assert plan["least_bytes"] == 2 * (rows * k + rows * n + groups * k * n)
    for kernel, grid in (("fwd", "1x7x1"), ("dx", "1x7x1"), ("dw", "1x1x7")):
        assert plan[kernel + "_tiles"] == {"fwd": "384x256"}.get(
            kernel, "256x384")
        assert plan[kernel + "_grid"] == grid
        assert plan[kernel + "_bytes"] == plan["least_bytes"]
        assert plan[kernel + "_reread"] == 1.0
        assert 0 < plan[kernel + "_vmem"] <= gm._VMEM_BUDGET


# ---------------------------------------------------------------------------
# the expert layer: ops composed as layers.moe_ffn composes them
# ---------------------------------------------------------------------------

def _op(name, ins, attrs=None):
    return get_op(name).fn(None, {k: [v] for k, v in ins.items()},
                           attrs or {})


def moe_by_ops(x, w_r, bias, w13, w2, top_k, held, routed_picks=None,
               norm=True):
    route = _op("moe_route", {"X": x, "W": w_r, "Bias": bias},
                {"top_k": top_k, "norm_topk_prob": norm})
    picks = route["TopE"] if routed_picks is None else routed_picks
    d = _op("moe_dispatch", {"X": x, "TopE": picks},
            {"experts_held": list(held)})
    y = _op("moe_experts", {"Rows": d["Rows"], "W13": w13, "W2": w2,
                            "GroupSizes": d["GroupSizes"],
                            "TileGroup": d["TileGroup"]})["Out"]
    out = _op("moe_combine", {"Y": y, "TopW": route["TopW"],
                              "Pos": d["Pos"], "RowPair": d["RowPair"],
                              "HeldPair": d["HeldPair"],
                              "GroupSizes": d["GroupSizes"]})
    return out["Out"], d["GroupSizes"], picks


def moe_dense(x, w_r, bias, w13, w2, top_k, held, routed_picks=None,
              norm=True):
    """Every held expert over every token, times the token's weight for
    it (0 where it did not pick it)."""
    scores = jax.nn.sigmoid(jnp.dot(x, w_r, precision="highest"))
    _t, picks = jax.lax.top_k(scores + bias, top_k)
    weights = jnp.take_along_axis(scores, picks, 1)
    if norm:
        weights = weights / (weights.sum(1, keepdims=True) + 1e-6)
    if routed_picks is not None:
        picks = routed_picks
    out = jnp.zeros_like(x)
    for g in range(held[1]):
        gate = jnp.sum(weights * (picks == held[0] + g), axis=1)
        a, b = jnp.split(jnp.dot(x, w13[g], precision="highest"), 2, axis=1)
        out += gate[:, None] * jnp.dot(jax.nn.silu(a) * b, w2[g],
                                       precision="highest")
    return out


def _layer_weights(tokens=48, d=16, ff=8, experts=8, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"x": jax.random.normal(k[0], (tokens, d)),
            "w_r": jax.random.normal(k[1], (d, experts)),
            "bias": 0.3 * jax.random.normal(k[2], (experts,)),
            "w13": 0.5 * jax.random.normal(k[3], (experts, d, 2 * ff)),
            "w2": 0.5 * jax.random.normal(k[4], (experts, ff, d))}


def _share(p, held):
    lo, hi = held[0], held[0] + held[1]
    return p["x"], p["w_r"], p["bias"], p["w13"][lo:hi], p["w2"][lo:hi]


def _close(got, want, tol, what):
    """max |got - want| within `tol` of the larger of 1 and max |want|."""
    scale = max(1.0, float(jnp.max(jnp.abs(want))))
    gap = float(jnp.max(jnp.abs(got - want)))
    assert gap <= tol * scale, "%s: %.3g over %.3g" % (what, gap, scale)


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
    got, sizes, picks = moe_by_ops(*args, top_k, held, norm=norm)
    _close(got, moe_dense(*args, top_k, held, norm=norm), 1e-6, "out")
    # the load counts what landed on each held expert
    want = [(np.asarray(picks) == held[0] + g).sum() for g in range(held[1])]
    assert list(np.asarray(sizes)) == want
    cot = jax.random.normal(jax.random.PRNGKey(4), got.shape)
    which = (0, 1, 3, 4)            # x, the router, the experts' matrices
    mine = jax.grad(lambda *a: jnp.sum(moe_by_ops(
        *a, top_k, held, norm=norm)[0] * cot), which)(*args)
    ref = jax.grad(lambda *a: jnp.sum(moe_dense(
        *a, top_k, held, norm=norm) * cot), which)(*args)
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
    got, sizes, _ = moe_by_ops(*args, 2, held, routed_picks=forced)
    want = moe_dense(*args, 2, held, routed_picks=forced)
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
    mine = jax.grad(lambda *a: jnp.sum(moe_by_ops(
        *a, 2, held, routed_picks=forced)[0] * cot), which)(*args)
    ref = jax.grad(lambda *a: jnp.sum(moe_dense(
        *a, 2, held, routed_picks=forced) * cot), which)(*args)
    for name, g, r in zip(("x", "router", "w13", "w2"), mine, ref):
        assert bool(jnp.all(jnp.isfinite(g))), name
        np.testing.assert_allclose(g, r, rtol=2e-4, atol=2e-5, err_msg=name)


def _grouped_matmul_that_leaves_nan(x, w, group_sizes, tm, interpret=None):
    """The XLA form, with what the kernels promise nothing about made as
    bad as it can be: the rows of no group (a tile's padding, the tail
    past the tiles in use) come back NaN from the product and from its
    dX, whatever went in."""
    lay = gm.layout(group_sizes, x.shape[0], tm)
    row = jnp.arange(x.shape[0])
    inside = ((row % tm) < lay["tile_end"][row // tm])[:, None]

    @jax.custom_vjp
    def product(x_, w_):
        return jnp.where(inside, gm.grouped_matmul_xla(
            x_, w_, group_sizes, tm), jnp.nan)

    def fwd(x_, w_):
        return product(x_, w_), (x_, w_)

    def bwd(res, dy):
        _out, vjp = jax.vjp(lambda a, b: gm.grouped_matmul_xla(
            a, b, group_sizes, tm), *res)
        dx, dw = vjp(jnp.where(inside, dy, 0.0))
        return jnp.where(inside, dx, jnp.nan), dw

    product.defvjp(fwd, bwd)
    return product(x, w)


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


# ---------------------------------------------------------------------------
# the rows in use: every pass against PR 32's whole-buffer spelling
# ---------------------------------------------------------------------------

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
    sizes = moe_by_ops(*args, top_k, held, routed_picks=forced)[1]
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


def _tool(*argv, name="mb_moe_rows.py"):
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", name)
    return subprocess.run(
        [sys.executable, tool] + list(argv), capture_output=True, text=True,
        timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))


def test_the_microbenchmark_of_the_row_passes_starts():
    done = _tool("--help")
    assert done.returncode == 0, done.stderr[-2000:]
    for flag in ("--walk-through", "--cell", "--rows-in-use"):
        assert flag in done.stdout, flag
    done = _tool("--cell", "kimi", "--tokens", "64")    # no TPU, no flag
    assert done.returncode == 1 and "not a TPU" in done.stderr


def test_the_microbenchmark_of_the_grouped_matmuls_tiles_walks_through():
    """Off the TPU it exits 1 without the flag; with it, at a tiny size in
    interpret mode: one line a kernel and call at the old tiles, at the
    plan's and at explicit ones (only at the call they divide)."""
    done = _tool("--cell", "kimi-vl", "--tokens", "64",
                 name="mb_gmm_tiles.py")
    assert done.returncode == 1 and "not a TPU" in done.stderr
    done = _tool("--cell", "kimi-vl", "--walk-through", "--tokens", "256",
                 "--d", "256", "--ffn", "128", "--calls", "1", "--tiles",
                 "old", "--tiles", "plan", "--tiles", "dw=256x128",
                 name="mb_gmm_tiles.py")
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.splitlines()
    assert "1536 pairs over 8 groups in a buffer of 2560 rows" in lines[0]
    assert "no device times" in lines[1]
    calls = [i for i, line in enumerate(lines) if line.startswith("call (")]
    assert [lines[i].split(":")[0] for i in calls] \
        == ["call (K 256, N 256)", "call (K 128, N 256)"]
    first = [line.split()[:4] for line in lines[calls[0] + 1:calls[1]]]
    assert first == [[who, "moe_gmm_" + kernel, "tiles", "256x256"]
                     for who in ("old", "plan")
                     for kernel in ("fwd", "dx", "dw")] \
        + [["given", "moe_gmm_dw", "tiles", "256x128"]]
    # 256 does not divide the second call's K of 128: not run there
    assert len(lines) - calls[1] - 1 == 6
    assert all("reread" in line and "roofline" in line
               for line in lines[calls[0] + 1:calls[1]])


def test_the_microbenchmark_walks_through_both_cells_forms():
    """At a tiny size off the TPU: one line a form, each "in use" form
    under the "whole" form it replaces, at the rows asked for."""
    done = _tool("--cell", "kimi", "--rows-in-use", "150", "--walk-through",
                 "--tokens", "128", "--d", "128", "--ffn", "128",
                 "--calls", "1")
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.splitlines()
    assert "top-8 of 256 experts, 8 held" in lines[0]
    assert "150 pairs landed" in lines[0] and "bounded=1" in lines[0]
    assert "no device times" in lines[1]
    names = [line[:66].strip() for line in lines[2:]]
    for whole, in_use in (("X -> buffer: one take", "X -> buffer, in use"),
                          ("buffer -> tokens, whole",
                           "buffer -> tokens, in use"),
                          ("moe_combine's backward, whole",
                           "moe_combine's backward, in use"),
                          ("silu(gate) * up, whole", "silu(gate) * up, in use"),
                          ("its backward, whole", "its backward, in use")):
        at = [i for i, name in enumerate(names) if name.startswith(whole)]
        assert at and names[at[0] + 1].startswith(in_use), (whole, names)
    assert all(line.rstrip().endswith("GB/s") for line in lines[2:])


# ---------------------------------------------------------------------------
# through a Program
# ---------------------------------------------------------------------------

def _run(build, feed):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        fetch = build()
    exe = pt.Executor()
    exe.run(startup)
    names = sorted(fetch)
    out = exe.run(main, feed=feed, fetch_list=[fetch[n] for n in names])
    return dict(zip(names, out)), main


@pytest.mark.parametrize("held", [None, (4, 4)])
def test_moe_ffn_through_a_program_with_its_gradients(held):
    x = np.random.RandomState(0).randn(24, 16).astype(np.float32)
    first, count = held or (0, 8)

    def build():
        xv = layers.data("x", [24, 16], dtype="float32",
                         append_batch_size=False)
        xv.stop_gradient = False
        out, load = layers.moe_ffn(xv, 8, 2, 8, experts_held=held,
                                   name="moe")
        layers.moe_balance(load, "moe", held)
        block = pt.default_main_program().global_block()
        params = [block.var(n) for n in ("moe_router.w_0",
                                         "moe_experts_gate_up",
                                         "moe_experts_down")]
        loss = layers.reduce_sum(layers.elementwise_mul(out, out))
        grads = pt.gradients([loss], [xv] + params)
        return dict({"out": out, "load": block.var("moe_expert_load"),
                     "bias": block.var("moe_expert_bias")},
                    **{"w%d" % i: v for i, v in enumerate(params)},
                    **{"g%d" % i: g for i, g in enumerate(grads)})

    got, main = _run(build, {"x": x})
    names = {p.name for p in main.global_block().all_parameters()}
    assert names == {"moe_router.w_0", "moe_experts_gate_up",
                     "moe_experts_down"}        # the bias is no Parameter
    assert got["w1"].shape == (count, 16, 16) and got["w2"].shape \
        == (count, 8, 16) and got["w0"].dtype == np.float32
    assert (got["bias"] == 0).all() and got["bias"].shape == (8,)
    args = (jnp.asarray(x), got["w0"], got["bias"], got["w1"], got["w2"])
    want = moe_dense(*args, 2, (first, count))
    np.testing.assert_allclose(got["out"], want, rtol=1e-4, atol=1e-5)
    picks = jax.lax.top_k(jax.nn.sigmoid(x @ got["w0"]), 2)[1]
    assert list(got["load"]) == [int((picks == e).sum()) for e in range(8)]
    ref = jax.grad(lambda *a: jnp.sum(moe_dense(*a, 2, (first, count))
                                      ** 2), (0, 1, 3, 4))(*args)
    for i, r in enumerate(ref):
        np.testing.assert_allclose(got["g%d" % i], r, rtol=3e-4, atol=3e-5)


@pytest.mark.parametrize("kw,match", [
    (dict(experts_held=(6, 4)), "no range"),
    (dict(experts_held=(0, 0)), "no range"),
    (dict(top_k=9), "top_k 9")])
def test_moe_ffn_refuses_what_it_cannot_hold(kw, match):
    with pt.program_guard(pt.Program(), pt.Program()):
        xv = layers.data("x", [8, 16], dtype="float32",
                         append_batch_size=False)
        with pytest.raises(ValueError, match=match):
            layers.moe_ffn(xv, 8, kw.pop("top_k", 2), 8, **kw)


def test_moe_balance_inside_a_segment_is_refused_by_name():
    with pt.program_guard(pt.Program(), pt.Program()):
        xv = layers.data("x", [8, 16], dtype="float32",
                         append_batch_size=False)

        def segment(h):
            out, load = layers.moe_ffn(h, 4, 2, 8, name="seg")
            layers.moe_balance(load, "seg")
            return out

        with pytest.raises(ValueError, match="where the segment's results"):
            layers.recompute_segment(segment, [xv])


def test_moe_bias_update_is_the_loss_free_balance_step():
    """+ rate under the mean load, - rate over it, nothing at it; the op
    takes no gradient."""
    from paddle_tpu.ops.registry import get_op
    op = get_op("moe_bias_update")
    assert not op.differentiable
    bias = jnp.asarray([0.0, 0.5, -0.25, 0.125], jnp.float32)
    load = jnp.asarray([10, 2, 6, 6], jnp.int32)        # mean 6
    out = op.fn(None, {"Bias": [bias], "Load": [load]}, {"rate": 0.01})
    np.testing.assert_allclose(out["Out"],
                               [-0.01, 0.51, -0.25, 0.125], atol=1e-7)


@pytest.mark.parametrize("recompute", [False, True])
def test_moe_balance_moves_the_bias_once_a_step_and_the_picks_follow(
        recompute):
    """A router that sends everything to experts 0 and 1: with the update
    on, the bias of the two falls and the others' rises a step, the
    forward pass (and its replay under recompute) reads the bias the step
    began with, and after enough steps the picks spread."""
    from paddle_tpu import optimizer
    from paddle_tpu.framework.scope import Scope
    x = np.abs(np.random.RandomState(1).randn(32, 16)).astype(np.float32)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        xv = layers.data("x", [32, 16], dtype="float32",
                         append_batch_size=False)

        def segment(h):
            return list(layers.moe_ffn(h, 8, 2, 8, experts_held=(0, 4),
                                       name="moe"))

        out, load = layers.recompute_segment(segment, [xv]) if recompute \
            else segment(xv)
        layers.moe_balance(load, "moe", (0, 4), bias_update_rate=0.05)
        loss = layers.reduce_mean(layers.elementwise_mul(out, out))
        optimizer.SGD(0.0).minimize(loss)       # the weights stay
    scope, exe = Scope(), pt.Executor()
    exe.run(startup, scope=scope)
    router = np.zeros((16, 8), np.float32)
    router[:, :2] = 0.05                        # x > 0: experts 0, 1 win
    scope.set_var("moe_router.w_0", jnp.asarray(router))
    loads, biases = [], []
    for _ in range(12):
        exe.run(main, feed={"x": x}, fetch_list=[loss], scope=scope)
        loads.append(np.asarray(scope.find_var("moe_expert_load")))
        biases.append(np.asarray(scope.find_var("moe_expert_bias")))
    assert exe.cache_misses == 1                # one compiled step
    assert list(loads[0]) == [32, 32, 0, 0, 0, 0, 0, 0]
    np.testing.assert_allclose(biases[0], [-0.05] * 2 + [0.05] * 6,
                               atol=1e-7)
    # the step that wrote biases[0] routed with the zeros it began with
    assert list(loads[1]) != list(loads[0]) or biases[1][0] < biases[0][0]
    assert loads[-1].sum() == 64 and loads[-1].max() < 32
    assert (loads[-1] > 0).sum() > 2


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


def _op_digest(main):
    """Every op of the program by block, type and slot sizes, the expert
    layer's ops (and their `grad_of`s) with their plain attrs too; names
    left out (they count up with the process)."""
    import hashlib
    import json
    rows = []
    for blk in main.blocks:
        for op in blk.ops:
            attrs = {k: v for k, v in sorted(op.attrs.items())
                     if isinstance(v, (int, float, str, bool, type(None)))} \
                if op.type.startswith("moe_") else {}
            rows.append([blk.idx, op.type,
                         {k: len(v) for k, v in sorted(op.inputs.items())},
                         {k: len(v) for k, v in sorted(op.outputs.items())},
                         attrs])
    return len(rows), hashlib.sha256(
        json.dumps(rows, sort_keys=True).encode()).hexdigest()[:16]


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
