"""The grouped matmul (Pallas kernels in interpret mode, and the XLA form)
against a dense per-row product in value and gradient, and its tile
planner: the tiles `plan` takes at the cells' calls, the byte model, the
`moe_gmm.plan` record."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import grouped_matmul as gm


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
