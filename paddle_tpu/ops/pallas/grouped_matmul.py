"""Pallas TPU grouped matmul: `[rows, K] x [G, K, N]` by row groups, with
its two backward products. What a dropless expert layer multiplies with.

The rows of group g (an expert's tokens) lie together in a buffer that is
cut into tiles of `tm` rows, and every group starts on a tile: group g owns
`max(ceil(size_g / tm), 1)` tiles, so a tile belongs to one group and an
empty group still owns one (its weight gradient has to be written as zeros
by somebody). `layout` turns the group sizes into that map. The buffer is
sized for the worst case, `buffer_rows`; the tiles past the last group's are
never visited: the grid's row axis is as long as the tiles in use (a traced
number; the tile -> group map rides in as scalar prefetch), so the empty
tail costs nothing and holds whatever the allocator left there. Rows of a
tile past its group's end are padding: the forward and dX compute them from
whatever the buffer holds (the callers keep that finite), dW masks them out.

  moe_gmm_fwd   out[r] = x[r] @ w[group(r)]            grid (N/tn, tiles, K/tk)
  moe_gmm_dx    dx[r]  = dy[r] @ w[group(r)]^T         grid (K/tn, tiles, N/tk)
  moe_gmm_dw    dw[g]  = sum_{r in g} x[r]^T dy[r]     grid (K/tk, N/tn, tiles)

The tile widths decide how often an operand comes out of HBM again, for a
block is fetched whenever its index moves between two grid steps. Forward
and dX read their row operand once an output tile (N/tn, K/tn times) and
the matrices once where the contraction is one block (the block stands
still while consecutive tiles share a group), else once a row tile; dW
reads x once an N tile and dy once a K tile. `plan` counts those bytes
(`hbm_bytes`) for every pair of 128-multiple divisors of the two widths,
the widths themselves included, and takes the pair that moves the fewest
among those whose blocks fit half the VMEM limit (`vmem_bytes`): at an
expert width of 1408 = 11 x 128, whose only other such divisor is 128, that
is the whole width (PERF.md section 6, PR 41). With obs on a lowering
records what it picked as `moe_gmm.plan`.

A width that is no multiple of 128 (Nemotron-H's experts are 1856 = 14.5 x
128 wide) has ONE tile, the whole width: a block as wide as its array is
the one block shape Mosaic takes off the 128-lane grid, it lays the block
out on 15 x 128 lanes in VMEM and masks the last half-vreg itself, in the
loads, the stores and the contraction alike. Nothing is padded in HBM: the
leaf, the row buffer and the FLOPs are the published width's, and
`vmem_bytes` counts the lanes the block really takes. Such a width must
still be a multiple of 8 (a matrix block's second-to-last axis).

Operands go to the MXU in their own dtype, sums are float32. Off the TPU, and
where a width is no multiple of 8, `tm` no multiple of 16, or no pair of
tiles fits VMEM, the same entry takes the plain XLA form,
`jax.lax.ragged_dot` over the same layout (`grouped_matmul_xla`), which is
also what the kernels are tested against.
"""
import collections
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
except Exception:  # pragma: no cover
    pltpu = None

from .interpret import default_interpret

_LANES = 128
_VMEM_LIMIT = 64 * 2 ** 20
#: what `plan` lets a kernel's blocks and accumulator take of it; the rest
#: is the compiler's (dW's row mask, the product before it is added)
_VMEM_BUDGET = _VMEM_LIMIT // 2


def row_tile(pairs):
    """Rows a tile of the buffer holds, from the (token, pick) pairs a call
    routes: 512 at training sizes, less where that would be mostly padding."""
    if pairs >= 8192:
        return 512
    return 128 if pairs >= 1024 else 8


def buffer_rows(pairs, groups, tm):
    """Rows that hold any split of `pairs` rows over `groups` groups, each
    starting on a tile: ceil(s/tm) tm <= s + tm - 1, and an empty group's
    one tile is tm."""
    return -(-pairs // tm) * tm + groups * tm


def layout(group_sizes, rows, tm):
    """{"starts": first buffer row of each group, "tile_group": the group
    each of the rows/tm tiles belongs to (the last group's past the tiles in
    use), "tile_end": rows of each tile that belong to its group (0 past the
    tiles in use), "tiles": how many tiles are in use}."""
    sizes = group_sizes.astype(jnp.int32)
    tiles = jnp.maximum(-(-sizes // tm), 1)
    upto = jnp.cumsum(tiles)
    starts = (upto - tiles) * tm
    tile = jnp.arange(rows // tm, dtype=jnp.int32)
    tile_group = jnp.minimum(
        jnp.searchsorted(upto, tile, side="right").astype(jnp.int32),
        sizes.shape[0] - 1)
    left = starts[tile_group] + sizes[tile_group] - tile * tm
    tile_end = jnp.where(tile < upto[-1], jnp.clip(left, 0, tm), 0)
    return {"starts": starts, "tile_group": tile_group,
            "tile_end": tile_end.astype(jnp.int32),
            "tiles": upto[-1].astype(jnp.int32)}


#: one call's tiles: rows a tile, then (output tile, contraction block) of
#: the forward and of dX, and dW's (K tile, N tile)
Tiles = collections.namedtuple("Tiles", "tm fwd dx dw")

KERNELS = ("fwd", "dx", "dw")


def _divisors(dim):
    """Every multiple of 128 that divides `dim`, and `dim` itself (the one
    tile of a width off the 128-lane grid)."""
    return [t for t in range(_LANES, dim, _LANES) if dim % t == 0] + [dim]


def _lanes(width):
    """Lanes a block `width` wide takes in VMEM: whole vregs of 128."""
    return -(-width // _LANES) * _LANES


def tiled_widths(kernel, k, n):
    """The two tiled widths of a kernel in the order of its pair of tiles:
    (output width, contraction width) of the forward and of dX, (K, N) of
    dW."""
    return {"fwd": (n, k), "dx": (k, n), "dw": (k, n)}[kernel]


def grid(kernel, rows, k, n, tm, tiles):
    """The kernel's grid over a buffer of `rows` rows (its row axis is as
    long as the tiles in use, `rows / tm` at most)."""
    a, b = tiled_widths(kernel, k, n)
    ta, tb = tiles
    return (a // ta, b // tb, rows // tm) if kernel == "dw" \
        else (a // ta, rows // tm, b // tb)


def hbm_bytes(kernel, rows, k, n, tm, tiles, groups, itemsize):
    """Bytes the kernel moves between HBM and VMEM over `rows` rows at
    these tiles (the header's re-read rules): a block is fetched again
    whenever its index moves from one grid step to the next, and an output
    block is written once."""
    a, b = tiled_widths(kernel, k, n)
    ta, tb = tiles
    if kernel == "dw":      # x once an N tile, dy once a K tile, dw written
        return (rows * k * (n // tb) + rows * n * (k // ta)
                + groups * k * n) * itemsize
    # the row operand once an output tile; the matrices once where the
    # contraction is one block (their block stands still inside a group),
    # else once a row tile; the rows of the result written
    matrices = groups if tb == b else rows // tm
    return (rows * b * (a // ta) + matrices * k * n + rows * a) * itemsize


def least_bytes(rows, k, n, groups, itemsize):
    """What any of the three has to move: each row and each matrix once."""
    return (rows * k + rows * n + groups * k * n) * itemsize


def vmem_bytes(kernel, tm, tiles, itemsize):
    """VMEM the kernel's pipeline holds: two buffers of each operand block
    and of the output block (the three blocks are the three faces of
    tm x ta x tb in every kernel), and the float32 accumulator, which has
    the output block's shape."""
    ta, tb = (_lanes(t) for t in tiles)
    acc = ta * tb if kernel == "dw" else tm * ta
    return 2 * (tm * ta + tm * tb + ta * tb) * itemsize + 4 * acc


def _pick(kernel, rows, k, n, tm, itemsize):
    """The pair of tiles that moves the fewest bytes within the VMEM
    budget; among equals the fewest grid steps."""
    a, b = tiled_widths(kernel, k, n)
    best = None
    for ta in _divisors(a):
        for tb in _divisors(b):
            if vmem_bytes(kernel, tm, (ta, tb), itemsize) > _VMEM_BUDGET:
                continue
            # the groups are not plan's to know: one, the least there is
            key = (hbm_bytes(kernel, rows, k, n, tm, (ta, tb), 1, itemsize),
                   (a // ta) * (b // tb))
            if best is None or key < best[0]:
                best = (key, (ta, tb))
    return best and best[1]


def plan(rows, k, n, tm, itemsize=2):
    """The three kernels' tiles for one call, or None where the call takes
    the XLA form. Decided from the shapes alone: a tile is any multiple of
    128 that divides its width, or the width itself (the only tile of a
    width that is no multiple of 128); of each kernel's pairs that fit
    `_VMEM_BUDGET` (`vmem_bytes`) the one that moves the fewest HBM bytes
    (`hbm_bytes`) over the buffer's rows wins. `tm` is the caller's
    (`row_tile`): the buffer and the layout hang on it."""
    if k % 8 or n % 8 or tm % 16 or rows % tm:
        return None
    picked = [_pick(kernel, rows, k, n, tm, itemsize) for kernel in KERNELS]
    if None in picked:
        return None
    return Tiles(tm, *picked)


def describe(what, rows, k, n, groups, itemsize):
    """`moe_gmm.plan`'s labels (ints, floats and strings): the call, and
    for each kernel its tiles, its grid over the buffer, the bytes it moves
    by `hbm_bytes`, their ratio to the least there is (`reread`) and its
    VMEM."""
    least = least_bytes(rows, k, n, groups, itemsize)
    out = {"rows": rows, "k": k, "n": n, "groups": groups, "tm": what.tm,
           "itemsize": itemsize, "least_bytes": least}
    for kernel in KERNELS:
        tiles = getattr(what, kernel)
        moved = hbm_bytes(kernel, rows, k, n, what.tm, tiles, groups,
                          itemsize)
        out.update({
            kernel + "_tiles": "%dx%d" % tiles,
            kernel + "_grid": "%dx%dx%d" % grid(kernel, rows, k, n,
                                                what.tm, tiles),
            kernel + "_bytes": moved,
            kernel + "_reread": round(moved / least, 3),
            kernel + "_vmem": vmem_bytes(kernel, what.tm, tiles, itemsize)})
    return out


def _params(semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _gmm(x, w, tile_group, tiles, tm, tn, tk, transpose_w, interpret):
    """x [rows, C] times w[g] ([C, n], or [n, C] with `transpose_w`) by
    tile; C is walked in blocks of tk into a float32 accumulator."""
    rows, c = x.shape
    n = w.shape[1] if transpose_w else w.shape[2]
    last = c // tk - 1
    dims = (((1,), (1,)), ((), ())) if transpose_w \
        else (((1,), (0,)), ((), ()))

    def kernel(_tile_group, x_ref, w_ref, out_ref, acc):
        ci = pl.program_id(2)

        @pl.when(ci == 0)
        def _():
            acc[...] = jnp.zeros_like(acc)

        acc[...] += jax.lax.dot_general(
            x_ref[...], w_ref[...], dims,
            preferred_element_type=jnp.float32)

        @pl.when(ci == last)
        def _():
            out_ref[...] = acc[...].astype(out_ref.dtype)

    w_spec = pl.BlockSpec((None, tn, tk),
                          lambda ni, ti, ci, tg: (tg[ti], ni, ci)) \
        if transpose_w else pl.BlockSpec(
            (None, tk, tn), lambda ni, ti, ci, tg: (tg[ti], ci, ni))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk),
                                   lambda ni, ti, ci, tg: (ti, ci)),
                      w_spec],
            out_specs=pl.BlockSpec((tm, tn),
                                   lambda ni, ti, ci, tg: (ti, ni)),
            grid=(n // tn, tiles, c // tk),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=_params(("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="moe_gmm_dx" if transpose_w else "moe_gmm_fwd",
    )(tile_group, x, w)


def _gmm_dw(x, dy, tile_group, tile_end, tiles, groups, tm, tk, tn,
            interpret):
    """dw[g] = x_g^T dy_g over the tiles of group g, the rows past the
    group's end masked out of x."""
    k, n = x.shape[1], dy.shape[1]

    def kernel(tg, te, x_ref, dy_ref, out_ref, acc):
        ti = pl.program_id(2)
        group = tg[ti]
        first = jnp.logical_or(ti == 0, tg[jnp.maximum(ti - 1, 0)] != group)
        final = jnp.logical_or(
            ti == pl.num_programs(2) - 1,
            tg[jnp.minimum(ti + 1, pl.num_programs(2) - 1)] != group)

        @pl.when(first)
        def _():
            acc[...] = jnp.zeros_like(acc)

        @pl.when(te[ti] > 0)
        def _():
            row = jax.lax.broadcasted_iota(jnp.int32, (tm, tk), 0)
            xs = jnp.where(row < te[ti], x_ref[...],
                           jnp.zeros_like(x_ref))
            acc[...] += jax.lax.dot_general(
                xs, dy_ref[...], (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(final)
        def _():
            out_ref[...] = acc[...].astype(out_ref.dtype)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((groups, k, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[pl.BlockSpec((tm, tk),
                                   lambda ki, ni, ti, tg, te: (ti, ki)),
                      pl.BlockSpec((tm, tn),
                                   lambda ki, ni, ti, tg, te: (ti, ni))],
            out_specs=pl.BlockSpec(
                (None, tk, tn), lambda ki, ni, ti, tg, te: (tg[ti], ki, ni)),
            grid=(k // tk, n // tn, tiles),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="moe_gmm_dw",
    )(tile_group, tile_end, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _grouped(x, w, tile_group, tile_end, tiles, what, interpret):
    tn, tk = what.fwd
    return _gmm(x, w, tile_group, tiles, what.tm, tn, tk, False, interpret)


def _grouped_fwd(x, w, tile_group, tile_end, tiles, what, interpret):
    out = _grouped(x, w, tile_group, tile_end, tiles, what, interpret)
    return out, (x, w, tile_group, tile_end, tiles)


def _grouped_bwd(what, interpret, res, dy):
    x, w, tile_group, tile_end, tiles = res
    dy = dy.astype(x.dtype)
    tn, tk = what.dx
    dx = _gmm(dy, w, tile_group, tiles, what.tm, tn, tk, True, interpret)
    tk, tn = what.dw
    dw = _gmm_dw(x, dy, tile_group, tile_end, tiles, w.shape[0], what.tm,
                 tk, tn, interpret)
    return dx, dw.astype(w.dtype), None, None, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul_xla(x, w, group_sizes, tm):
    """The same product in plain XLA over the same layout: the padding rows
    are zeroed, then `ragged_dot` over each group's whole tiles."""
    lay = layout(group_sizes, x.shape[0], tm)
    row = jnp.arange(x.shape[0], dtype=jnp.int32)
    inside = (row % tm) < lay["tile_end"][row // tm]
    whole = jnp.maximum(-(-group_sizes.astype(jnp.int32) // tm), 1) * tm
    return jax.lax.ragged_dot(
        jnp.where(inside[:, None], x, jnp.zeros_like(x)), w, whole,
        preferred_element_type=jnp.float32).astype(x.dtype)


def grouped_matmul(x, w, group_sizes, tm, interpret=None):
    """out[r] = x[r] @ w[g] for the rows r of group g, x [rows, K] laid out
    as `layout(group_sizes, rows, tm)` says, w [G, K, N], group_sizes [G]
    int32. Rows of no group come back as anything (zeros in the XLA form).
    On the TPU the Pallas kernels where `plan` finds tiles; off it
    (`interpret` unset on another backend) the XLA form. `interpret=True`
    runs the kernels in interpret mode (the tests)."""
    if x.ndim != 2 or w.ndim != 3 or x.shape[1] != w.shape[1] \
            or group_sizes.shape != (w.shape[0],):
        raise ValueError(
            "grouped_matmul: x %r, w %r, group_sizes %r do not fit "
            "(rows, K), (G, K, N), (G,)"
            % (x.shape, w.shape, group_sizes.shape))
    if interpret is None:
        interpret = default_interpret()
        if interpret:
            return grouped_matmul_xla(x, w, group_sizes, tm)
    what = plan(x.shape[0], x.shape[1], w.shape[2], tm, x.dtype.itemsize)
    if what is None and not interpret:
        return grouped_matmul_xla(x, w, group_sizes, tm)
    if what is None:        # interpret mode takes any tile that divides
        k, n = x.shape[1], w.shape[2]
        what = Tiles(tm, (n, k), (k, n), (k, n))
    from ...framework import obs
    if obs.enabled():
        now = obs.now()
        obs.record("moe_gmm.plan", now, now, **describe(
            what, x.shape[0], x.shape[1], w.shape[2], w.shape[0],
            x.dtype.itemsize))
    lay = layout(group_sizes, x.shape[0], tm)
    return _grouped(x, w.astype(x.dtype), lay["tile_group"],
                    lay["tile_end"], lay["tiles"], what, bool(interpret))
