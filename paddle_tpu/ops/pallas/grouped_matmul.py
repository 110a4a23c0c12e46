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

Operands go to the MXU in their own dtype, sums are float32. Off the TPU, and
where a width is no multiple of 128 or `tm` no multiple of 16, the same entry
takes the plain XLA form, `jax.lax.ragged_dot` over the same layout
(`grouped_matmul_xla`), which is also what the kernels are tested against.
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


def _divisor(dim, cap):
    """Largest multiple of 128 that divides `dim` and is at most `cap`."""
    best = None
    for t in range(_LANES, min(dim, cap) + 1, _LANES):
        if dim % t == 0:
            best = t
    return best


#: one call's tiles: rows a tile, then (output tile, contraction block) of
#: the forward and of dX, and dW's (K tile, N tile)
Tiles = collections.namedtuple("Tiles", "tm fwd dx dw")


def plan(rows, k, n, tm):
    """The three kernels' tiles for one call, or None where the call takes
    the XLA form."""
    if k % _LANES or n % _LANES or tm % 16 or rows % tm:
        return None
    return Tiles(tm, (_divisor(n, 512), _divisor(k, 2048)),
                 (_divisor(k, 512), _divisor(n, 2048)),
                 (_divisor(k, 1024), _divisor(n, 512)))


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
    what = plan(x.shape[0], x.shape[1], w.shape[2], tm)
    if what is None and not interpret:
        return grouped_matmul_xla(x, w, group_sizes, tm)
    if what is None:        # interpret mode takes any tile that divides
        k, n = x.shape[1], w.shape[2]
        what = Tiles(tm, (n, k), (k, n), (k, n))
    lay = layout(group_sizes, x.shape[0], tm)
    return _grouped(x, w.astype(x.dtype), lay["tile_group"],
                    lay["tile_end"], lay["tiles"], what, bool(interpret))
