"""Pallas TPU selective scan (the Mamba-1 state-space recurrence).

For each batch row and channel e, with a state of N numbers:

    h_t = exp(delta_t[e] * A[e, :]) * h_{t-1} + (delta_t[e] * x_t[e]) * B_t
    y_t[e] = h_t . C_t + D[e] * x_t[e]

x, delta: (B, T, E) in the model's dtype; A: (E, N) float32 (negative);
B, C: (B, T, N); D: (E,). The (T, E, N) states never touch HBM: a
`lax.scan` over T steps, or an associative scan that materialises them,
is not a training path at T = 8192, E = 5120 (2.7 GB a layer in float32).

Forward: grid (B, E/EB, T/L); the innermost axis walks the sequence in
chunks of L steps and is sequential, the state (N, EB) float32 lives in
VMEM scratch across it (N on sublanes, channels on lanes). Each grid step
runs its L time steps on 128-channel columns, a `fori_loop` over groups of
eight steps unrolled inside (Mosaic loads a dynamic row range only where it
is a whole aligned tile), and also writes the state its chunk STARTED from,
(B, T/L, N, E) float32 (10 MB a layer at the sizes above): what the
backward restarts from.

Backward: the same grid with the chunks in reverse. A grid step first
recomputes its chunk's L states from the saved start into VMEM (never HBM),
then walks the chunk backwards carrying g_t = dL/dh_t, and forms dx,
ddelta, dA, dD and the per-lane parts of dB and dC. dB_t[n] and dC_t[n]
are sums over ALL channels: each grid step reduces its own EB channels (the
lane sums by one matmul against ones a chunk, on the idle MXU) and XLA
adds the E/EB parts.

B and C reach the kernels broadcast over 128 lanes, (B, T, N, 128) float32,
made by XLA (67 MB each at the sizes above): a step then reads its (N, 128)
tile whole and needs no lane broadcast. State, exp and all sums are
float32; x, delta, y and their cotangents travel in the model's dtype.

Off the TPU the same entry runs a chunked `lax.scan` with `jax.checkpoint`
per chunk (`scan_xla`), which is also what the kernels are tested against.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
    _HAS_TPU_PALLAS = True
except Exception:  # pragma: no cover
    pltpu = None
    _HAS_TPU_PALLAS = False

from .interpret import default_interpret

_LANES = 128
_VMEM_BUDGET = 24 * 2 ** 20     # what one backward grid step may hold
_VMEM_MARGIN = 8 * 2 ** 20      # Mosaic's own temporaries
_HIGHEST = jax.lax.Precision.HIGHEST


def pick_channel_block(e):
    """Channels a grid step holds: the widest of 512, 256, 128 that
    divides E (the state is then N x EB float32: 8 vregs at N=16,
    EB=512); None where E is not a multiple of 128."""
    for eb in (512, 256, 128):
        if e % eb == 0:
            return eb
    return None


def vmem_bytes(kernel, chunk, e_blk, n, itemsize):
    """Upper reckoning of what one grid step of `kernel` ("fwd" | "bwd")
    holds in VMEM: every block twice (the pipeline's two buffers), the
    float32 working copies of the chunk's rows, and for the backward the
    chunk's recomputed states."""
    row_f32 = chunk * e_blk * 4
    row_io = chunk * e_blk * itemsize
    bc = chunk * n * _LANES * 4
    state = n * e_blk * 4
    if kernel == "fwd":
        blocks = 3 * row_io + 2 * bc + 3 * state
        scratch = 3 * row_f32 + state
    else:
        blocks = 5 * row_io + 2 * bc + 4 * state + 2 * 8 * chunk * n * 4
        scratch = (5 * row_f32 + (chunk + 1) * state + 2 * bc + 3 * state)
    return 2 * blocks + scratch


def pick_chunk(t, e_blk, n, itemsize):
    """Time steps a grid step walks: the largest power of two up to 256
    whose backward step fits `_VMEM_BUDGET`, and no longer than the
    sequence (rounded up to 16 rows, a bfloat16 tile)."""
    chunk = 256
    while chunk > 16 and (vmem_bytes("bwd", chunk, e_blk, n, itemsize)
                          > _VMEM_BUDGET or chunk // 2 >= t):
        chunk //= 2
    return chunk


def _compiler_params(kernel, chunk, e_blk, n, itemsize):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=vmem_bytes(kernel, chunk, e_blk, n, itemsize)
        + _VMEM_MARGIN)


def _columns(e_blk):
    return [slice(j * _LANES, (j + 1) * _LANES)
            for j in range(e_blk // _LANES)]


_GROUP = 8      # time steps a loop iteration unrolls: one f32 tile of rows


def _group_rows(g):
    """Rows [8g, 8g+8) of a (chunk, EB) buffer: Mosaic loads a dynamic
    row range only where it is a whole aligned tile, so the loops walk
    the chunk in groups of eight steps and unroll the eight."""
    return pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)


def _put_row(tile, r, row):
    """`tile` (8, 128) with its row `r` replaced by `row` (1, 128)."""
    at = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0) == r
    return jnp.where(at, row, tile)


def _fwd_kernel(x_ref, d_ref, a_ref, b_ref, c_ref, dd_ref, y_ref, hs_ref,
                h_sc, xf, df, yf, *, chunk):
    cols = _columns(h_sc.shape[1])

    @pl.when(pl.program_id(2) == 0)
    def _init():
        h_sc[:] = jnp.zeros_like(h_sc)

    hs_ref[0, 0] = h_sc[:]
    xf[:] = x_ref[0].astype(jnp.float32)
    df[:] = d_ref[0].astype(jnp.float32)

    def group(g, hs):
        hs = list(hs)
        for j, col in enumerate(cols):
            d8, x8 = df[_group_rows(g), col], xf[_group_rows(g), col]
            a_col, d_col = a_ref[:, col], dd_ref[:, col]
            y8 = jnp.zeros((_GROUP, _LANES), jnp.float32)
            h = hs[j]
            for r in range(_GROUP):
                t = g * _GROUP + r
                d, x = d8[r:r + 1], x8[r:r + 1]         # (1, 128)
                h = jnp.exp(d * a_col) * h + (d * x) * b_ref[0, t]
                y8 = _put_row(y8, r, jnp.sum(h * c_ref[0, t], axis=0,
                                             keepdims=True) + d_col * x)
            yf[_group_rows(g), col] = y8
            hs[j] = h
        return tuple(hs)

    hs = jax.lax.fori_loop(0, chunk // _GROUP, group,
                           tuple(h_sc[:, col] for col in cols))
    for col, h in zip(cols, hs):
        h_sc[:, col] = h
    y_ref[0] = yf[:].astype(y_ref.dtype)


def _bwd_kernel(x_ref, d_ref, a_ref, b_ref, c_ref, dd_ref, dy_ref, hs_ref,
                dx_ref, ddl_ref, da_ref, ddd_ref, db_ref, dc_ref,
                g_sc, da_sc, ddd_sc, h_all, xf, df, dyf, dxf, ddf, bbuf,
                cbuf, *, chunk):
    n, e_blk = g_sc.shape
    cols = _columns(e_blk)
    c = pl.program_id(2)
    groups = chunk // _GROUP

    @pl.when(c == 0)
    def _init():
        g_sc[:] = jnp.zeros_like(g_sc)
        da_sc[:] = jnp.zeros_like(da_sc)
        ddd_sc[:] = jnp.zeros_like(ddd_sc)

    xf[:] = x_ref[0].astype(jnp.float32)
    df[:] = d_ref[0].astype(jnp.float32)
    dyf[:] = dy_ref[0].astype(jnp.float32)

    def rows(t):
        return pl.ds(pl.multiple_of(t * n, n), n)

    # the chunk's states again, from the state it started from: h_all's
    # rows [t*n, (t+1)*n) hold h_{t-1}, so row block 0 is the start
    h_all[pl.ds(0, n), :] = hs_ref[0, 0]

    def replay(g, hs):
        hs = list(hs)
        for j, col in enumerate(cols):
            d8, x8 = df[_group_rows(g), col], xf[_group_rows(g), col]
            a_col = a_ref[:, col]
            h = hs[j]
            for r in range(_GROUP):
                t = g * _GROUP + r
                d, x = d8[r:r + 1], x8[r:r + 1]
                h = jnp.exp(d * a_col) * h + (d * x) * b_ref[0, t]
                h_all[rows(t + 1), col] = h
            hs[j] = h
        return tuple(hs)

    jax.lax.fori_loop(0, groups, replay,
                      tuple(hs_ref[0, 0, :, col] for col in cols))
    bbuf[:] = jnp.zeros_like(bbuf)
    cbuf[:] = jnp.zeros_like(cbuf)

    def back(i, carry):
        gs, das, dds = (list(z) for z in carry)
        g8 = groups - 1 - i
        for j, col in enumerate(cols):
            d8, x8 = df[_group_rows(g8), col], xf[_group_rows(g8), col]
            dy8 = dyf[_group_rows(g8), col]
            a_col, d_col = a_ref[:, col], dd_ref[:, col]
            dx8 = jnp.zeros((_GROUP, _LANES), jnp.float32)
            dd8 = jnp.zeros((_GROUP, _LANES), jnp.float32)
            g_next, da, dd = gs[j], das[j], dds[j]
            for r in reversed(range(_GROUP)):
                t = g8 * _GROUP + r
                d, x, dy = d8[r:r + 1], x8[r:r + 1], dy8[r:r + 1]
                bt, ct = b_ref[0, t], c_ref[0, t]
                a = jnp.exp(d * a_col)
                g = ct * dy + g_next                    # dL/dh_t
                cbuf[rows(t), :] = cbuf[rows(t), :] \
                    + h_all[rows(t + 1), col] * dy
                bbuf[rows(t), :] = bbuf[rows(t), :] + g * (d * x)
                ddx = jnp.sum(g * bt, axis=0, keepdims=True)  # d(delta*x)
                daa = g * h_all[rows(t), col] * a       # dL/d(delta*A)
                dd8 = _put_row(dd8, r, jnp.sum(daa * a_col, axis=0,
                                               keepdims=True) + ddx * x)
                dx8 = _put_row(dx8, r, ddx * d + d_col * dy)
                g_next = a * g
                da = da + daa * d
                dd = dd + dy * x
            ddf[_group_rows(g8), col] = dd8
            dxf[_group_rows(g8), col] = dx8
            gs[j], das[j], dds[j] = g_next, da, dd
        return tuple(gs), tuple(das), tuple(dds)

    zeros_n = tuple(jnp.zeros((n, _LANES), jnp.float32) for _ in cols)
    zeros_1 = tuple(jnp.zeros((1, _LANES), jnp.float32) for _ in cols)
    gs, das, dds = jax.lax.fori_loop(
        0, groups, back,
        (tuple(g_sc[:, col] for col in cols), zeros_n, zeros_1))
    for col, g, da, dd in zip(cols, gs, das, dds):
        g_sc[:, col] = g
        da_sc[:, col] = da_sc[:, col] + da
        ddd_sc[:, col] = ddd_sc[:, col] + dd

    dx_ref[0] = dxf[:].astype(dx_ref.dtype)
    ddl_ref[0] = ddf[:].astype(ddl_ref.dtype)
    # lane sums of the chunk's (L*N, 128) parts by one matmul against
    # ones: row r of the result holds every (t, n)'s sum along its lanes
    ones = jnp.ones((8, _LANES), jnp.float32)
    contract_lanes = (((1,), (1,)), ((), ()))
    db_ref[0, 0] = jax.lax.dot_general(
        ones, bbuf[:], contract_lanes, precision=_HIGHEST,
        preferred_element_type=jnp.float32)
    dc_ref[0, 0] = jax.lax.dot_general(
        ones, cbuf[:], contract_lanes, precision=_HIGHEST,
        preferred_element_type=jnp.float32)

    @pl.when(c == pl.num_programs(2) - 1)
    def _finalize():
        da_ref[0] = da_sc[:]
        ddd_ref[0] = ddd_sc[:]


def _pad_time(x, t_pad):
    return jnp.pad(x, ((0, 0), (0, t_pad - x.shape[1]), (0, 0)))


def _lane_broadcast(m, t_pad):
    """(B, T, N) -> (B, T_pad, N, 128) float32, zero rows past T."""
    m = _pad_time(m.astype(jnp.float32), t_pad)
    return jnp.broadcast_to(m[..., None], m.shape + (_LANES,))


def _specs(chunk, e_blk, n, nc, reverse):
    """BlockSpecs by operand kind for grid (b, e, c): `row` (B, T, E)
    tensors, `a` the (N, E) transposed A, `bc` the lane-broadcast B and C,
    `d` the (1, E) skip weights, `hs` the chunk-start states. `reverse`
    walks the chunks last to first."""
    def ci(c):
        return nc - 1 - c if reverse else c

    return {
        "row": pl.BlockSpec((1, chunk, e_blk), lambda b, e, c: (b, ci(c), e)),
        "a": pl.BlockSpec((n, e_blk), lambda b, e, c: (0, e)),
        "bc": pl.BlockSpec((1, chunk, n, _LANES),
                           lambda b, e, c: (b, ci(c), 0, 0)),
        "d": pl.BlockSpec((1, e_blk), lambda b, e, c: (0, e)),
        "hs": pl.BlockSpec((1, 1, n, e_blk),
                           lambda b, e, c: (b, ci(c), 0, e)),
    }


def _pallas_forward(x, delta, a_t, bx, cx, d_row, chunk, e_blk, interpret):
    if not _HAS_TPU_PALLAS:
        raise NotImplementedError("pallas tpu backend unavailable")
    b, t_pad, e = x.shape
    n, nc = a_t.shape[0], t_pad // chunk
    sp = _specs(chunk, e_blk, n, nc, reverse=False)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk),
        grid=(b, e // e_blk, nc),
        in_specs=[sp["row"], sp["row"], sp["a"], sp["bc"], sp["bc"],
                  sp["d"]],
        out_specs=[sp["row"], sp["hs"]],
        out_shape=[jax.ShapeDtypeStruct((b, t_pad, e), x.dtype),
                   jax.ShapeDtypeStruct((b, nc, n, e), f32)],
        scratch_shapes=[pltpu.VMEM((n, e_blk), f32)]
        + [pltpu.VMEM((chunk, e_blk), f32)] * 3,
        compiler_params=_compiler_params("fwd", chunk, e_blk, n,
                                         x.dtype.itemsize),
        name="ssm_scan_fwd",
        interpret=interpret,
    )(x, delta, a_t, bx, cx, d_row)


def _pallas_backward(x, delta, a_t, bx, cx, d_row, dy, hs, chunk, e_blk,
                     interpret):
    b, t_pad, e = x.shape
    n, nc, ne = a_t.shape[0], t_pad // chunk, e // e_blk
    sp = _specs(chunk, e_blk, n, nc, reverse=True)
    f32 = jnp.float32
    per_batch = pl.BlockSpec((1, n, e_blk), lambda b_, e_, c: (b_, 0, e_))
    per_batch_row = pl.BlockSpec((1, 1, e_blk),
                                 lambda b_, e_, c: (b_, 0, e_))
    parts = pl.BlockSpec((1, 1, 8, chunk * n),
                         lambda b_, e_, c: (b_, e_, 0, nc - 1 - c))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk),
        grid=(b, ne, nc),
        in_specs=[sp["row"], sp["row"], sp["a"], sp["bc"], sp["bc"],
                  sp["d"], sp["row"], sp["hs"]],
        out_specs=[sp["row"], sp["row"], per_batch, per_batch_row, parts,
                   parts],
        out_shape=[jax.ShapeDtypeStruct((b, t_pad, e), x.dtype),
                   jax.ShapeDtypeStruct((b, t_pad, e), delta.dtype),
                   jax.ShapeDtypeStruct((b, n, e), f32),
                   jax.ShapeDtypeStruct((b, 1, e), f32),
                   jax.ShapeDtypeStruct((b, ne, 8, t_pad * n), f32),
                   jax.ShapeDtypeStruct((b, ne, 8, t_pad * n), f32)],
        scratch_shapes=[pltpu.VMEM((n, e_blk), f32),
                        pltpu.VMEM((n, e_blk), f32),
                        pltpu.VMEM((1, e_blk), f32),
                        pltpu.VMEM(((chunk + 1) * n, e_blk), f32)]
        + [pltpu.VMEM((chunk, e_blk), f32)] * 5
        + [pltpu.VMEM((chunk * n, _LANES), f32)] * 2,
        compiler_params=_compiler_params("bwd", chunk, e_blk, n,
                                         x.dtype.itemsize),
        name="ssm_scan_bwd",
        interpret=interpret,
    )(x, delta, a_t, bx, cx, d_row, dy, hs)


def _kernel_operands(x, delta, a, bm, c, d, chunk):
    t = x.shape[1]
    t_pad = -(-t // chunk) * chunk
    return (_pad_time(x, t_pad), _pad_time(delta, t_pad),
            a.astype(jnp.float32).T, _lane_broadcast(bm, t_pad),
            _lane_broadcast(c, t_pad),
            d.astype(jnp.float32).reshape(1, -1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan(x, delta, a, bm, c, d, chunk, e_blk, interpret):
    return _scan_fwd(x, delta, a, bm, c, d, chunk, e_blk, interpret)[0]


def _scan_fwd(x, delta, a, bm, c, d, chunk, e_blk, interpret):
    y, hs = _pallas_forward(*_kernel_operands(x, delta, a, bm, c, d, chunk),
                            chunk, e_blk, interpret)
    return y[:, :x.shape[1]], (x, delta, a, bm, c, d, hs)


def _scan_bwd(chunk, e_blk, interpret, res, dy):
    x, delta, a, bm, c, d, hs = res
    b, t, _e = x.shape
    n = a.shape[1]
    ops = _kernel_operands(x, delta, a, bm, c, d, chunk)
    dx, ddelta, da, dd, db, dc = _pallas_backward(
        *ops, _pad_time(dy.astype(x.dtype), ops[0].shape[1]), hs, chunk,
        e_blk, interpret)

    def over_blocks(parts, like):
        return parts[:, :, 0].sum(axis=1).reshape(b, -1, n)[:, :t].astype(
            like.dtype)

    return (dx[:, :t], ddelta[:, :t],
            da.sum(axis=0).T.astype(a.dtype), over_blocks(db, bm),
            over_blocks(dc, c), dd.sum(axis=(0, 1)).astype(d.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


def scan_xla(x, delta, a, bm, c, d, chunk=64):
    """The same recurrence as a chunked `lax.scan`: an outer scan over
    chunks of `chunk` steps, each under `jax.checkpoint`, so the backward
    keeps one state a chunk and recomputes the rest. float32 state and
    exp; y in x's dtype. The path off the TPU, and the kernels' oracle."""
    b, t, e = x.shape
    n = a.shape[1]
    chunk = min(chunk, t)
    t_pad = -(-t // chunk) * chunk
    f32 = jnp.float32
    a32, d32 = a.astype(f32), d.astype(f32)

    def chunks(m):
        m = jnp.pad(m, ((0, 0), (0, t_pad - t), (0, 0)))
        return m.reshape(b, t_pad // chunk, chunk, -1).transpose(1, 2, 0, 3)

    def step(h, inp):
        xt, dt, bt, ct = (z.astype(f32) for z in inp)
        h = jnp.exp(dt[..., None] * a32) * h \
            + (dt * xt)[..., None] * bt[:, None, :]
        return h, jnp.sum(h * ct[:, None, :], axis=-1) + d32 * xt

    @jax.checkpoint
    def one_chunk(h, inp):
        return jax.lax.scan(step, h, inp)

    _h, ys = jax.lax.scan(one_chunk, jnp.zeros((b, e, n), f32),
                          (chunks(x), chunks(delta), chunks(bm), chunks(c)))
    ys = ys.reshape(t_pad, b, e).transpose(1, 0, 2)
    return ys[:, :t].astype(x.dtype)


def plan(x_shape, n, itemsize, chunk=None):
    """What a call will do, for `ssm.plan`; None where the shape goes to
    `scan_xla` (E not a multiple of 128, or N not a multiple of 8)."""
    _b, t, e = x_shape
    e_blk = pick_channel_block(e)
    if e_blk is None or n % 8:
        return None
    chunk = chunk or pick_chunk(t, e_blk, n, itemsize)
    return {"chunk": chunk, "chunks": -(-t // chunk), "channel_block": e_blk,
            "state": n,
            "vmem_fwd": vmem_bytes("fwd", chunk, e_blk, n, itemsize)
            + _VMEM_MARGIN,
            "vmem_bwd": vmem_bytes("bwd", chunk, e_blk, n, itemsize)
            + _VMEM_MARGIN}


def selective_scan(x, delta, a, bm, c, d, chunk=None, interpret=None):
    """Selective scan entry (shapes in the module docstring). On the TPU
    the Pallas kernels; off it (`interpret` unset on another backend)
    `scan_xla`. `interpret=True` runs the kernels in interpret mode (the
    tests); an explicit `chunk` replaces `pick_chunk`'s."""
    if x.shape != delta.shape or bm.shape != c.shape \
            or a.shape != (x.shape[2], bm.shape[2]) \
            or bm.shape[:2] != x.shape[:2] or d.shape != (x.shape[2],):
        raise ValueError(
            "selective_scan: x %r, delta %r, A %r, B %r, C %r, D %r do not "
            "fit (B,T,E), (B,T,E), (E,N), (B,T,N), (B,T,N), (E,)"
            % (x.shape, delta.shape, a.shape, bm.shape, c.shape, d.shape))
    if interpret is None:
        interpret = default_interpret()
        if interpret:
            return scan_xla(x, delta, a, bm, c, d)
    what = plan(x.shape, a.shape[1], x.dtype.itemsize, chunk)
    if what is None or (not interpret and what["chunk"] % 16):
        return scan_xla(x, delta, a, bm, c, d)
    from ...framework import obs
    if obs.enabled():
        now = obs.now()
        obs.record("ssm.plan", now, now, **what)
    return _scan(x, delta, a, bm, c, d, what["chunk"],
                 what["channel_block"], bool(interpret))
