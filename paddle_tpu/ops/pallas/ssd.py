"""Pallas TPU kernels of the chunked Mamba-2 recurrence (`mamba2_scan`; the
mathematics is `ops/ssm_ops.py`'s module docstring and nothing of it
changes here).

`ssd_fwd`: grid (B, H / HB, chunks), the chunk axis innermost and
sequential. A grid step holds one chunk of HB heads of one group (HB
divides H / G): the group's B and C (Q, N), the heads' x side by side (Q,
HB * P), their dt a row a head, (HB, Q): a small copy XLA makes in front.
A decay score needs L_i down the sublanes and L_j along the lanes: the
cumulative log-decay is formed as rows and transposed in VMEM to the bit
(`_transposed`), so that e^{L_i - L_i} is 1 whatever L has grown to. The heads' float32 states live side
by side in VMEM scratch across the chunk axis, (N, HB * P). The step forms
C B^T once, every head's masked decay scores e^{L_i - L_j} dt_j (i >= j
only: no `exp` of a positive number) in VMEM, never in HBM, and writes y
(float32) and the state the chunk BEGAN with, (B, chunks, H / HB, N, HB *
P) float32: `_ssd_fwd`'s residual, the heads of a step side by side.
`ssd_bwd`: the same grid with the chunks in reverse, the cotangent of the
state in scratch (lambda_c = dS0_c + whole_c lambda_{c+1}). A step rebuilds
its chunk's scores from x, dt, B, C and the saved state, pulls the chunk
back by hand and writes dx, dB and dC (summed over the step's heads) and
two rows a head: d dt but for the decay's part, and d l, the cotangent of
the log-decay a token l_j = a dt_j (d dt += a d l and d a = sum dt d l are
XLA's, outside: H numbers a token). One kernel a direction: the backward's
live values fit VMEM (`vmem_bytes`).

Every array of a body is (HB, rows, columns) and every head-wise matmul a
batched one: an operation runs for all the step's heads before the next
does. The matmuls against the state are ONE matmul a step, (Q, N) against
the heads side by side, HB * P lanes wide; P = 64 is half a lane tile, so
the heads are cut out of a wide block and set back side by side in VMEM
(`_stack`, `_side_by_side`).

Precision is the XLA form's, place for place: dt, the cumulative
log-decay (a 0/1 triangle matmul at HIGHEST), every decay, the state and
its cotangent, d dt and d l are float32; bfloat16 operands go to the MXU
exactly where `_mm(..., mxu)` sends them (C B^T, scores x, the chunk's own
state, C S0) and where jax's pullback of those sends their cotangents.

`plan` maps a call's shapes to the tiling, or None where the kernels do
not tile: `mamba2_scan` then takes the XLA form, as it does off the TPU
(`interpret.default_interpret`).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

try:
    from jax.experimental.pallas import tpu as pltpu
    _HAS_TPU_PALLAS = True
except Exception:  # pragma: no cover
    pltpu = None
    _HAS_TPU_PALLAS = False

from ..linear_attn_ops import _mxu_dtype
from .delta_rule import _NT, _TN, _dot, _dot32, _side_by_side, _stack

CHUNK = 128     # tokens a chunk: the one length the kernels tile
_LANES = 128
_F32 = jnp.float32
_BF16 = jnp.bfloat16
_VMEM_MARGIN = 8 * 2 ** 20      # Mosaic's own temporaries
_VMEM_MOST = 96 * 2 ** 20       # of a v5e core's 128 MiB

# dot_general's dimension numbers over plain matrices (`delta_rule`'s are
# over (heads, rows, columns), a head a batch)
_NN2 = (((1,), (0,)), ((), ()))
_NT2 = (((1,), (1,)), ((), ()))
_TN2 = (((0,), (0,)), ((), ()))


@functools.lru_cache(maxsize=None)
def _constants():
    """(3, Q, Q) float32 of zeros and ones: [0][j, i] = j <= i (a row of
    l against it is its cumulative sum), [1] its transpose (a row of d L
    against it is the sum from there on), [2] the identity."""
    j = np.arange(CHUNK)[:, None]
    i = np.arange(CHUNK)[None, :]
    return np.stack([j <= i, i <= j, i == j]).astype(np.float32)


def _transposed(m, eye):
    """(rows, Q) float32 -> (Q, rows), to the bit: m in three bfloat16
    parts (8 bits each: together float32's 24), each against the identity
    on the MXU, added as they were split. (L_i down the sublanes is then
    the very number L_i along the lanes is: a decay e^{L_i - L_i} is 1.)"""
    high = m.astype(_BF16)
    rest = m - high.astype(_F32)
    mid = rest.astype(_BF16)
    low = (rest - mid.astype(_F32)).astype(_BF16)
    eye = eye.astype(_BF16)
    high, mid, low = (jax.lax.dot_general(eye, part, _NT2,
                                          preferred_element_type=_F32)
                      for part in (high, mid, low))
    return high + mid + low


def _columns(m):
    """(Q, heads) -> (heads, Q, 1)."""
    return jnp.stack([m[:, i:i + 1] for i in range(m.shape[1])])


def _rows(m):
    """(heads, Q) -> (heads, 1, Q)."""
    return jnp.stack([m[i:i + 1, :] for i in range(m.shape[0])])


def _over_lanes(scalars, width):
    """(heads, 1, 1) -> (1, heads * width): a head's number over its lanes
    of the heads side by side."""
    return jnp.concatenate([jnp.broadcast_to(scalars[i], (1, width))
                            for i in range(scalars.shape[0])], axis=1)


def _chunk_parts(b_ref, c_ref, dt_ref, a_ref, ones_ref, mxu):
    """The decays of one chunk, every array (heads, ...): `decay` the
    masked e^{L_i - L_j} and `decay_dt` it times dt_j (heads, Q, Q), `grow`
    e^{L_i}, `fade` e^{L_last - L_i}, `dt_cols` (heads, Q, 1), `whole`
    e^{L_last} (heads, 1, 1), and C B^T (Q, Q)."""
    dt = dt_ref[...]                                    # a row a head
    heads = dt.shape[0]
    log = _dot32(dt * a_ref[...], ones_ref[0], _NN2)
    down = _columns(_transposed(jnp.concatenate([log, dt], axis=0),
                                ones_ref[2]))
    log_col, dt_cols = down[:heads], down[heads:]
    i = jax.lax.broadcasted_iota(jnp.int32, (1, CHUNK, CHUNK), 1)
    j = jax.lax.broadcasted_iota(jnp.int32, (1, CHUNK, CHUNK), 2)
    decay = jnp.exp(jnp.where(i >= j, log_col - _rows(log), -jnp.inf))
    last = log_col[:, CHUNK - 1:, :]
    dt_rows = _rows(dt)
    return {"decay": decay, "dt_rows": dt_rows, "decay_dt": decay * dt_rows,
            "dt_cols": dt_cols, "grow": jnp.exp(log_col),
            "fade": jnp.exp(last - log_col), "whole": jnp.exp(last),
            "cb": _dot(c_ref[...], b_ref[...], mxu, _NT2)}


def _fwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, ones_ref, y_ref,
                states_ref, state, wide, *, heads, mxu):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    s0 = state[...]
    states_ref[...] = s0
    p = _chunk_parts(b_ref, c_ref, dt_ref, a_ref, ones_ref, mxu)
    x = _stack(x_ref, heads)
    within = _dot(p["decay_dt"] * p["cb"], x, mxu)
    wide[...] = _dot(c_ref[...], s0, mxu, _NN2)
    y = within + _stack(wide, heads) * p["grow"]
    y_ref[...] = _side_by_side(y)
    own = _dot(b_ref[...], _side_by_side(x * (p["fade"] * p["dt_cols"])),
               mxu, _TN2)
    state[...] = _over_lanes(p["whole"], x.shape[2]) * s0 + own


def _bwd_kernel(x_ref, b_ref, c_ref, dt_ref, a_ref, ones_ref, states_ref,
                dy_ref, dx_ref, db_ref, dc_ref, ddt_ref, dl_ref, d_state,
                wide, *, heads, mxu):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        d_state[...] = jnp.zeros_like(d_state)

    p = _chunk_parts(b_ref, c_ref, dt_ref, a_ref, ones_ref, mxu)
    b, c = b_ref[...], c_ref[...]
    x, dy = _stack(x_ref, heads), _stack(dy_ref, heads)
    width = x.shape[2]
    s0, lam = states_ref[...], d_state[...]
    grow, fade, whole = p["grow"], p["fade"], p["whole"]
    weight = fade * p["dt_cols"]            # of x_j in the chunk's own state
    # y = (decay dt C B^T) x + e^L (C S0)
    scores = p["decay_dt"] * p["cb"]
    d_scores = _dot(dy, x, mxu, _NT)
    dx = _dot(scores, dy, mxu, _TN)
    d_decay = d_scores * p["cb"] * p["decay"]       # times dt_j: d L's
    d_cb = jnp.sum(d_scores * p["decay_dt"], axis=0)
    grown = dy * grow
    wide[...] = _dot(c, s0, mxu, _NN2)
    d_log = jnp.sum(grown * _stack(wide, heads), axis=2, keepdims=True)
    grown = _side_by_side(grown)
    dc_ref[...] = (_dot(d_cb, b, mxu, _NN2)
                   + _dot(grown, s0, mxu, _NT2)).astype(dc_ref.dtype)
    # S_end = whole S0 + B^T (x weight)
    wide[...] = _dot(b, lam, mxu, _NN2)
    d_weighted = _stack(wide, heads)
    dx = dx + d_weighted * weight
    d_weight = jnp.sum(d_weighted * x, axis=2, keepdims=True)
    db_ref[...] = (_dot(d_cb, c, mxu, _TN2)
                   + _dot(_side_by_side(x * weight), lam, mxu, _NT2)
                   ).astype(db_ref.dtype)
    dx_ref[...] = _side_by_side(dx).astype(dx_ref.dtype)
    d_state[...] = _dot(c, grown, mxu, _TN2) \
        + _over_lanes(whole, width) * lam
    # the decays: L_i down a column of `d_decay`, L_j along a row; the
    # columns' parts go onto the diagonal, so that a sum down the sublanes
    # gives every part as a row
    lane_head = jax.lax.broadcasted_iota(
        jnp.int32, (heads, 1, heads * width), 2) // width
    head = jax.lax.broadcasted_iota(jnp.int32, (heads, 1, heads * width), 0)
    d_whole = jnp.sum(jnp.where(
        lane_head == head, jnp.sum(lam * s0, axis=0, keepdims=True)[None],
        0.0), axis=2, keepdims=True)
    faded = d_weight * weight
    d_last = jnp.sum(faded, axis=1, keepdims=True) + d_whole * whole
    d_decay_dt = d_decay * p["dt_rows"]
    eye = ones_ref[2]
    row = jax.lax.broadcasted_iota(jnp.int32, (1, CHUNK, 1), 1)
    d_log = d_log + jnp.sum(d_decay_dt, axis=2, keepdims=True) - faded \
        + jnp.where(row == CHUNK - 1, d_last, 0.0)
    d_log = jnp.sum(eye * d_log - d_decay_dt, axis=1)
    ddt_ref[...] = jnp.sum(eye * (d_weight * fade) + d_decay, axis=1)
    dl_ref[...] = _dot32(d_log, ones_ref[1], _NN2)


def pick_heads(per_group, head_dim):
    """Heads a grid step holds: the most of 8, 4, 2, 1 that divides the
    heads of a group and stands as whole 128-lane tiles side by side, or
    None. On the chip at (2, 8192, 64 x 64), 8 groups, state 128, forward
    / forward + backward ms a call: 3.99 / 15.03 at 2, 2.89 / 11.07 at 4,
    2.26 / 8.25 at 8: the same work in fewer, fuller grid steps (PERF.md,
    PR 47)."""
    return next((n for n in (8, 4, 2, 1)
                 if per_group % n == 0 and n * head_dim % _LANES == 0), None)


def vmem_bytes(kernel, heads, head_dim, state, itemsize):
    """Upper reckoning of what one grid step of `kernel` ("ssd_fwd" |
    "ssd_bwd") holds in VMEM: every block twice (the pipeline's two
    buffers), the state and the wide float32 buffer in scratch, and the
    float32 values the body keeps: (Q, Q) matrices a head (6 forward, 12
    backward) and (Q, P) arrays a head, P in lanes of 128 (8 and 20)."""
    wide = heads * head_dim
    lanes = -(-head_dim // _LANES) * _LANES
    blocks = CHUNK * wide * (itemsize + 4) + 2 * CHUNK * state * itemsize \
        + state * wide * 4 + 4 * CHUNK * _LANES * 4 + 2 * CHUNK * CHUNK * 4
    squares, slabs = 6, 8
    if kernel == "ssd_bwd":
        blocks += CHUNK * wide * itemsize + 2 * CHUNK * state * 4 \
            + 2 * CHUNK * _LANES * 4
        squares, slabs = 12, 20
    return 2 * blocks + (state + CHUNK) * wide * 4 \
        + heads * CHUNK * 4 * (squares * CHUNK + slabs * lanes)


def plan(x_shape, groups, state, chunk, itemsize):
    """What a call will do, for `ssd.plan`; None where the shape goes to
    the XLA form: a chunk that is not 128 tokens, a state that is no
    multiple of 128, no number of a group's heads that stands as whole lane
    tiles side by side, or a step too large for VMEM."""
    _b, _t, h, p = x_shape
    if chunk != CHUNK or state % _LANES or h % groups \
            or not _HAS_TPU_PALLAS:
        return None
    heads = pick_heads(h // groups, p)
    if heads is None:
        return None
    vmem = {k: vmem_bytes(k, heads, p, state, itemsize)
            for k in ("ssd_fwd", "ssd_bwd")}
    if max(vmem.values()) + _VMEM_MARGIN > _VMEM_MOST:
        return None
    return {"kernels": "pallas: ssd_fwd, ssd_bwd; grid (batch, heads / %d, "
                       "chunks), chunks sequential, the (N, %d P) float32 "
                       "states of %d heads side by side in VMEM across "
                       "them; C B^T once a step, the masked decay scores "
                       "a head in VMEM alone, the state's matmuls %d lanes "
                       "wide; backward by hand from the states the chunks "
                       "began with" % (heads, heads, heads, heads * p),
            "heads_a_step": heads, "vmem_fwd": vmem["ssd_fwd"],
            "vmem_bwd": vmem["ssd_bwd"]}


def _views(x, dt, a, b, c, heads):
    """The kernels' views of `_ssd`'s operands: (B, T, H * P), (B, T, G *
    N) (the same bytes), dt a row a head (B, H / HB, HB, T): a small copy,
    and a (H / HB, HB, 1)."""
    bsz, chunks, q, h, _p = x.shape
    t = chunks * q
    flat = lambda m: m.reshape(bsz, t, -1)
    return (flat(x), flat(b), flat(c),
            dt.reshape(bsz, t, h // heads, heads).transpose(0, 2, 3, 1),
            a.reshape(h // heads, heads, 1))


def _call(name, kernel, x, b, heads, reverse, interpret):
    """(`pl.pallas_call` of `kernel` but for its specs and out_shape, the
    BlockSpecs by operand kind)."""
    bsz, chunks, _q, h, p = x.shape
    groups, state = b.shape[3:]
    steps_a_group = h // groups // heads
    wide = heads * p

    def spec(block, index):
        def at(b_, h_, c_):
            return index(b_, h_, chunks - 1 - c_ if reverse else c_)
        return pl.BlockSpec(block, at)

    specs = {
        "x": spec((None, CHUNK, wide), lambda b_, h_, c_: (b_, c_, h_)),
        "bc": spec((None, CHUNK, state),
                   lambda b_, h_, c_: (b_, c_, h_ // steps_a_group)),
        # a step's own B and C sums: (B, T, (H / HB) * N)
        "dbc": spec((None, CHUNK, state), lambda b_, h_, c_: (b_, c_, h_)),
        "row": spec((None, None, heads, CHUNK),
                    lambda b_, h_, c_: (b_, h_, 0, c_)),
        "a": spec((None, heads, 1), lambda b_, h_, c_: (h_, 0, 0)),
        "ones": spec((3, CHUNK, CHUNK), lambda b_, h_, c_: (0, 0, 0)),
        "states": spec((None, None, None, state, wide),
                       lambda b_, h_, c_: (b_, c_, h_, 0, 0)),
    }
    call = functools.partial(
        pl.pallas_call,
        functools.partial(kernel, heads=heads, mxu=_mxu_dtype(x.dtype)),
        grid=(bsz, h // heads, chunks),
        scratch_shapes=[pltpu.VMEM((state, wide), _F32),
                        pltpu.VMEM((CHUNK, wide), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(name, heads, p, state,
                                        x.dtype.itemsize) + _VMEM_MARGIN),
        name=name, interpret=interpret)
    return call, specs


def _heads_of(x, b):
    return pick_heads(x.shape[3] // b.shape[3], x.shape[4])


# jitted: the call's views and the kernel stay one computation a call site
# (`delta_rule._forward`'s reason), traced once a shape and process
@functools.partial(jax.jit, static_argnames=("interpret",))
def _forward(x, dt, a, b, c, interpret):
    bsz, chunks, q, h, p = x.shape
    state = b.shape[4]
    heads = _heads_of(x, b)
    call, sp = _call("ssd_fwd", _fwd_kernel, x, b, heads, False, interpret)
    return call(
        in_specs=[sp["x"], sp["bc"], sp["bc"], sp["row"], sp["a"],
                  sp["ones"]],
        out_specs=[sp["x"], sp["states"]],
        out_shape=[jax.ShapeDtypeStruct((bsz, chunks * q, h * p), _F32),
                   jax.ShapeDtypeStruct(
                       (bsz, chunks, h // heads, state, heads * p), _F32)],
    )(*_views(x, dt, a, b, c, heads), _constants())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _backward(x, dt, a, b, c, states, dy, interpret):
    bsz, chunks, q, h, p = x.shape
    groups, state = b.shape[3:]
    heads = _heads_of(x, b)
    t, steps = chunks * q, h // heads
    call, sp = _call("ssd_bwd", _bwd_kernel, x, b, heads, True, interpret)
    # a group's B and C in one step: their gradients leave as they are
    of_bc = b.dtype if steps == groups else _F32
    row = jax.ShapeDtypeStruct((bsz, steps, heads, t), _F32)
    sums = jax.ShapeDtypeStruct((bsz, t, steps * state), of_bc)
    dx, db, dc, ddt, dl = call(
        in_specs=[sp["x"], sp["bc"], sp["bc"], sp["row"], sp["a"],
                  sp["ones"], sp["states"], sp["x"]],
        out_specs=[sp["x"], sp["dbc"], sp["dbc"], sp["row"], sp["row"]],
        out_shape=[jax.ShapeDtypeStruct((bsz, t, h * p), x.dtype), sums,
                   sums, row, row],
    )(*_views(x, dt, a, b, c, heads), _constants(), states,
      dy.astype(_F32).reshape(bsz, t, h * p))
    by_token = lambda m: m.reshape(bsz, h, t).swapaxes(1, 2).reshape(
        dt.shape)
    ddt, dl = by_token(ddt), by_token(dl)
    of_group = lambda m: m.reshape(
        bsz, chunks, q, groups, steps // groups, state).sum(4).astype(
            b.dtype)
    return (dx.reshape(x.shape), ddt + a * dl,
            jnp.sum(dt * dl, axis=(0, 1, 2)), of_group(db), of_group(dc))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def ssd(x, dt, a, b, c, interpret=False):
    """`ssm_ops._ssd` by the kernels, its operands and its result: x (B,
    C, 128, H, P), dt (B, C, 128, H) float32, a (H,) float32 negative, b,
    c (B, C, 128, G, N) -> y (B, C, 128, H, P) float32, at shapes `plan`
    takes. `interpret=True` runs the kernel bodies in interpret mode (the
    tests)."""
    return _ssd_fwd(x, dt, a, b, c, interpret)[0]


def _ssd_fwd(x, dt, a, b, c, interpret):
    y, states = _forward(x, dt, a, b, c, interpret=interpret)
    return y.reshape(x.shape), (x, dt, a, b, c, states)


def _ssd_bwd(interpret, res, dy):
    return _backward(*res, dy, interpret=interpret)


ssd.defvjp(_ssd_fwd, _ssd_bwd)
