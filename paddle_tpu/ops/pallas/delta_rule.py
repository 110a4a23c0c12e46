"""Pallas TPU kernels of the chunked gated delta rule (`kda_attention`; the
mathematics is `ops/linear_attn_ops.py`'s module docstring, equations
(1)-(3), and nothing of it changes here).

`kda_fwd`: grid (B, H / HB, chunks), the chunk axis innermost and
sequential; the float32 state of each of the HB heads a grid step holds
lives in VMEM scratch across it (transposed, S^T (V, K): its decay e^{G_C}
is then a row spread over sublanes). A grid step reads one 64-token chunk
of q, k, g, v and beta straight from the op's own layout ((B, T, H * K)
views: no transposed copy is made), forms in VMEM everything `_intra`
forms for a whole group in HBM, applies (1)-(3) to the state and writes O
and the state the chunk BEGAN with, (groups, chunks a group, B, H, V, K)
float32: what the backward restarts from. `kda_bwd`: the same grid with the
chunks in reverse, d S^T in scratch. A grid step rebuilds its chunk's
parts from q, k, v, g, beta and the saved state, walks (1)-(3) backwards
(`_walk_back`'s formulas) and pulls the chunk math back by hand; it writes
dq, dk, dv, dg (float32) and dbeta. One kernel a direction: the backward's
100 or so (64, K) float32 values a head fit VMEM (`vmem_bytes`).

A chunk's math is some forty small matmuls in chains (each waits for the
last one's result: 0.17 us a float32 link on a v5e against 0.10 us of MXU
time), so a grid step holds HB heads and every array of the body is (HB,
rows, columns), every matmul a batched one: an operation runs for all the
step's heads before the next does, the heads' chains are independent and
fill each other's waits. On the chip, 1 -> 8 heads a step takes the forward
from 12.5 to 4.8 ms a call (`pick_heads`). (Batched, not unrolled in
Python: the same order of operations, an eighth of the tracing and lowering
every program that holds the op pays before it can even ask the compile
cache: 5.8 -> 0.9 s for the Kimi step's twelve calls.)

The chunk math in VMEM is the XLA form's, spelled for the MXU:

  - every decay difference e^{G_r - G_s}, r > s, is e^{G_r - b} e^{b -
    G_s} with b the cumulative decay of a row between s and r, so no `exp`
    of a positive number is taken; the XLA form re-bases once, at 16-row
    sub-blocks, and takes the differences inside a sub-block directly,
    (16, 16, K) numbers a block reduced over lanes. Here the re-basing
    goes on down: LEVEL l = 0..5 splits every block of 64 >> l rows at its
    middle row b_l, and with t = e^{-|G - b_l|} (e^{G_r - b_l} in the later
    half, e^{b_l - G_s} in the earlier) the level's pairs (r later, s
    earlier) are (k t)_r . (k t)_s and (q t)_r . (k t)_s: ONE float32
    matmul a level, (k t) against [k t; q t], masked to the level's pairs.
    The levels' pairs partition the strictly lower triangle; the diagonal
    of A^q is a row sum. The cumulative decay is one matmul of a 0/1
    triangle against g (`_dot_exact`);
  - the pullback of a level is a matmul's: with M = mask (d A) and N =
    mask (d A^q), [d (k t); d (q t)] = [[M + M^T, N^T], [N, 0]] [k t; q t],
    then dk += d (k t) t, dq += d (q t) t scale and dG_r += or -= (k t) d
    (k t) + (q t) d (q t) as r lies in the later or the earlier half: the
    docstring's dq = R, dk = P + P^T + R^T, dG = k (P - P^T) + q R - k R^T,
    a level at a time (d b_l is zero: A reads differences only);
  - the solve is `_unit_lower_inverse_fwd`'s, multiplied out (`_solve`):
    the 16-row diagonal blocks by the finite series, then the blocks below
    them; its pullback d low = -strictly_lower(X^T dX X^T).

Precision is the XLA form's: the cumulative decay, the decay differences,
both products, the solve and the state are float32 (matmuls at HIGHEST);
bfloat16 operands go to the MXU exactly where `_mm(..., mxu)` sends them.
On the chip the kernels then lie as far from the float32 answer as the XLA
form does, to two digits, in value and in every gradient (PERF.md, PR 39;
float32 operands throughout `kda_bwd` were tried for the sake of `A_log`'s
gradient, a residual 2,000 times smaller than its terms: 3.7 ms a call
dearer, and the cell's `grad_norm_gap` did not move).

`plan` maps a call's shapes to the kernels' tiling, or None where they do
not tile (K or V no multiple of 128): `kda_attention` then takes the XLA
form, as it does off the TPU (`interpret.default_interpret`).
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

from ..linear_attn_ops import CHUNK, SUB, _groups_of, _mxu_dtype


HALVINGS = 6    # levels that split a block at its middle: 64 -> 1 row
assert CHUNK == 1 << HALVINGS
_LANES = 128
_F32 = jnp.float32
_BF16 = jnp.bfloat16
_HIGHEST = jax.lax.Precision.HIGHEST
_VMEM_MARGIN = 8 * 2 ** 20      # Mosaic's own temporaries

# dot_general's dimension numbers over (heads, rows, columns): a head a batch
_NN = (((2,), (1,)), ((0,), (0,)))  # a @ b
_NT = (((2,), (2,)), ((0,), (0,)))  # a @ b^T
_TN = (((1,), (1,)), ((0,), (0,)))  # a^T @ b


def _dot32(a, b, dims=_NN):
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=_F32)


def _dot(a, b, mxu, dims=_NN):
    """float32 result; bfloat16 operands where `mxu` says (`_mm`'s rule)."""
    if mxu == _BF16:
        return jax.lax.dot_general(a.astype(mxu), b.astype(mxu), dims,
                                   preferred_element_type=_F32)
    return _dot32(a, b, dims)


def _dot_exact(ones, x):
    """`ones` (M, 64) bfloat16 of zeros and ones against x (64, N) float32,
    to float32's last bits in ONE bfloat16 matmul: x is split into three
    bfloat16 parts (8 bits each: together float32's 24), the parts stand
    side by side, and the three results are added. What HIGHEST would do
    in six passes, three of them against the zero parts of `ones`."""
    high = x.astype(_BF16)
    rest = x - high.astype(_F32)
    mid = rest.astype(_BF16)
    low = (rest - mid.astype(_F32)).astype(_BF16)
    n = x.shape[1]
    out = jnp.dot(ones, jnp.concatenate([high, mid, low], axis=1),
                  preferred_element_type=_F32)
    return out[:, :n] + out[:, n:2 * n] + out[:, 2 * n:]


@functools.lru_cache(maxsize=None)
def _constants():
    """(sums (2, 64, 64) bfloat16, masks (6, 128, 128) float32), zeros and
    ones. `sums[0]` against g is the cumulative decay (row r: rows <= r),
    `sums[1]` its transpose. `masks[l]` holds level l's pairs P_l (r in the
    later, s in the earlier half of one block of 64 >> l rows) as
    [[P + P^T, P^T], [P, 0]]: the top half masks a level's product [G |
    A^q^T] and its cotangent, the bottom half d A^q."""
    r = np.arange(CHUNK)[:, None]
    s = np.arange(CHUNK)[None, :]
    masks = []
    for level in range(HALVINGS):
        half = CHUNK >> (level + 1)
        pairs = (r // (2 * half) == s // (2 * half)) \
            & (r % (2 * half) >= half) & (s % (2 * half) < half)
        masks.append(np.block([[pairs | pairs.T, pairs.T],
                               [pairs, np.zeros_like(pairs)]]))
    return (np.stack([s <= r, r <= s]).astype(jnp.bfloat16),
            np.stack(masks).astype(np.float32))


def _stack(ref, heads):
    """The block (64, heads * D) as (heads, 64, D) float32."""
    d = ref.shape[1] // heads
    return jnp.stack([ref[:, i * d:(i + 1) * d]
                      for i in range(heads)]).astype(_F32)


def _side_by_side(x):
    """(heads, 64, D) -> (64, heads * D): a block's layout."""
    return jnp.concatenate([x[i] for i in range(x.shape[0])], axis=1)


def _bases(cum_ref):
    """Every halving level's base b_l, (heads, 64, K) each: row r holds the
    cumulative decay of the last row before the middle of r's block of
    64 >> l rows, so G_r - b_l <= 0 in the later half and >= 0 in the
    earlier half. Rows of the cumulative decay (in VMEM scratch) loaded
    spread over their blocks: no arithmetic."""
    heads, _c, d = cum_ref.shape
    tile_row = jax.lax.broadcasted_iota(jnp.int32, (heads, 8, d), 1)

    def spread(row, rows):
        return jnp.broadcast_to(cum_ref[:, row:row + 1, :], (heads, rows, d))

    out = []
    for level in range(HALVINGS):
        half = CHUNK >> (level + 1)
        if half >= 4:
            out.append(jnp.concatenate(
                [spread(first + half - 1, 2 * half)
                 for first in range(0, CHUNK, 2 * half)], axis=1))
            continue
        tiles = []
        for first in range(0, CHUNK, 8):
            tile = spread(first + half - 1, 8)
            for mid in range(3 * half, 8, 2 * half):
                tile = jnp.where(tile_row >= mid - half,
                                 spread(first + mid - 1, 8), tile)
            tiles.append(tile)
        out.append(jnp.concatenate(tiles, axis=1))
    return out


def _level(qs, k, cum, base):
    """(k t, q t scale, t) of a level, t = e^{-|G - b_l|}: e^{G_r - b_l} in
    the later half of a block and e^{b_l - G_s} in the earlier, so that
    (k t)_r . (k t)_s = A_rs for the level's pairs. No exponent is
    positive."""
    t = jnp.exp(-jnp.abs(cum - base))
    return k * t, qs * t, t


def _grid(heads, rows=CHUNK):
    """(row numbers, column numbers) of (heads, rows, 64) matrices."""
    return (jax.lax.broadcasted_iota(jnp.int32, (heads, rows, CHUNK), 1),
            jax.lax.broadcasted_iota(jnp.int32, (heads, rows, CHUNK), 2))


def _products(qs, k, cum, bases, masks_ref):
    """(strictly_lower(A), lower(A^q)^T) of equations (1) and (2), (heads,
    64, 64) float32 each: a level's pairs as ONE matmul a head, (k t)
    against [k t; q t], which gives [G | A^q^T] with G symmetric."""
    both = jnp.zeros((qs.shape[0], CHUNK, 2 * CHUNK), _F32)
    for level, base in enumerate(bases):
        kt, qt, _t = _level(qs, k, cum, base)
        both = both + masks_ref[level, :CHUNK] * _dot32(
            kt, jnp.concatenate([kt, qt], axis=1), _NT)
    rows, cols = _grid(qs.shape[0])
    diagonal = jnp.sum(qs * k, axis=2, keepdims=True)
    return (jnp.where(rows > cols, both[:, :, :CHUNK], 0.0),
            both[:, :, CHUNK:] + jnp.where(rows == cols, diagonal, 0.0))


def _spread(packed):
    """The four SUB x SUB blocks standing side by side in `packed` (heads,
    16, 64), on the diagonal of (heads, 64, 64) matrices."""
    rows, cols = _grid(packed.shape[0])
    return jnp.where(rows // SUB == cols // SUB,
                     jnp.concatenate([packed] * (CHUNK // SUB), axis=1), 0.0)


def _solve(low):
    """(I + low)^-1, `low` (heads, 64, 64) strictly lower. With M = -low:
    the SUB-row diagonal blocks D by the finite series I + M + .. + M^15
    (`_block_inverse`'s product, multiplied out), doubled four times, S <-
    S + P S and P <- P P as ONE matmul P [P | S], the four blocks standing
    side by side (16 rows go through the MXU, not 64) against themselves
    on a diagonal; then with C the blocks below the diagonal and N = -D C
    (N^4 = 0): X = (I + N + N^2 + N^3) D, as S = D + N D, X = S + N^2 S:
    the 2 x 2 block recursion of `_unit_lower_inverse_fwd`, multiplied
    out."""
    heads = low.shape[0]
    rows, cols = _grid(heads)
    own = rows // SUB == cols // SUB
    on_diagonal = jnp.where(own, low, 0.0)
    power = -sum(on_diagonal[:, i * SUB:(i + 1) * SUB]
                 for i in range(CHUNK // SUB))
    sub_rows, sub_cols = _grid(heads, SUB)
    total = (sub_rows == sub_cols % SUB).astype(_F32)
    reach = 1
    while 2 * reach < SUB:
        out = _dot32(power, jnp.concatenate(
            [_spread(power), _spread(total)], axis=2))
        power, total = out[:, :, :CHUNK], total + out[:, :, CHUNK:]
        reach *= 2
    blocks = _spread(total + _dot32(power, _spread(total)))
    step = -_dot32(blocks, jnp.where(own, 0.0, low))
    out = _dot32(step, jnp.concatenate([step, blocks], axis=2))
    half = blocks + out[:, :, CHUNK:]
    return half + _dot32(out[:, :, :CHUNK], half)


def _beta_columns(beta_ref, heads):
    """The step's `heads` columns of the (64, H) block, (heads, 64, 1)
    float32."""
    beta = beta_ref[...].astype(_F32)
    lanes = jax.lax.broadcasted_iota(jnp.int32, beta.shape, 1)
    first = pl.program_id(1) * heads
    return jnp.stack([
        jnp.sum(jnp.where(lanes == first + i, beta, 0.0), axis=1,
                keepdims=True) for i in range(heads)])


def _chunk_parts(q_ref, k_ref, v_ref, g_ref, beta_ref, sums_ref, masks_ref,
                 cum_ref, *, scale, heads, mxu):
    """What `_intra` returns for one chunk, and what its pullback reads,
    every array (heads, ...): an operation runs for all the step's heads
    before the next does."""
    cums = _dot_exact(sums_ref[0], g_ref[...])      # the heads side by side
    d_k = cums.shape[1] // heads
    for i in range(heads):
        cum_ref[i] = cums[:, i * d_k:(i + 1) * d_k]
    cum, bases = cum_ref[...], _bases(cum_ref)
    k, v = _stack(k_ref, heads), _stack(v_ref, heads)
    qs = _stack(q_ref, heads) * scale
    beta = _beta_columns(beta_ref, heads)
    a_kk, a_qk_t = _products(qs, k, cum, bases, masks_ref)
    x = _solve(beta * a_kk)
    grow = jnp.exp(cum)
    fade = jnp.exp(cum[:, CHUNK - 1:] - cum)        # e^{G_C - G}
    b_v, b_k = beta * v, beta * k * grow
    return {"k": k, "v": v, "qs": qs, "beta": beta, "cum": cum,
            "bases": bases, "a_kk": a_kk, "a_qk_t": a_qk_t, "x": x,
            "grow": grow, "fade": fade, "b_v": b_v, "b_k": b_k,
            "w_v": _dot(x, b_v, mxu), "w_k": _dot(x, b_k, mxu),
            "q_bar": qs * grow, "k_end": k * fade}


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, sums_ref, masks_ref,
                o_ref, states_ref, state, cum_ref, *, mxu, **sizes):
    """`state` holds S^T, (V, K) a head: e^{G_C} then scales its lanes, a
    row spread over sublanes."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    s_t = state[...]
    states_ref[...] = s_t
    p = _chunk_parts(q_ref, k_ref, v_ref, g_ref, beta_ref, sums_ref,
                     masks_ref, cum_ref, mxu=mxu, **sizes)
    u = p["w_v"] - _dot(p["w_k"], s_t, mxu, _NT)
    o = _dot(p["q_bar"], s_t, mxu, _NT) + _dot(p["a_qk_t"], u, mxu, _TN)
    state[...] = p["grow"][:, CHUNK - 1:] * s_t \
        + _dot(u, p["k_end"], mxu, _TN)
    o_ref[...] = _side_by_side(o).astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, sums_ref, masks_ref,
                states_ref, do_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                dbeta_ref, d_state, cum_ref, *, mxu, **sizes):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        d_state[...] = jnp.zeros_like(d_state)

    heads = sizes["heads"]
    p = _chunk_parts(q_ref, k_ref, v_ref, g_ref, beta_ref, sums_ref,
                     masks_ref, cum_ref, mxu=mxu, **sizes)
    k, v, beta, x, qs = p["k"], p["v"], p["beta"], p["x"], p["qs"]
    d_o = _stack(do_ref, heads)
    s_t, ds_t = states_ref[...], d_state[...]
    decay = p["grow"][:, CHUNK - 1:]
    # (1)-(3) backwards: `_walk_back`'s step, on S^T and d S^T
    u = p["w_v"] - _dot(p["w_k"], s_t, mxu, _NT)
    du = _dot(p["a_qk_t"], d_o, mxu) + _dot(p["k_end"], ds_t, mxu, _NT)
    d_q_bar = _dot(d_o, s_t, mxu)
    d_k_end = _dot(u, ds_t, mxu)
    d_end = jnp.sum(s_t * ds_t, axis=1, keepdims=True) * decay
    d_w_k = -_dot(du, s_t, mxu)
    d_state[...] = _dot(d_o, p["q_bar"], mxu, _TN) + decay * ds_t \
        - _dot(du, p["w_k"], mxu, _TN)
    # the WY factors: Wv = X (beta v), Wk = X (beta k e^G)
    d_b_v = _dot(x, du, mxu, _TN)
    d_x = _dot(du, p["b_v"], mxu, _NT) + _dot(d_w_k, p["b_k"], mxu, _NT)
    d_b_k = _dot(x, d_w_k, mxu, _TN)
    faded = d_k_end * p["k_end"]
    d_end = d_end + jnp.sum(faded, axis=1, keepdims=True)
    # the solve, then A = low / beta and A^q
    d_low = -_dot32(x, _dot32(d_x, x, _NT), _TN)
    rows, cols = _grid(heads)
    d_a = jnp.where(rows > cols, beta * d_low, 0.0)
    d_a_qk = _dot(d_o, u, mxu, _NT)
    diagonal = jnp.sum(jnp.where(rows == cols, d_a_qk, 0.0), axis=2,
                       keepdims=True)
    row = jax.lax.broadcasted_iota(jnp.int32, (heads, CHUNK, 1), 1)
    d_qs = d_q_bar * p["grow"] + diagonal * k
    dk = d_b_k * (beta * p["grow"]) + d_k_end * p["fade"] + diagonal * qs
    d_cum = d_b_k * p["b_k"] + d_q_bar * p["q_bar"] - faded \
        + jnp.where(row == CHUNK - 1, d_end, 0.0)
    cot = jnp.concatenate([
        jnp.concatenate([d_a + jnp.swapaxes(d_a, 1, 2),
                         _dot(u, d_o, mxu, _NT)], axis=2),
        jnp.concatenate([d_a_qk, jnp.zeros_like(d_a_qk)], axis=2)], axis=1)
    for level, base in enumerate(p["bases"]):
        kt, qt, t = _level(qs, k, p["cum"], base)
        both = _dot32(masks_ref[level] * cot,
                      jnp.concatenate([kt, qt], axis=1))
        d_kt, d_qt = both[:, :CHUNK], both[:, CHUNK:]
        dk, d_qs = dk + d_kt * t, d_qs + d_qt * t
        # G_r rises with |G - b_l| in the later half of a block (its bit
        # of the row number set) and falls with it in the earlier
        d_t = d_kt * kt + d_qt * qt
        later = (row >> (HALVINGS - 1 - level)) & 1 == 1
        d_cum = d_cum + jnp.where(later, d_t, -d_t)
    dq_ref[...] = _side_by_side(d_qs * sizes["scale"]).astype(dq_ref.dtype)
    dk_ref[...] = _side_by_side(dk).astype(dk_ref.dtype)
    dv_ref[...] = _side_by_side(beta * d_b_v).astype(dv_ref.dtype)
    dg_ref[...] = _dot_exact(sums_ref[1], _side_by_side(d_cum))
    dbeta_ref[...] = _row_sums(d_b_k * k * p["grow"]) \
        + _row_sums(d_b_v * v) + _row_sums(d_low * p["a_kk"])


def _row_sums(x):
    """Each row's sum, as a ROW: (heads, rows, n) -> (heads, 1, rows), by a
    matmul against ones (a lane reduce gives a column)."""
    return _dot32(jnp.ones((x.shape[0], 8, x.shape[2]), _F32), x, _NT)[:, :1]


def pick_heads(h):
    """Heads a grid step holds, their chains of small dependent matmuls
    side by side: the most of 8, 4, 2, 1 that divides H. On the chip at
    (2, 8192, 16, 128), forward / backward ms a call: 12.5 / 19.5 at 1
    (unrolled form), 7.9 / 13.9 at 2, 5.7 / 11.3 at 4, 4.8 / 10.5 at 8;
    16 read 4.6 / 10.1 and would hold 60 MiB of VMEM (PERF.md, PR 39)."""
    return next(n for n in (8, 4, 2, 1) if h % n == 0)


def vmem_bytes(kernel, heads, d_k, d_v, itemsize):
    """Upper reckoning of what one grid step of `kernel` ("kda_fwd" |
    "kda_bwd") holds in VMEM: every block twice (the pipeline's two
    buffers), the states and the cumulative decays in scratch, and the
    float32 values the body keeps (every head's are live at once: about 40
    (64, K) arrays a head forward, 100 backward, as Mosaic's own count of
    the compiled kernels came to)."""
    row_k, row_v = CHUNK * d_k, CHUNK * d_v
    consts = 2 * CHUNK * CHUNK * 2 + HALVINGS * 4 * CHUNK * CHUNK * 4
    state = heads * d_k * d_v * 4
    blocks = heads * ((2 * row_k + row_v) * itemsize + row_k * 4
                      + row_v * itemsize) + CHUNK * _LANES * 4 + state
    live = 40
    if kernel == "kda_bwd":
        blocks += heads * ((2 * row_k + row_v) * itemsize + row_k * 4) \
            + heads * CHUNK * 4
        live = 100
    return 2 * (blocks + consts) + state + heads * row_k * 4 \
        + heads * live * max(row_k, row_v) * 4


def plan(q_shape, d_v, itemsize):
    """What a call will do, for `kda.plan`; None where the shape goes to
    the XLA form (K or V not a multiple of 128)."""
    _b, t, h, d_k = q_shape
    if d_k % _LANES or d_v % _LANES or not _HAS_TPU_PALLAS:
        return None
    heads = pick_heads(h)
    return {"kernels": "pallas: kda_fwd, kda_bwd; grid (batch, heads / %d, "
                       "chunks), chunks sequential, the (V, K) float32 "
                       "state in VMEM across them, %d heads a step side "
                       "by side; decay differences re-based at %d halving "
                       "levels, a masked float32 matmul each; solve: 16-row "
                       "blocks as I + M + .. + M^15 doubled four times, the "
                       "blocks below as (I + N + N^2 + N^3) D, backward "
                       "-strictly_lower(X^T dX X^T); products: backward by "
                       "hand, [[M + M^T, N^T], [N, 0]] [k t; q t] a level"
                       % (heads, heads, HALVINGS),
            "heads_a_step": heads, "levels": HALVINGS,
            "vmem_fwd": vmem_bytes("kda_fwd", heads, d_k, d_v, itemsize),
            "vmem_bwd": vmem_bytes("kda_bwd", heads, d_k, d_v, itemsize)}


def _specs(b, t_pad, h, d_k, d_v, heads, reverse):
    nc = t_pad // CHUNK
    per, _groups = _groups_of(t_pad)

    def ci(c):
        return nc - 1 - c if reverse else c

    return {
        "k": pl.BlockSpec((None, CHUNK, heads * d_k),
                          lambda b_, h_, c: (b_, ci(c), h_)),
        "v": pl.BlockSpec((None, CHUNK, heads * d_v),
                          lambda b_, h_, c: (b_, ci(c), h_)),
        "beta": pl.BlockSpec((None, CHUNK, h),
                             lambda b_, h_, c: (b_, ci(c), 0)),
        "sums": pl.BlockSpec((2, CHUNK, CHUNK), lambda b_, h_, c: (0, 0, 0)),
        "masks": pl.BlockSpec((HALVINGS, 2 * CHUNK, 2 * CHUNK),
                              lambda b_, h_, c: (0, 0, 0)),
        "states": pl.BlockSpec(
            (None, None, None, heads, d_v, d_k),
            lambda b_, h_, c: (ci(c) // per, ci(c) % per, b_, h_, 0, 0)),
        "dbeta": pl.BlockSpec((None, None, None, heads, 1, CHUNK),
                              lambda b_, h_, c: (b_, h_, ci(c), 0, 0, 0)),
    }


def _flat(x):
    """(B, T, H, D) -> (B, T, H * D): the same bytes."""
    return x.reshape(x.shape[:2] + (-1,))


def _pad_time(x, t_pad):
    if x.shape[1] == t_pad:
        return x
    return jnp.pad(x, ((0, 0), (0, t_pad - x.shape[1]))
                   + ((0, 0),) * (x.ndim - 2))


def _operands(q, k, v, g, beta, t_pad):
    """The kernels' views of the op's inputs, T padded with zeros to whole
    chunks (a padded token has k = 0, beta = 0, g = 0: it leaves the state
    as it is)."""
    return tuple(_pad_time(_flat(x) if x.ndim == 4 else x, t_pad)
                 for x in (q, k, v, g, beta))


def _call(name, kernel, q, v, scale, reverse, interpret):
    """(`pl.pallas_call` of `kernel` but for its specs and out_shape, the
    BlockSpecs by operand kind, T padded to whole chunks)."""
    b, t, h, d_k = q.shape
    d_v = v.shape[3]
    heads = pick_heads(h)
    t_pad = -(-t // CHUNK) * CHUNK
    call = functools.partial(
        pl.pallas_call,
        functools.partial(kernel, scale=scale, heads=heads,
                          mxu=_mxu_dtype(q.dtype)),
        grid=(b, h // heads, t_pad // CHUNK),
        scratch_shapes=[pltpu.VMEM((heads, d_v, d_k), _F32),
                        pltpu.VMEM((heads, CHUNK, d_k), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_bytes(name, heads, d_k, d_v,
                                        q.dtype.itemsize) + _VMEM_MARGIN),
        name=name, interpret=interpret)
    return call, _specs(b, t_pad, h, d_k, d_v, heads, reverse), t_pad


# jitted: the call's pads, views and the kernel stay one computation a call
# site (the Kimi step ran 0.9% faster and held 0.16 GiB less so: XLA places
# the step's buffers otherwise), traced once a shape and process
@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _forward(q, k, v, g, beta, scale, interpret):
    b, _t, h, d_k = q.shape
    d_v = v.shape[3]
    call, sp, t_pad = _call("kda_fwd", _fwd_kernel, q, v, scale, False,
                            interpret)
    per, groups = _groups_of(t_pad)
    return call(
        in_specs=[sp["k"], sp["k"], sp["v"], sp["k"], sp["beta"],
                  sp["sums"], sp["masks"]],
        out_specs=[sp["v"], sp["states"]],
        out_shape=[jax.ShapeDtypeStruct((b, t_pad, h * d_v), v.dtype),
                   jax.ShapeDtypeStruct((groups, per, b, h, d_v, d_k),
                                        _F32)],
    )(*_operands(q, k, v, g, beta, t_pad), *_constants())


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _backward(q, k, v, g, beta, states, d_out, scale, interpret):
    b, t, h, d_k = q.shape
    d_v = v.shape[3]
    heads = pick_heads(h)
    call, sp, t_pad = _call("kda_bwd", _bwd_kernel, q, v, scale, True,
                            interpret)
    wide_k = jax.ShapeDtypeStruct((b, t_pad, h * d_k), q.dtype)
    dq, dk, dv, dg, dbeta = call(
        in_specs=[sp["k"], sp["k"], sp["v"], sp["k"], sp["beta"],
                  sp["sums"], sp["masks"], sp["states"], sp["v"]],
        out_specs=[sp["k"], sp["k"], sp["v"], sp["k"], sp["dbeta"]],
        out_shape=[wide_k, wide_k,
                   jax.ShapeDtypeStruct((b, t_pad, h * d_v), v.dtype),
                   jax.ShapeDtypeStruct((b, t_pad, h * d_k), _F32),
                   jax.ShapeDtypeStruct(
                       (b, h // heads, t_pad // CHUNK, heads, 1, CHUNK),
                       _F32)],
    )(*_operands(q, k, v, g, beta, t_pad), *_constants(), states,
      _pad_time(_flat(d_out.astype(v.dtype)), t_pad))
    # (B, H / HB, chunks, HB, 1, 64) -> (B, T, H)
    dbeta = jnp.moveaxis(dbeta[:, :, :, :, 0], 3, 2).reshape(b, h, t_pad)
    dbeta = jnp.moveaxis(dbeta, 1, 2)[:, :t].astype(beta.dtype)
    return (dq[:, :t].reshape(q.shape), dk[:, :t].reshape(k.shape),
            dv[:, :t].reshape(v.shape),
            dg[:, :t].reshape(g.shape).astype(g.dtype), dbeta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def kda(q, k, v, g, beta, scale, interpret=False):
    """The gated delta rule by the kernels. q, k, g (B, T, H, K), v (B, T,
    H, V), beta (B, T, H), K and V multiples of 128 (`plan` says); o (B, T,
    H, V) in v's dtype. `interpret=True` runs the kernel bodies in
    interpret mode (the tests)."""
    return _kda_fwd(q, k, v, g, beta, scale, interpret)[0]


def _kda_fwd(q, k, v, g, beta, scale, interpret):
    out, states = _forward(q, k, v, g, beta, scale=scale,
                           interpret=interpret)
    return (out[:, :q.shape[1]].reshape(v.shape),
            (q, k, v, g, beta, states))


def _kda_bwd(scale, interpret, res, d_out):
    *inputs, states = res
    return _backward(*inputs, states, d_out, scale=scale,
                     interpret=interpret)


kda.defvjp(_kda_fwd, _kda_bwd)
