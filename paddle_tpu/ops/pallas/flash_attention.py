"""Pallas TPU flash attention.

The hot op of every BASELINE transformer config. Tiles Q/K/V blocks through
VMEM with online-softmax accumulation — the (T,T) score matrix never touches
HBM, so attention becomes MXU-bound instead of HBM-bound for long sequences.

Three or four kernels a call, each with the tile `pick_blocks` gives it
for the call's shape (on a v5e up to 1024x1024: a grid step costs
microseconds whatever it holds, so few wide steps win). bfloat16 q, k, v,
dO go to the MXU as they are and p, ds are cast down for the second
matmuls, all with float32 results; every other dtype runs float32 operands
at HIGHEST. The online softmax state (m, l, the accumulators), exp and the
scale stay float32.

Forward (`flash_fwd`): grid (B*H, Tq/BQ, Tk/BK), f32 accumulators in VMEM
scratch persisting across the (innermost, sequential) k-block dimension;
emits the softmax statistics (row max m, normalizer l) alongside the
output.
Backward: the kernels recompute p = exp(s - m) / l per tile from the saved
(out, m, l) residuals — flash-attention-2 style, no (T,T) matrix in HBM in
either direction, with the additive mask applied in-kernel. Which of two
forms a call gets is `backward_rule`'s answer, from its shapes:
  - fused (`flash_bwd`; no window, any group of query heads a key/value
    head, any two widths, and its rows fit VMEM: GPT's plain causal call,
    BERT's key-masked one, latent attention's D 192 | Dv 128, and the
    grouped-query calls of SDAR, SmallThinker's global layer, LFM2,
    Nemotron and Phi's full layers): grid (B*H, Tk/BK, Tq/BQ), q-blocks
    innermost. A tile's s, p, dp and ds are formed once and feed all three
    gradients: dK/dV in accumulators written when the k-block's last
    q-block is done, dQ in a float32 scratch of the head's whole query
    length, written to HBM once a head. Five matmuls a tile. With n > 1
    query heads a kv head the grid is (B*Hkv, n, Tk/BK, Tq/BQ): dQ's row
    stays one head's, and dK/dV are summed over the group's heads in
    float32 scratch as long as the kv head's whole row, each block written
    to HBM once, when the group's last head is through with it.
  - split (`flash_bwd_dkv`, grid as the fused kernel's at n = 1 with the
    group's heads folded into the innermost axis, and `flash_bwd_dq`, grid
    as the forward's): each forms s, p, dp, ds for itself, seven matmuls a
    tile between them. Windows run here (their grids walk a banded inner
    axis), and a call so long that the fused kernel's rows do not fit.
Under a causal mask a grid step above the diagonal runs no body, and its
index maps name the block the nearest working step holds, so it fetches
nothing either.
The statistics stay separate on purpose: folding them into
lse = m + log(l) puts the hardware log/exp approximation error into the
exponent — measured on a v5e, sum(p) then sat ~3e-5 off 1 and the f32
gradients 2e-5..9e-5 off a float64 oracle (XLA's own: 1e-6). The mask
cotangent (needed only for learned biases) is a separate XLA expression
that DCEs away when unused.

Layout contract: q is (B, Hq, Tq, D), k (B, Hkv, Tk, D), v (B, Hkv, Tk,
Dv); additive mask broadcastable (B, 1, 1, Tk) or (B, 1, Tq, Tk). On CPU
(tests) the kernel runs in interpret mode.

The supported (heads, window, widths) space:
  - heads: Hq = n * Hkv for any whole n >= 1 (grouped-query attention):
    query head h reads key/value head h // n. The forward and dQ kernels
    name the kv head in their index maps; the fused backward walks the
    group's n query heads on a grid axis of its own and the dK/dV kernel
    on its innermost one, and both sum them in float32.
    `Hq % Hkv != 0` is a ValueError.
  - window: `window=W` (only with `causal=True`) makes key s visible to
    query t iff t - W < s <= t (bottom-right aligned like the causal
    mask). The innermost grid axis then spans only the blocks a tile row
    can see (not the whole sequence), starting at the row's first visible
    block. `window <= 0`, or a window without `causal`, is a ValueError.
  - widths: the value width Dv may differ from the q/k width D (the
    output and dO are Dv wide). D % 8 or Dv % 8 != 0 goes to XLA. D need
    be no multiple of the 128 lanes: a block spans the whole head width,
    and D = 192 with Dv = 128 (latent attention's decompressed heads)
    compiles as it is, on the fused backward (192 pads to 256 lanes in
    VMEM only: dQ's row is weighed at 256, dV's accumulator at 128).
With n == 1, no window and Dv == D the forward kernel, its tile, index
maps and VMEM request are the ones the plain causal call always had; with
no window the backward is the fused kernel whatever n and Dv are, and at
n == 1 it lowers to the text it had before it took a group.
"""
import functools
from typing import NamedTuple, Optional

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

_NEG_INF = -1e30


def _dot_precision(dtype):
    """MXU precision for kernel matmuls given the user-facing dtype.

    f32 (and fp16: 10 mantissa bits > bf16's 7) inputs at DEFAULT
    precision run a single bf16 pass on the MXU (~1e-3 relative error) —
    a user asking for f32/fp16 attention gets full-precision math
    (HIGHEST = multi-pass), matching the reference's true-precision CUDA
    kernels. bf16 inputs stay on the fast path: their products are exact
    in the f32 accumulator, so DEFAULT already matches the oracle."""
    return (jax.lax.Precision.DEFAULT
            if jnp.dtype(dtype) == jnp.bfloat16 else
            jax.lax.Precision.HIGHEST)


def _mxu_dtype(dtype):
    """What the kernels hand the MXU. bfloat16 inputs go in as they are:
    DEFAULT precision rounds 32-bit operands to bfloat16 for its one pass
    anyway, so an upcast only costs converts and twice the vector
    registers; the probabilities (and ds) are cast down for the second
    matmuls exactly as `_xla_attention` does. Every other dtype keeps
    float32 operands at HIGHEST."""
    return (jnp.bfloat16 if jnp.dtype(dtype) == jnp.bfloat16
            else jnp.float32)


def _tile_positions(qi, kj, block_q, block_k):
    """(BQ, BK) int32 tiles of each score's query and key position."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return q_pos, k_pos


def _causal_keep(qi, kj, causal_offset, block_q, block_k):
    """Bool (BQ, BK) tile of the bottom-right-aligned causal mask
    (query i sees keys j <= i + causal_offset) — shared by all kernels."""
    q_pos, k_pos = _tile_positions(qi, kj, block_q, block_k)
    return q_pos + causal_offset >= k_pos


def _last_key(qi, causal_offset, block_q):
    """Last key that q-block `qi`'s last query sees under the causal mask."""
    return qi * block_q + (block_q - 1) + causal_offset


def _last_k_block(qi, causal_offset, block_q, block_k):
    """Last k-block that q-block `qi` sees under the causal mask."""
    return _last_key(qi, causal_offset, block_q) // block_k


def _first_q_block(kj, causal_offset, block_q, block_k):
    """First q-block that sees k-block `kj` under the causal mask."""
    return jnp.maximum(kj * block_k - causal_offset, 0) // block_q


def _window_keep(qi, kj, causal_offset, block_q, block_k, window):
    """Bool (BQ, BK) tile of the causal sliding window: query i sees keys
    j with i + causal_offset - window < j <= i + causal_offset."""
    q_pos, k_pos = _tile_positions(qi, kj, block_q, block_k)
    rel = q_pos + causal_offset - k_pos
    return (rel >= 0) & (rel < window)


def _keep_tile(qi, kj, causal_offset, block_q, block_k, window):
    if window is None:
        return _causal_keep(qi, kj, causal_offset, block_q, block_k)
    return _window_keep(qi, kj, causal_offset, block_q, block_k, window)


def _window_first_k_block(qi, causal_offset, block_q, block_k, window):
    """First k-block that q-block `qi`'s first query sees in its window.
    Works on Python ints and on traced scalars alike."""
    lo = qi * block_q + causal_offset - window + 1
    lo = max(lo, 0) if isinstance(lo, int) else jnp.maximum(lo, 0)
    return lo // block_k


def _window_last_q_block(kj, causal_offset, block_q, block_k, window, nq):
    """Last q-block that still has k-block `kj`'s last key in a window."""
    hi = (kj * block_k + block_k - 1 - causal_offset + window - 1) // block_q
    return min(hi, nq - 1) if isinstance(hi, int) else jnp.minimum(hi, nq - 1)


def _div(x, n):
    """x // n for x >= 0, Python ints and int32 arrays alike: a shift where
    n is a power of two (the TPU's vector unit has no integer divide)."""
    return x >> (n.bit_length() - 1) if n & (n - 1) == 0 else x // n


def _bd_tile_blocks(i, block, bd):
    """Of tile `i` of `block` rows among the 2T rows [noisy | clean] of a
    block-diffusion call (`bd` = (L, T); tiles divide T, so a tile lies in
    one half): (whether it is of the noisy half, the first and the last
    diffusion block its rows belong to; positions count from 0 in each
    half). Python ints and traced scalars alike."""
    length, t = bd
    row = i * block
    pos = row % t
    return row < t, pos // length, (pos + block - 1) // length


def _bd_tile_visible(qi, kj, block_q, block_k, bd):
    """Whether any query of q-tile `qi` sees any key of k-tile `kj` under
    the block-diffusion rule (`_block_diffusion_keep`). Python ints (the
    plan's count) and traced scalars (the kernels' `pl.when`) alike."""
    q_noisy, q_lo, q_hi = _bd_tile_blocks(qi, block_q, bd)
    k_noisy, k_lo, k_hi = _bd_tile_blocks(kj, block_k, bd)
    q_clean, k_clean = qi * block_q >= bd[1], kj * block_k >= bd[1]
    return ((q_noisy & k_noisy & (k_lo <= q_hi) & (q_lo <= k_hi))
            | (q_noisy & k_clean & (k_lo < q_hi))
            | (q_clean & k_clean & (k_lo <= q_hi)))


def _block_diffusion_keep(qi, kj, block_q, block_k, length, t):
    """Bool (BQ, BK) tile of the block-diffusion mask over 2T rows, the
    noisy copy first: row r has position r mod T and block position // L;
    a clean query sees the clean keys of its own and earlier blocks, a
    noisy query the noisy keys of its own block and the clean keys of
    strictly earlier blocks, and no clean query a noisy key. From the
    tile's indices alone: the blocks of the tile's rows as a column, of its
    keys as a row (one shift each where L is a power of two), and the
    range of key blocks a row sees, [own - below, own - above], which the
    tile's two halves set as scalars."""
    q_blk = _div(qi * block_q % t + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0), length)
    k_blk = _div(kj * block_k % t + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1), length)
    q_noisy, k_noisy = qi * block_q < t, kj * block_k < t
    none = 2 * t                # more than any block's index
    above = jnp.where(k_noisy, jnp.where(q_noisy, 0, none),
                      jnp.where(q_noisy, 1, 0))
    below = jnp.where(k_noisy & q_noisy, 0, none)
    return (k_blk <= q_blk - above) & (k_blk >= q_blk - below)


def _bd_nearest_k(qi, kj, block_q, block_k, bd):
    """The k-block a (qi, kj) grid step of the forward and dQ kernels
    names under the block-diffusion rule: kj where the tile runs, else the
    nearest k-block of row `qi` whose tile does, so that a step with no
    work fetches nothing. A noisy row's tiles are its diagonal ones in the
    noisy half and the clean tiles that begin before its last block; a
    clean row's the clean tiles up to its own."""
    length, t = bd
    half = t // block_k
    noisy, q_lo, q_hi = _bd_tile_blocks(qi, block_q, bd)
    d_lo = q_lo * length // block_k
    d_hi = ((q_hi + 1) * length - 1) // block_k
    seen = jnp.where(noisy, (q_hi * length + block_k - 1) // block_k,
                     d_hi + 1)              # clean k-tiles the row sees
    diagonal = jnp.clip(kj, d_lo, d_hi)
    clean = jnp.minimum(jnp.maximum(kj, half), half + seen - 1)
    return jnp.where(noisy & ((kj < half) | (seen == 0)), diagonal, clean)


def _bd_nearest_q(kj, qi, block_q, block_k, bd):
    """`_bd_nearest_k`'s counterpart for the dK/dV kernels, whose innermost
    axis walks q-blocks: a noisy key tile is seen by its diagonal q-tiles
    of the noisy half alone, a clean one by the noisy q-tiles that end
    after its first block and by the clean q-tiles from its own on."""
    length, t = bd
    half = t // block_q
    noisy, k_lo, k_hi = _bd_tile_blocks(kj, block_k, bd)
    e_lo = k_lo * length // block_q
    e_hi = ((k_hi + 1) * length - 1) // block_q
    first_noisy = (k_lo + 1) * length // block_q
    clean = jnp.maximum(qi, half + e_lo)
    return jnp.where(
        noisy, jnp.clip(qi, e_lo, e_hi),
        jnp.where((qi < half) & (first_noisy < half),
                  jnp.maximum(qi, first_noisy), clean))


def window_grid(tq, tk, block_q, block_k, window, kj_innermost):
    """Length of the innermost grid axis of a windowed kernel: the most
    blocks any tile row (kj_innermost: a q-block's k-blocks; else a
    k-block's q-blocks) can see. Python ints only."""
    off, nq, nk = tk - tq, tq // block_q, tk // block_k
    if kj_innermost:
        return max(
            (qi * block_q + block_q - 1 + off) // block_k
            - _window_first_k_block(qi, off, block_q, block_k, window) + 1
            for qi in range(nq))
    return max(
        _window_last_q_block(kj, off, block_q, block_k, window, nq)
        - max(kj * block_k - off, 0) // block_q + 1 for kj in range(nk))


def _for_visible_tile(body, qi, kj, *, causal, causal_offset, block_q,
                      block_k, last_q=None, block_diffusion=None):
    """Run `body` unless the (qi, kj) tile lies wholly above the causal
    diagonal (no query of the tile sees any of its keys). Under a window
    the grid starts each row at its first visible block, so only the far
    end is left to test: in the dK/dV kernel that is `last_q`, the
    k-block's last q-block. Under the block-diffusion rule the grid is the
    whole square and `_bd_tile_visible` says which of its tiles run."""
    if block_diffusion is not None:
        pl.when(_bd_tile_visible(qi, kj, block_q, block_k,
                                 block_diffusion))(body)
        return
    if not causal:
        body()
        return
    if last_q is not None:
        pl.when(qi <= last_q)(body)
        return
    pl.when(kj * block_k <= _last_key(qi, causal_offset, block_q))(body)


def _bwd_p_ds(q_ref, k_ref, v_ref, do_ref, stats_ref, delta_ref, mask_ref,
              qi, kj, *, scale, causal, causal_offset, block_q, block_k,
              mask_mode, precision, window=None, block_diffusion=None):
    """Recompute the probability tile p = exp(s - m) / l — the forward's
    own normalization — and the logit cotangent ds = p * (dO V^T - delta)
    from the forward residuals: the shared core of both backward
    kernels. p and ds come back in the MXU's operand dtype."""
    mxu = _mxu_dtype(q_ref.dtype)
    q = q_ref[0].astype(mxu)                    # (BQ, D)
    k = k_ref[0].astype(mxu)                    # (BK, D)
    v = v_ref[0].astype(mxu)
    do = do_ref[0].astype(mxu)                  # (BQ, D)
    m = stats_ref[0, 0]                         # (BQ,) row max
    inv_l = 1.0 / stats_ref[0, 1]               # (BQ,) 1 / normalizer
    delta = delta_ref[0, 0].astype(jnp.float32)  # row 0 is real
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision) * scale  # (BQ, BK)
    if mask_mode == "qk":
        s = s + mask_ref[0, 0].astype(jnp.float32)
    elif mask_mode == "k":
        s = s + mask_ref[0, 0, 0][None, :].astype(jnp.float32)
    p = jnp.exp(s - m[:, None]) * inv_l[:, None]
    if causal:
        p = jnp.where(_keep_tile(qi, kj, causal_offset, block_q, block_k,
                                 window), p, 0.0)
    if block_diffusion is not None:
        p = jnp.where(_block_diffusion_keep(qi, kj, block_q, block_k,
                                            *block_diffusion), p, 0.0)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=precision)                    # (BQ, BK)
    ds = p * (dp - delta[:, None])
    return q, k, do, p.astype(mxu), ds.astype(mxu)


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, stats_ref, acc_ref,
                m_ref, l_ref, *, scale, causal, causal_offset, block_q,
                block_k, mask_mode, precision, window=None,
                block_diffusion=None):
    qi = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    mxu = _mxu_dtype(q_ref.dtype)
    kj = j if window is None else j + _window_first_k_block(
        qi, causal_offset, block_q, block_k, window)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def body():
        q = q_ref[0].astype(mxu)                  # (BQ, D)
        k = k_ref[0].astype(mxu)                  # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision) * scale  # (BQ, BK)
        if mask_mode == "qk":
            s = s + mask_ref[0, 0].astype(jnp.float32)
        elif mask_mode == "k":
            s = s + mask_ref[0, 0, 0][None, :].astype(jnp.float32)
        if causal:
            # bottom-right aligned for Tq != Tk (matches _xla_attention's
            # tril(..., tk - tq)): query i sees keys j <= i + (tk - tq)
            s = jnp.where(_keep_tile(qi, kj, causal_offset, block_q,
                                     block_k, window), s, _NEG_INF)
        if block_diffusion is not None:
            # a row no key of which lies in this tile gathers weights of 1
            # here; the first tile that holds one of its keys scales them
            # to nothing (corr = exp(-1e30 - m)), and every row has a key
            s = jnp.where(_block_diffusion_keep(
                qi, kj, block_q, block_k, *block_diffusion), s, _NEG_INF)

        m_prev = m_ref[:, :1]                      # (BQ, 1)
        m_blk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_blk)
        p = jnp.exp(s - m_new)                     # (BQ, BK)
        corr = jnp.exp(m_prev - m_new)             # (BQ, 1)
        l_new = corr * l_ref[:, :1] + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(mxu), v_ref[0].astype(mxu), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision)                   # (BQ, D)
        acc_ref[:] = acc_ref[:] * corr + pv
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    _for_visible_tile(body, qi, kj, causal=causal,
                      causal_offset=causal_offset, block_q=block_q,
                      block_k=block_k, block_diffusion=block_diffusion)

    @pl.when(j == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # softmax statistics for the Pallas backward, packed into the
        # sublane dim of 8 that Mosaic's (8, 128) block alignment needs
        # anyway: row 0 = running max m, rows 1.. = normalizer l
        row = jax.lax.broadcasted_iota(jnp.int32, stats_ref.shape[1:], 0)
        stats_ref[0] = jnp.where(row == 0, m_ref[:, 0][None, :],
                                 l[:, 0][None, :])


def _seq_specs(h, d, mask_mode, causal, causal_offset, block_q, block_k,
               kj_innermost, dv=None, group=1, window=None, n_inner=None,
               nq=None, block_diffusion=None, head_axis=False):
    """BlockSpecs of one kernel: `q_spec` for what is tiled along the
    queries at the q/k width (q, dq: (bh, block_q, D)), `row_spec` for the
    per-row statistics ((bh, 8, block_q)), `k_spec` for k and dk,
    `mask_spec` for the additive mask, and `o_spec` / `v_spec` for what is
    `dv` wide (out, dO / v, dv; the same objects as `q_spec` / `k_spec`
    where the widths are equal). Grid index order is (bh, i, j) for the
    forward/dQ kernels (kj_innermost) and (bh, j, i) for dK/dV. A causal
    grid step above the diagonal does no work, so its innermost index is
    clamped to the nearest block that does: the step then names the block
    its neighbour holds and fetches nothing. Under a `window` the innermost
    axis counts from the row's first visible block and is clamped at its
    last. With `group` query heads to a kv head, the first grid axis is
    over query heads where kj_innermost (the kv row is bb // group) and
    over kv heads for dK/dV, whose innermost axis then walks the group's
    heads, `n_inner` q-blocks each. `h` is the heads of the first axis.
    Under `block_diffusion` a step whose tile no query sees names the
    nearest block of its row that one does (`_bd_nearest_k` / `_q`).
    `head_axis` is the fused backward's grid for a group: (kv head, head of
    the group, j, i), the head on an axis of its own."""
    if kj_innermost:
        def ij(a, b_):
            if block_diffusion is not None:
                b_ = _bd_nearest_k(a, b_, block_q, block_k, block_diffusion)
            elif window is not None:
                b_ = jnp.minimum(
                    b_ + _window_first_k_block(a, causal_offset, block_q,
                                               block_k, window),
                    _last_k_block(a, causal_offset, block_q, block_k))
            elif causal:
                b_ = jnp.minimum(b_, _last_k_block(a, causal_offset,
                                                   block_q, block_k))
            return a, b_
    else:
        def ij(a, b_):
            if group != 1 and not head_axis:
                b_ = b_ % n_inner
            if block_diffusion is not None:
                b_ = _bd_nearest_q(a, b_, block_q, block_k, block_diffusion)
            elif window is not None:
                b_ = jnp.minimum(
                    b_ + _first_q_block(a, causal_offset, block_q, block_k),
                    _window_last_q_block(a, causal_offset, block_q, block_k,
                                         window, nq))
            elif causal:
                b_ = jnp.maximum(b_, _first_q_block(a, causal_offset,
                                                    block_q, block_k))
            return b_, a

    if group == 1:
        def q_row(bb, b_, head):
            return bb

        kv_row = q_row
    elif kj_innermost:
        def q_row(bb, b_, head):
            return bb

        def kv_row(bb, b_, head):
            return bb // group
    else:
        def q_row(bb, b_, head):
            return bb * group + (head if head_axis else b_ // n_inner)

        def kv_row(bb, b_, head):
            return bb

    def q_map(bb, a, b_, head=None):
        return (q_row(bb, b_, head), ij(a, b_)[0], 0)

    def row_map(bb, a, b_, head=None):
        return (q_row(bb, b_, head), 0, ij(a, b_)[0])

    def kv_map(bb, a, b_, head=None):
        return (kv_row(bb, b_, head), ij(a, b_)[1], 0)

    def on_grid(index_map):
        if not head_axis:
            return index_map
        return lambda bb, head, a, b_: index_map(bb, a, b_, head)

    if mask_mode == "none":
        mask_spec = pl.BlockSpec(
            (1, 1, 1, 1), on_grid(lambda bb, a, b_, head=None: (0, 0, 0, 0)))
    elif mask_mode == "k":
        mask_spec = pl.BlockSpec(
            (1, 1, 1, block_k), on_grid(
                lambda bb, a, b_, head=None: (bb // h, 0, 0, ij(a, b_)[1])))
    else:
        mask_spec = pl.BlockSpec(
            (1, 1, block_q, block_k), on_grid(
                lambda bb, a, b_, head=None: (bb // h, 0) + ij(a, b_)))
    q_spec = pl.BlockSpec((1, block_q, d), on_grid(q_map))
    k_spec = pl.BlockSpec((1, block_k, d), on_grid(kv_map))
    if dv is None or dv == d:
        o_spec, v_spec = q_spec, k_spec
    else:
        o_spec = pl.BlockSpec((1, block_q, dv), on_grid(q_map))
        v_spec = pl.BlockSpec((1, block_k, dv), on_grid(kv_map))
    return (q_spec, pl.BlockSpec((1, 8, block_q), on_grid(row_map)), k_spec,
            mask_spec, o_spec, v_spec)


def _mask_mode(mask):
    """An additive mask broadcastable (B,1,1,Tk) is "k", (B,1,Tq,Tk) "qk"."""
    if mask is None:
        return "none"
    return "k" if mask.shape[2] == 1 else "qk"


def _mask_input(mask, dtype):
    """The kernels' mask operand: a (1,1,1,1) dummy where there is none."""
    return jnp.zeros((1, 1, 1, 1), dtype) if mask is None else mask


# Score-shaped (block_q, block_k) f32 tiles that Mosaic keeps in VMEM at
# once, beside the blocks and the accumulators: found by bisecting
# `vmem_limit_bytes` on compiles for a v5e (bf16 D=64 and f32 D=128 with a
# key mask, 512x512 and 1024x1024: at most 2.0 / 4.4 / 3.3, and 6.8 for
# the fused backward, whose bf16 tiles need 1.9) and rounded up. At D=192 /
# Dv=128 the fused backward's 8 still bound it (bf16, Tq=8192: least limit
# 30.3 MiB at 1024x1024 where 54.1 are reckoned, 21.6 of 27.1 at 512x512;
# f32 with a key mask at Tq=2048, 512x512: 9.9 of 19.3)
_TILE_TEMPS = {"fwd": 3, "bwd_dkv": 6, "bwd_dq": 5, "bwd": 8}
_VMEM_DEFAULT = 16 * 2 ** 20    # Mosaic's scoped limit on a v5e
_VMEM_CEILING = 96 * 2 ** 20    # of the chip's 128 MiB


def vmem_bytes(kernel, block_q, block_k, d, itemsize, mask_mode="none",
               dv=None, tq=0, tk=0):
    """Upper reckoning of the VMEM one grid step of `kernel` holds: every
    block twice (the pipeline's two buffers) with its lanes padded to 128,
    the f32 accumulators, and `_TILE_TEMPS` f32 score-shaped tiles. `dv`
    is the value width where it differs from `d`; `tq` the query length,
    which only the fused backward ("bwd") holds whole: its dQ block and
    the f32 row that dQ is summed in; `tk` the key length where that
    kernel sums dK and dV over a group of query heads, in f32 rows as long
    as the kv head's (0: one query head a kv head, a k-block's
    accumulators; `_bwd_rows` gives both)."""
    lanes = -(-d // 128) * 128
    lanes_v = lanes if dv is None else -(-dv // 128) * 128
    q_blk, k_blk = block_q * lanes, block_k * lanes
    o_blk, v_blk = block_q * lanes_v, block_k * lanes_v
    row_blk = 8 * block_q * 4
    mask_blk = {"none": 0, "k": 8 * block_k,
                "qk": block_q * block_k}[mask_mode] * itemsize
    if kernel == "fwd":
        blocks = (q_blk + o_blk + k_blk + v_blk) * itemsize + row_blk
        scratch = (o_blk + 2 * block_q * 128) * 4
    elif kernel == "bwd_dkv":
        blocks = (q_blk + o_blk + 2 * k_blk + 2 * v_blk) * itemsize \
            + 2 * row_blk
        scratch = (k_blk + v_blk) * 4
    elif kernel == "bwd":
        blocks = (q_blk + o_blk + 2 * k_blk + 2 * v_blk
                  + tq * lanes) * itemsize + 2 * row_blk
        scratch = (max(k_blk, tk * lanes) + max(v_blk, tk * lanes_v)
                   + tq * lanes) * 4
    else:
        blocks = (2 * q_blk + o_blk + k_blk + v_blk) * itemsize \
            + 2 * row_blk
        scratch = q_blk * 4
    return (2 * (blocks + mask_blk) + scratch
            + _TILE_TEMPS[kernel] * block_q * block_k * 4)


def _bwd_rows(tq, tk, group):
    """`vmem_bytes`' `tq` and `tk` of a fused backward: dQ's row always,
    dK/dV's only where a group of query heads is summed in them."""
    return {"tq": tq, "tk": tk if group > 1 else 0}


def _compiler_params(kernel, block_q, block_k, d, dtype, mask_mode,
                     dv=None, tq=0, tk=0):
    """Ask Mosaic for the VMEM the tile is reckoned to need where its
    default would not do, so that a large tile compiles instead of
    failing. The fused backward also says that its inner grid axes (two,
    and the group's heads where `tk` says there is a group) run in order:
    dQ's row and dK/dV's accumulators live across them."""
    need = vmem_bytes(kernel, block_q, block_k, d, jnp.dtype(dtype).itemsize,
                      mask_mode, dv, tq, tk)
    limit = None if need <= _VMEM_DEFAULT else min(need, _VMEM_CEILING)
    if kernel == "bwd":
        return pltpu.CompilerParams(
            dimension_semantics=("parallel",)
            + ("arbitrary",) * (3 if tk else 2),
            vmem_limit_bytes=limit)
    if limit is None:
        return None
    return pltpu.CompilerParams(vmem_limit_bytes=limit)


def _rule_kwargs(window, block_diffusion):
    """The kernels' `window` and `block_diffusion` keywords, each left out
    where there is none."""
    extra = {} if window is None else {"window": window}
    if block_diffusion is not None:
        extra["block_diffusion"] = tuple(block_diffusion)
    return extra


def _pallas_forward(q, k, v, mask, scale, causal, block_q, block_k,
                    interpret, window=None, block_diffusion=None):
    if not _HAS_TPU_PALLAS:
        raise NotImplementedError("pallas tpu backend unavailable")
    b, h, tq, d = q.shape
    hkv, tk, dv = k.shape[1], k.shape[2], v.shape[-1]
    bh = b * h
    q3 = q.reshape(bh, tq, d)
    k3 = k.reshape(b * hkv, tk, d)
    v3 = v.reshape(b * hkv, tk, dv)

    mask_mode = _mask_mode(mask)
    q_spec, row_spec, k_spec, mask_spec, o_spec, v_spec = _seq_specs(
        h, d, mask_mode, causal, tk - tq, block_q, block_k,
        kj_innermost=True, dv=dv, group=h // hkv, window=window,
        block_diffusion=block_diffusion)
    nk = tk // block_k if window is None else window_grid(
        tq, tk, block_q, block_k, window, True)
    out, stats = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          causal_offset=tk - tq, block_q=block_q,
                          block_k=block_k, mask_mode=mask_mode,
                          precision=_dot_precision(q.dtype),
                          **_rule_kwargs(window, block_diffusion)),
        grid=(bh, tq // block_q, nk),
        in_specs=[q_spec, k_spec, v_spec, mask_spec],
        out_specs=[o_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, 8, tq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=_compiler_params("fwd", block_q, block_k, d,
                                         q.dtype, mask_mode, dv),
        name="flash_fwd",
        interpret=interpret,
    )(q3, k3, v3, _mask_input(mask, q.dtype))
    return out.reshape(b, h, tq, dv), stats


def _add_dkv(dk_acc, dv_acc, q, do, p, ds, scale, precision,
             at=slice(None)):
    """dv += p^T dO ; dk += scale * ds^T q, into the f32 accumulators
    (`at`: the k-block's slab of them, where they hold a whole row)."""
    dv_acc[at] = dv_acc[at] + jax.lax.dot_general(
        p, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision)
    dk_acc[at] = dk_acc[at] + scale * jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32, precision=precision)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, stats_ref, delta_ref,
                    mask_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, causal_offset, block_q, block_k,
                    mask_mode, precision, window=None, n_inner=None,
                    nq=None, block_diffusion=None):
    """dK/dV for one k-block, accumulating over q-blocks (innermost grid
    dim; with grouped heads over the group's heads x their `n_inner`
    q-blocks). Recomputes p from the residuals — no (T,T) in HBM."""
    kj = pl.program_id(1)
    i = pl.program_id(2)
    nq_steps = pl.num_programs(2)
    qi = i if n_inner is None else i % n_inner
    last_q = None
    if window is not None:
        qi = qi + _first_q_block(kj, causal_offset, block_q, block_k)
        last_q = _window_last_q_block(kj, causal_offset, block_q, block_k,
                                      window, nq)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def body():
        q, _, do, p, ds = _bwd_p_ds(
            q_ref, k_ref, v_ref, do_ref, stats_ref, delta_ref, mask_ref,
            qi, kj, scale=scale, causal=causal,
            causal_offset=causal_offset, block_q=block_q,
            block_k=block_k, mask_mode=mask_mode, precision=precision,
            **_rule_kwargs(window, block_diffusion))
        _add_dkv(dk_acc, dv_acc, q, do, p, ds, scale, precision)

    _for_visible_tile(body, qi, kj, causal=causal,
                      causal_offset=causal_offset, block_q=block_q,
                      block_k=block_k, last_q=last_q,
                      block_diffusion=block_diffusion)

    @pl.when(i == nq_steps - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, stats_ref, delta_ref,
                   mask_ref, dq_ref, dq_acc, *, scale, causal,
                   causal_offset, block_q, block_k, mask_mode, precision,
                   window=None, block_diffusion=None):
    qi = pl.program_id(1)
    j = pl.program_id(2)
    nk = pl.num_programs(2)
    kj = j if window is None else j + _window_first_k_block(
        qi, causal_offset, block_q, block_k, window)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def body():
        _, k, _, _, ds = _bwd_p_ds(
            q_ref, k_ref, v_ref, do_ref, stats_ref, delta_ref, mask_ref,
            qi, kj, scale=scale, causal=causal,
            causal_offset=causal_offset, block_q=block_q,
            block_k=block_k, mask_mode=mask_mode, precision=precision,
            **_rule_kwargs(window, block_diffusion))
        dq_acc[:] = dq_acc[:] + scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)

    _for_visible_tile(body, qi, kj, causal=causal,
                      causal_offset=causal_offset, block_q=block_q,
                      block_k=block_k, block_diffusion=block_diffusion)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, stats_ref, delta_ref, mask_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *, scale,
                causal, causal_offset, block_q, block_k, mask_mode,
                precision, block_diffusion=None, group=1):
    """The fused backward: p and ds of a tile are formed once and feed all
    three gradients. Grid (bh, k-blocks, q-blocks) as dK/dV's, whose
    accumulators it keeps; dQ is summed in `dq_acc`, a float32 scratch of
    the head's whole query length (one (block_q, D) slab a q-block), over
    ascending k-blocks as the dQ kernel sums it, and leaves for HBM once,
    after the head's last tile.

    With `group` query heads a kv head the grid is (kv head, head of the
    group, k-blocks, q-blocks): dQ's row stays one head's, and dK/dV are
    summed over the group's heads, in the dK/dV kernel's order (heads,
    then q-blocks), in float32 scratch as long as the kv head's whole row
    (one (block_k, D | Dv) slab a k-block), each slab leaving for HBM when
    the group's last head is through with it."""
    if group == 1:
        kj, qi = pl.program_id(1), pl.program_id(2)
        nk, nq = pl.num_programs(1), pl.num_programs(2)
        at = slice(None)
    else:
        head, kj, qi = pl.program_id(1), pl.program_id(2), pl.program_id(3)
        nk, nq = pl.num_programs(2), pl.num_programs(3)
        at = kj             # the k-block's slab of dK/dV's rows

    def k_block_at(q_block, head_of_group):
        """Whether this step is that one of the k-block's walk."""
        here = qi == q_block
        return here if group == 1 else here & (head == head_of_group)

    @pl.when((kj == 0) & (qi == 0))
    def _init_head():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(k_block_at(0, 0))
    def _init():
        dk_acc[at] = jnp.zeros(dk_ref.shape[1:], jnp.float32)
        dv_acc[at] = jnp.zeros(dv_ref.shape[1:], jnp.float32)

    def body():
        q, k, do, p, ds = _bwd_p_ds(
            q_ref, k_ref, v_ref, do_ref, stats_ref, delta_ref, mask_ref,
            qi, kj, scale=scale, causal=causal,
            causal_offset=causal_offset, block_q=block_q,
            block_k=block_k, mask_mode=mask_mode, precision=precision,
            block_diffusion=block_diffusion)
        _add_dkv(dk_acc, dv_acc, q, do, p, ds, scale, precision, at)
        # dq[q-block] += scale * ds k
        dq_acc[qi] = dq_acc[qi] + scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)

    _for_visible_tile(body, qi, kj, causal=causal,
                      causal_offset=causal_offset, block_q=block_q,
                      block_k=block_k, block_diffusion=block_diffusion)

    @pl.when(k_block_at(nq - 1, group - 1))
    def _finalize():
        dk_ref[0] = dk_acc[at].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[at].astype(dv_ref.dtype)

    @pl.when((kj == nk - 1) & (qi == nq - 1))
    def _finalize_head():
        for n in range(dq_acc.shape[0]):
            dq_ref[0, n * block_q:(n + 1) * block_q, :] = \
                dq_acc[n].astype(dq_ref.dtype)


def _bwd_inputs(q, k, v, mask, out, stats, g):
    """The backward kernels' operands: (bh, T, D) views, and delta =
    rowsum(dO * O) (a cheap elementwise pass in XLA) with the sublane dim
    of 8 that the stats carry for Mosaic's block alignment."""
    b, h, tq, d = q.shape
    hkv, tk, dv = k.shape[1], k.shape[2], v.shape[-1]
    bh = b * h
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(bh, 1, tq)
    return (q.reshape(bh, tq, d), k.reshape(b * hkv, tk, d),
            v.reshape(b * hkv, tk, dv), g.reshape(bh, tq, dv), stats,
            jnp.broadcast_to(delta, (bh, 8, tq)),
            _mask_input(mask, q.dtype))


def _bwd_call(kernel, which, operands, h, mask_mode, scale, causal, block_q,
              block_k, interpret, kj_innermost, window=None,
              block_diffusion=None):
    """What the backward kernels' pallas_calls share (`which`: the
    kernel's name in KERNELS or FUSED_KERNELS): the kernel with its
    parameters and the keyword arguments for the seven operands (q, k, v,
    dO, stats, delta, mask) all take; then the BlockSpecs for the outputs
    (q-tiled, k-tiled, v-tiled). `h` is the query heads of a batch row;
    the kv heads follow from the operands. The fused backward of a group
    walks (kv head, head of the group, k-blocks, q-blocks)."""
    q3, k3, v3 = operands[:3]
    bh, tq, d = q3.shape
    bhkv, tk, dv = k3.shape[0], k3.shape[1], v3.shape[2]
    group = bh // bhkv
    head_axis = which == "bwd" and group != 1
    nq, nk = tq // block_q, tk // block_k
    n_q = nq if window is None else window_grid(tq, tk, block_q, block_k,
                                                window, False)
    n_k = nk if window is None else window_grid(tq, tk, block_q, block_k,
                                                window, True)
    q_spec, row_spec, k_spec, mask_spec, o_spec, v_spec = _seq_specs(
        h if kj_innermost else h // group, d, mask_mode, causal, tk - tq,
        block_q, block_k, kj_innermost, dv=dv, group=group, window=window,
        n_inner=n_q, nq=nq, block_diffusion=block_diffusion,
        head_axis=head_axis)
    extra = _rule_kwargs(window, block_diffusion)
    if head_axis:
        extra["group"] = group
        grid = (bhkv, group, nk, nq)
    elif kj_innermost:
        grid = (bh, nq, n_k)
    else:
        grid = (bhkv, nk, group * n_q)
        if window is not None:
            extra["nq"] = nq
        if group != 1:
            extra["n_inner"] = n_q
    body = functools.partial(kernel, scale=scale, causal=causal,
                             causal_offset=tk - tq, block_q=block_q,
                             block_k=block_k, mask_mode=mask_mode,
                             precision=_dot_precision(q3.dtype), **extra)
    rows = _bwd_rows(tq, tk, group) if which == "bwd" else {}
    common = dict(
        grid=grid,
        in_specs=[q_spec, k_spec, v_spec, o_spec, row_spec, row_spec,
                  mask_spec],
        compiler_params=_compiler_params(which, block_q, block_k, d,
                                         q3.dtype, mask_mode, dv, **rows),
        interpret=interpret)
    return body, common, q_spec, k_spec, v_spec


def _pallas_bwd_dkv(operands, h, mask_mode, scale, causal, block_q, block_k,
                    interpret, window=None, block_diffusion=None):
    body, common, _, k_spec, v_spec = _bwd_call(
        _bwd_dkv_kernel, "bwd_dkv", operands, h, mask_mode, scale, causal,
        block_q, block_k, interpret, kj_innermost=False, window=window,
        block_diffusion=block_diffusion)
    k3, v3 = operands[1:3]
    return pl.pallas_call(
        body,
        out_specs=[k_spec, v_spec],
        out_shape=[jax.ShapeDtypeStruct(k3.shape, k3.dtype),
                   jax.ShapeDtypeStruct(v3.shape, v3.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, k3.shape[2]), jnp.float32),
                        pltpu.VMEM((block_k, v3.shape[2]), jnp.float32)],
        name="flash_bwd_dkv",
        **common,
    )(*operands)


def _pallas_bwd_dq(operands, h, mask_mode, scale, causal, block_q, block_k,
                   interpret, window=None, block_diffusion=None):
    body, common, q_spec, _, _ = _bwd_call(
        _bwd_dq_kernel, "bwd_dq", operands, h, mask_mode, scale, causal,
        block_q, block_k, interpret, kj_innermost=True, window=window,
        block_diffusion=block_diffusion)
    q3 = operands[0]
    return pl.pallas_call(
        body,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q3.shape, q3.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, q3.shape[2]), jnp.float32)],
        name="flash_bwd_dq",
        **common,
    )(*operands)


def _pallas_bwd(operands, h, mask_mode, scale, causal, block_q, block_k,
                interpret, block_diffusion=None):
    """The fused backward's call (no window: `backward_rule`). dQ's block
    is the head's whole row under an index map that moves with the head
    alone, so it is written back once a head; dK's accumulator is as wide
    as q and k, dV's as wide as v. With a group of query heads a kv head
    the accumulators are the kv head's whole rows, and a dK/dV block is
    named only on the group's last head (before it: block 0, which that
    head names first), so each is written back once, summed."""
    body, common, _, k_spec, v_spec = _bwd_call(
        _bwd_kernel, "bwd", operands, h, mask_mode, scale, causal, block_q,
        block_k, interpret, kj_innermost=False,
        block_diffusion=block_diffusion)
    q3, k3, v3 = operands[:3]
    _bh, tq, d = q3.shape
    tk, dv = k3.shape[1], v3.shape[2]
    group = q3.shape[0] // k3.shape[0]
    if group == 1:
        dq_map, kv_rows = (lambda bb, a, b_: (bb, 0, 0)), (block_k,)
    else:
        def dq_map(bb, head, a, b_):
            return (bb * group + head, 0, 0)

        def summed(bb, head, a, b_):
            return (bb, jnp.where(head == group - 1, a, 0), 0)

        k_spec = pl.BlockSpec((1, block_k, d), summed)
        v_spec = pl.BlockSpec((1, block_k, dv), summed)
        kv_rows = (tk // block_k, block_k)
    return pl.pallas_call(
        body,
        out_specs=[pl.BlockSpec((1, tq, d), dq_map), k_spec, v_spec],
        out_shape=[jax.ShapeDtypeStruct(q3.shape, q3.dtype),
                   jax.ShapeDtypeStruct(k3.shape, k3.dtype),
                   jax.ShapeDtypeStruct(v3.shape, v3.dtype)],
        scratch_shapes=[pltpu.VMEM((tq // block_q, block_q, d), jnp.float32),
                        pltpu.VMEM(kv_rows + (d,), jnp.float32),
                        pltpu.VMEM(kv_rows + (dv,), jnp.float32)],
        name="flash_bwd",
        **common,
    )(*operands)


def _pallas_backward(q, k, v, mask, out, stats, g, scale, causal, blocks,
                     interpret, window=None, block_diffusion=None):
    b, h, tq, d = q.shape
    operands = _bwd_inputs(q, k, v, mask, out, stats, g)
    mask_mode = _mask_mode(mask)
    if _kernels_of(blocks) is FUSED_KERNELS:
        dq3, dk3, dv3 = _pallas_bwd(operands, h, mask_mode, scale, causal,
                                    *blocks[1], interpret,
                                    block_diffusion=block_diffusion)
    else:
        dk3, dv3 = _pallas_bwd_dkv(operands, h, mask_mode, scale, causal,
                                   *blocks[1], interpret, window=window,
                                   block_diffusion=block_diffusion)
        dq3 = _pallas_bwd_dq(operands, h, mask_mode, scale, causal,
                             *blocks[2], interpret, window=window,
                             block_diffusion=block_diffusion)
    return dq3.reshape(q.shape), dk3.reshape(k.shape), dv3.reshape(v.shape)


def visible_mask(tq, tk, window=None, block_diffusion=None):
    """Bool (Tq, Tk): the bottom-right-aligned causal mask, cut to the
    last `window` keys where there is one; or, with `block_diffusion` =
    (L, T) and Tq = Tk = 2T, the block-diffusion mask over [noisy | clean]
    rows (`_block_diffusion_keep` says it in words)."""
    if block_diffusion is not None:
        length, t = block_diffusion
        row = jnp.arange(2 * t)
        noisy, blk = row < t, row % t // length
        own, earlier = blk[:, None] == blk[None, :], \
            blk[None, :] < blk[:, None]
        return jnp.where(
            noisy[:, None], jnp.where(noisy[None, :], own, earlier),
            ~noisy[None, :] & (own | earlier))
    cm = jnp.tril(jnp.ones((tq, tk), bool), tk - tq)
    if window is not None:
        cm = cm & ~jnp.tril(jnp.ones((tq, tk), bool), tk - tq - window)
    return cm


def _repeat_kv(q, k, v):
    """k and v with each head repeated for its group of query heads."""
    group = q.shape[1] // k.shape[1]
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)


def _xla_attention(q, k, v, mask, scale, causal, window=None,
                   block_diffusion=None):
    prec = _dot_precision(q.dtype)
    k, v = _repeat_kv(q, k, v)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32,
                        precision=prec) * scale
    if mask is not None:
        logits = logits + mask.astype(jnp.float32)
    if causal or block_diffusion is not None:
        tq, tk = logits.shape[-2], logits.shape[-1]
        logits = jnp.where(visible_mask(tq, tk, window, block_diffusion),
                           logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v,
                      precision=prec)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, mask, scale, causal, blocks, interpret, window,
           block_diffusion=None):
    """`blocks`: the (block_q, block_k) of each kernel the call runs, in
    KERNELS' order, or in FUSED_KERNELS' where the backward is fused."""
    out, _ = _pallas_forward(q, k, v, mask, scale, causal, *blocks[0],
                             interpret, window, block_diffusion)
    return out


def _flash_fwd(q, k, v, mask, scale, causal, blocks, interpret, window,
               block_diffusion):
    out, stats = _pallas_forward(q, k, v, mask, scale, causal, *blocks[0],
                                 interpret, window, block_diffusion)
    return out, (q, k, v, mask, out, stats)


def _xla_dmask(q, k, v, mask, out, lse, g, scale, causal, window=None):
    """Mask cotangent via the straight softmax-backward formula. This DOES
    materialize (B,H,Tq,Tk) — but it is emitted as a standalone expression,
    so when the mask grad is unused (padding masks, the BERT/ERNIE case)
    XLA dead-code-eliminates it and only the Pallas kernels remain."""
    prec = _dot_precision(q.dtype)
    k, v = _repeat_kv(q, k, v)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32), precision=prec) * scale
    s = s + mask.astype(jnp.float32)
    p = jnp.exp(s - lse[..., None])
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        p = jnp.where(visible_mask(tq, tk, window), p, 0.0)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    dp = jnp.einsum("bhqd,bhkd->bhqk", g.astype(jnp.float32),
                    v.astype(jnp.float32), precision=prec)
    ds = p * (dp - delta[..., None])
    reduce_axes = tuple(ax for ax in range(4)
                        if mask.shape[ax] == 1 and ds.shape[ax] > 1)
    return jnp.sum(ds, axis=reduce_axes, keepdims=True).astype(mask.dtype)


def _flash_bwd(scale, causal, blocks, interpret, window, block_diffusion,
               res, g):
    q, k, v, mask, out, stats = res
    # Pallas backward: recompute p from the (m, l, delta) residuals with
    # the mask applied in-kernel — the (T,T) matrix never touches HBM for
    # dq/dk/dv in either direction
    dq, dk, dv = _pallas_backward(q, k, v, mask, out, stats, g, scale,
                                  causal, blocks, interpret, window,
                                  block_diffusion)
    if mask is None:
        return dq, dk, dv, None
    lse = (stats[:, 0] + jnp.log(stats[:, 1])).reshape(q.shape[:3])
    dmask = _xla_dmask(q, k, v, mask, out, lse, g, scale, causal, window)
    return dq, dk, dv, dmask


_flash.defvjp(_flash_fwd, _flash_bwd)


KERNELS = ("fwd", "bwd_dkv", "bwd_dq")
FUSED_KERNELS = ("fwd", "bwd")      # what a call with the fused backward runs


def _fit(block, t):
    """Largest block <= `block` that divides `t`, by halving."""
    block = min(block, t)
    while t % block:
        block //= 2
    return block


def pick_blocks(tq, tk, d, dtype, kernel, causal=False, window=None,
                dv=None, block_diffusion=None, group=1):
    """(block_q, block_k) of `kernel` (of KERNELS or FUSED_KERNELS) for a
    call's shape.

    What the sweep on a v5e showed (PERF.md, PR 25; bf16, D=64 and 128,
    T=512..4096): a grid step costs a few microseconds whatever its tile
    holds, most of it in proportion to block_q and none to block_k, so the
    widest tile wins up to 1024x1024 and nothing beyond it does. The
    forward takes that even where one tile is the whole causal square.
    The backward kernels' work grows with the tile's area (seven matmuls
    and two transposes), so there skipping blocks above the causal
    diagonal pays: a quarter of the sequence a side (10 of 16 tiles run),
    but never under 512, where the step's cost loses more than skipping
    saves. The fused backward ("bwd") takes the dK/dV kernel's tile: swept
    on a v5e at GPT's two shapes (PERF.md, PR 30; ms a call), 1024x1024
    4.71 at T=4096 (1024x512 4.78, 512x1024 4.80, 512x512 5.25) and
    512x512 2.24 at T=1024 (1024x1024 2.32, 512x1024 2.34), and at latent
    attention's (2, 16, 8192, 192 | 128) (PERF.md, PR 43): 1024x1024 15.08
    (1024x512 15.53, 512x1024 15.55, 512x512 15.91, 2048x1024 16.65: three
    times the matmul a tile does not move the order); the room its dQ row
    takes is `backward_rule`'s to weigh, not this tile's. With a `group` of
    query heads a kv head it also holds dK/dV's float32 rows, as long as
    the kv head's, and its tile is weighed with both rows: 1024x1024 at
    the cells' lengths (71 of 96 MiB at T = 16,384, D 128), halved beyond
    (512x1024 at 32,768). Swept at that shape too (PERF.md, PR 49):
    (1, 32:4, 16384, 128) under block diffusion 1024x1024 23.16 (1024x512
    26.42, 512x512 32.86), (2, 28:4, 16384, 128) causal 61.40 (66.05,
    80.12).
    Other dtypes run float32 operands at HIGHEST: twice the VMEM
    and six MXU passes a tile, so 512 is their cap (reckoned, not swept).
    Under a sliding `window` a tile row sees window + block_q keys whatever
    the sequence's length, so all three kernels take a tile about the
    window's size (the next power of two, at least 256): a wider one does
    masked work, a narrower one pays more steps (reckoned from PR 25's
    step costs, not swept). A tile reckoned over the VMEM ceiling (`dv`:
    the value width where it differs from `d`) is halved until it fits.
    Under `block_diffusion` = (L, T) the two lower-triangular quadrants of
    the 2T square are causal calls of T rows at a tile's scale (L is far
    under a tile), so the tile is the causal rule's at T, and it divides T:
    no tile straddles the two halves (reckoned; PERF.md, PR 48 has what
    the chip read)."""
    itemsize = jnp.dtype(dtype).itemsize
    side = 1024 if jnp.dtype(dtype) == jnp.bfloat16 else 512
    rows = _bwd_rows(tq, tk, group) if kernel == "bwd" and group > 1 else {}
    if block_diffusion is not None:
        tq = tk = int(block_diffusion[1])
        causal = True
    if window is not None:
        side = min(side, max(256, 1 << (int(window) - 1).bit_length()))
    elif causal and kernel != "fwd":
        side = min(side, max(512, min(tq, tk) // 4))
    bq, bk = _fit(side, tq), _fit(side, tk)
    while (vmem_bytes(kernel, bq, bk, d, itemsize, "qk", dv, **rows)
           > _VMEM_CEILING and max(bq, bk) > 128):
        if bq >= bk:
            bq = _fit(bq // 2, tq)
        else:
            bk = _fit(bk // 2, tk)
    return bq, bk


def _kernels_of(blocks):
    """The names of the kernels a call with these tiles runs."""
    return FUSED_KERNELS if len(blocks) == len(FUSED_KERNELS) else KERNELS


def plan(q_shape, k_shape, v_shape, causal, window, blocks, backward=None,
         block_diffusion=None):
    """What a call will do, for `flash.plan`: per kernel the tile, the grid
    steps and how many of the (q-block, k-block) tiles run, how many lie
    wholly above the causal diagonal and how many wholly outside the
    window; with the group size, the two widths, which visibility rule the
    call runs under (`mask`: "none", "causal", "window" or
    "block_diffusion") and which backward it got (`backward_rule`'s
    answer). Under the block-diffusion rule a kernel's row is its tile,
    `tiles_run` (the tiles some query of which sees some key: the only
    ones whose body runs and whose blocks are fetched) and `tiles_grid`
    (the whole square the grid walks). Python ints only."""
    (_b, hq, tq, d), hkv, tk, dv = q_shape, k_shape[1], k_shape[2], \
        v_shape[-1]
    off, out = tk - tq, {"group": hq // hkv, "d_qk": d, "d_v": dv,
                         "window": window, "causal": bool(causal),
                         "backward": backward,
                         "mask": mask_name(causal, window, block_diffusion)}
    if block_diffusion is not None:
        out["block_diffusion"] = list(block_diffusion)
    for kernel, (bq, bk) in zip(_kernels_of(blocks), blocks):
        nq, nk = tq // bq, tk // bk
        if block_diffusion is not None:
            out[kernel] = {
                "block_q": bq, "block_k": bk,
                "grid_inner": nq if kernel in ("bwd_dkv", "bwd") else nk,
                "tiles_run": sum(
                    bool(_bd_tile_visible(qi, kj, bq, bk, block_diffusion))
                    for qi in range(nq) for kj in range(nk)),
                "tiles_grid": nq * nk}
            continue
        above = outside = 0
        for qi in range(nq):
            last = (qi * bq + bq - 1 + off) // bk if causal else nk - 1
            first = 0 if window is None else _window_first_k_block(
                qi, off, bq, bk, window)
            above += nk - 1 - last
            outside += first
        kj_innermost = kernel not in ("bwd_dkv", "bwd")
        inner = nk if kj_innermost else nq
        if window is not None:
            inner = window_grid(tq, tk, bq, bk, window, kj_innermost)
        out[kernel] = {"block_q": bq, "block_k": bk,
                       "grid_inner": inner,
                       "tiles_visited": nq * nk - above - outside,
                       "tiles_skipped_causal": above,
                       "tiles_skipped_window": outside}
    return out


def mask_name(causal, window, block_diffusion=None):
    """The visibility rule a call runs under, by name."""
    if block_diffusion is not None:
        return "block_diffusion"
    return "window" if window is not None else \
        "causal" if causal else "none"


def _record_plan(q, k, v, causal, window, blocks, backward,
                 block_diffusion=None):
    """One `flash.plan` record a lowering, while obs is on."""
    from ...framework import obs
    if obs.enabled():
        now = obs.now()
        obs.record("flash.plan", now, now,
                   **plan(q.shape, k.shape, v.shape, causal, window, blocks,
                          backward, block_diffusion))


class AttentionPath(NamedTuple):
    """What `attention_path` returns: the path ("xla" or "flash"); under
    "flash" the (block_q, block_k) of each kernel the call runs (KERNELS'
    order, or FUSED_KERNELS' with the fused backward) and which backward
    it got, "fused" or "split: <the rule that kept the two kernels>";
    under "xla" the rule that sent the call there."""
    path: str
    blocks: Optional[tuple]
    why: Optional[str]
    backward: Optional[str] = None


def backward_rule(q_shape, k_shape, v_shape, dtype, causal, window,
                  block_diffusion=None):
    """Which backward a flash call gets, from its shapes: "fused" (one
    kernel, `_bwd_kernel`) or "split: <rule>" (dK/dV and dQ kernels), the
    first rule that holds:
      "window"  a sliding window, grouped heads or not: its grids walk a
                banded inner axis the fused kernel does not have;
      "vmem"    the fused kernel at its tile, with dQ's whole row (Tq x D
                in float32 and the output block, D padded to whole 128
                lanes), dV's accumulator at the value width and, for a
                group of query heads, dK's and dV's float32 rows as long
                as the kv head's (Tk x (D + Dv)), is reckoned over the
                VMEM ceiling: bfloat16 at Tq = 65,536 for D = 64 or 128,
                at 32,768 for D = 192 (a group's tile is halved first:
                `pick_blocks`).
    The value width alone decides nothing: Dv != D is fused like Dv == D
    (until PR 43 it was a rule of its own, "widths"). Nor does a group of
    query heads a key/value head (until PR 49 the rule "group": the fused
    kernel sums dK/dV over the group's heads itself), nor the
    block-diffusion rule: all three backward kernels take it."""
    tq, tk, d, dv = q_shape[2], k_shape[2], q_shape[-1], v_shape[-1]
    group = q_shape[1] // k_shape[1]
    if window is not None:
        return "split: window"
    bq, bk = pick_blocks(tq, tk, d, dtype, "bwd", causal, dv=dv,
                         block_diffusion=block_diffusion, group=group)
    if vmem_bytes("bwd", bq, bk, d, jnp.dtype(dtype).itemsize, "qk", dv,
                  **_bwd_rows(tq, tk, group)) > _VMEM_CEILING:
        return "split: vmem"
    return "fused"


def attention_path(q_shape, k_shape, v_shape, dtype, causal, window,
                   interpret, auto=False, block_q=None, block_k=None,
                   block_diffusion=None):
    """Which attention a call gets and with which tiles, from the call's
    own arguments (Python ints and strings; nothing is traced): the one
    place that decides it. `ops/attention_ops._sdpa` and `flash_attention`
    both ask here, and `flash.plan` records the tiles it gave. A mask does
    not enter: the kernels take a key mask or a (Tq, Tk) one as they are.

    The rules, in order; the first that holds sends the call to XLA:
      "short"    `auto` (the op's impl "auto") and Tq * Tk <= 256 * 256:
                 XLA's fused attention beats the tiled kernel there
                 (measured 1026 vs 912 samples/s on BERT-base seq128,
                 v5e); an explicit "flash" skips this rule.
      "no_keys"  causal with Tq > Tk: rows i < Tq - Tk see no key at all;
                 only the XLA reference defines that edge (uniform over
                 all-masked logits).
      "no_tile"  a tile side under 8, or D % 8 or Dv % 8 (D = 64, 128,
                 192 and 256 all pass: a block holds the whole width).
      "lanes"    compiled (`interpret` false) with a tile side under 128:
                 Mosaic wants the last two block dims 128-lane aligned
                 (the stats block puts block_q on the lane dim).
    Otherwise "flash", with the backward `backward_rule` names and each
    kernel with `pick_blocks`' tile; an explicit `block_q`/`block_k`
    replaces that side of every kernel's. Under `block_diffusion` = (L, T)
    a tile has to divide T (no tile may hold rows of both halves): an
    explicit side that does not is a ValueError."""
    tq, tk, d, dv = q_shape[2], k_shape[2], q_shape[-1], v_shape[-1]
    fit_q, fit_k = tq, tk
    if block_diffusion is not None:
        fit_q = fit_k = half = int(block_diffusion[1])
        for side in (block_q, block_k):
            if side and half % side:
                raise ValueError(
                    "attention: a tile of %d rows does not divide the %d "
                    "rows of a block-diffusion call's half (a tile holds "
                    "noisy rows or clean rows, never both)" % (side, half))
    if auto and tq * tk <= 256 * 256:
        return AttentionPath("xla", None, "short")
    if causal and tq > tk:
        return AttentionPath("xla", None, "no_keys")
    backward = backward_rule(q_shape, k_shape, v_shape, dtype, causal,
                             window, block_diffusion)
    blocks = []
    for kernel in (FUSED_KERNELS if backward == "fused" else KERNELS):
        bq, bk = pick_blocks(tq, tk, d, dtype, kernel, causal, window,
                             None if dv == d else dv, block_diffusion,
                             q_shape[1] // k_shape[1])
        blocks.append((_fit(block_q or bq, fit_q),
                       _fit(block_k or bk, fit_k)))
    least = min(min(pair) for pair in blocks)
    if least < 8 or d % 8 or dv % 8:
        return AttentionPath("xla", None, "no_tile")
    if not interpret and least < 128:
        return AttentionPath("xla", None, "lanes")
    return AttentionPath("flash", tuple(blocks), None, backward)


def flash_attention(q, k, v, mask=None, scale=1.0, causal=False,
                    block_q=None, block_k=None, interpret=None,
                    window=None, block_diffusion=None):
    """Flash attention entry. q: (B,Hq,Tq,D), k: (B,Hkv,Tk,D), v:
    (B,Hkv,Tk,Dv) (the module docstring states the supported space).
    Falls back to interpret mode off-TPU so tests exercise the same
    kernel, and to plain fused XLA attention where `attention_path` finds
    no tile for the shape.

    Each kernel's tile comes from the call's shape (`pick_blocks`), as
    does the backward it runs (`backward_rule`). An explicit
    `block_q`/`block_k` replaces that side of every kernel's tile.

    `block_diffusion=(L, T)` (Python ints; Tq = Tk = 2T, L divides T; no
    `causal`, `window` or `mask` beside it) is the block-diffusion
    training mask over a noisy copy (rows 0..T-1) and a clean copy (rows
    T..2T-1) of a T-token document cut into blocks of L:
    `_block_diffusion_keep` states it. It is computed in the tile from row
    indices, the three quarters of the square nobody sees run no body and
    fetch no block, and no (2T, 2T) array exists anywhere."""
    if interpret is None:
        interpret = default_interpret()
    if block_diffusion is not None:
        block_diffusion = (int(block_diffusion[0]), int(block_diffusion[1]))
    check_call(q.shape, k.shape, v.shape, causal, window, block_diffusion,
               mask)
    path, blocks, _why, backward = attention_path(
        q.shape, k.shape, v.shape, q.dtype, causal, window, interpret,
        block_q=block_q, block_k=block_k, block_diffusion=block_diffusion)
    if path == "xla":
        return _xla_attention(q, k, v, mask, scale, causal, window,
                              block_diffusion)
    _record_plan(q, k, v, causal, window, blocks, backward, block_diffusion)
    return _flash(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                  None if mask is None else jnp.asarray(mask),
                  scale, causal, blocks, interpret, window, block_diffusion)


def check_call(q_shape, k_shape, v_shape, causal, window,
               block_diffusion=None, mask=None):
    """A clear error for a call outside the supported space, before any
    kernel is built (Mosaic's own would name a block shape)."""
    if block_diffusion is not None:
        length, half = block_diffusion
        if causal or window is not None or mask is not None:
            raise ValueError(
                "attention: block_diffusion is a visibility rule of its "
                "own: no causal, window or additive mask beside it")
        if length < 1 or half < 1 or half % length \
                or q_shape[2] != 2 * half or k_shape[2] != 2 * half:
            raise ValueError(
                "attention: block_diffusion=(%r, %r) wants blocks of L that "
                "divide T and 2T = %d query and key rows (a noisy and a "
                "clean copy), got %d and %d" % (length, half, 2 * half,
                                               q_shape[2], k_shape[2]))
    hq, hkv = q_shape[1], k_shape[1]
    if hkv < 1 or hq % hkv:
        raise ValueError(
            "attention: %d query heads are not a whole multiple of %d "
            "key/value heads" % (hq, hkv))
    if tuple(k_shape[:3]) != tuple(v_shape[:3]) \
            or q_shape[-1] != k_shape[-1]:
        raise ValueError(
            "attention: k %r and v %r must share batch, heads and length, "
            "q %r and k the head width" % (tuple(k_shape), tuple(v_shape),
                                          tuple(q_shape)))
    if window is not None:
        if not causal:
            raise ValueError("attention: a sliding window needs causal=True")
        if int(window) <= 0:
            raise ValueError("attention: window must be positive, got %r"
                             % (window,))
