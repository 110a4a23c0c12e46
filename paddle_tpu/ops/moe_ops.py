"""Sparse-expert op kernels: a dropless expert layer that is told which
experts it holds. Four ops, so that the device trace splits the layer by
its own names:

  moe_route     scores over ALL experts, top-k, the picks' weights (float32),
                and the picks each expert received (the step's load)
  moe_dispatch  the (token, pick) pairs that landed on a held expert, laid
                out expert by expert in a row buffer; the rows gathered
  moe_experts   the gated MLP of every held expert over its rows: two
                grouped matmuls (ops/pallas/grouped_matmul.py)
  moe_combine   each token's weighted sum of its picks' rows
and `moe_bias_update`, the loss-free balance step on the expert bias.

Where the weights are applied: in `moe_combine`, in float32, to each pick's
row as it is added to its token's sum; backward, to each buffer row's
gradient (`w_row`), and the weight's own gradient is a row-wise dot in row
space (y . dOut's row) read back over `Pos`. Every crossing between token
order and expert order is a plain row gather in the rows' own dtype: X ->
buffer one take, buffer -> tokens k takes (one a pick) accumulated in
float32. Nothing of shape (tokens, top_k, d) is written out.

The layer holds experts `first .. first + count - 1` of `num_experts` (the
expert-parallel rank's share). A pick that lands on an absent expert adds
nothing: the result is the part the held experts give. Nothing stands in for
the other ranks or their exchange.

Dropless: the buffer is sized for the worst case (every pick on a held
expert: tokens x top_k rows, and a tile's padding a group), so no imbalance
drops a row; the grouped matmuls visit only the tiles in use. Rows past the
tiles in use are never written and hold anything: every read of the buffer
that leaves this file's ops goes through a `where` on the pick's own mask.

Gather both ways: `moe_dispatch` also returns the inverse map (`RowPair`:
which pair sits in each row), so the backward of a gather over `Pos` is a
gather over `RowPair`, and the other way round: `_rows_of_tokens` and
`sum_of_picks` are each other's transpose, `moe_dispatch` runs the first
forward and the second backward, `moe_combine` the second forward and the
first backward. No scatter is ever lowered (`moe_route` reads the picks'
scores through the one-hot of the picks for the same reason).

Reference parity: none (the reference predates sparse experts). The
equations are the published `lfm2_moe` block's: sigmoid scores, an expert
bias added for the choice only, weights renormalised over the picks.
"""
import jax
import jax.numpy as jnp
from jax import lax

from .pallas import grouped_matmul as gmm
from .registry import register_op, register_shape_rule
from .shape_rules import ShapeError, TensorMeta, _x

_HIGHEST = lax.Precision.HIGHEST


def _held(attrs):
    first, count = attrs["experts_held"]
    return int(first), int(count)


@register_op("moe_route", nondiff=("Bias",))
def _moe_route(ctx, ins, attrs):
    """s = sigmoid(X W) in float32; the picks are the top-k of s + Bias;
    their weights are s at the picks, over their sum + 1e-6 where
    `norm_topk_prob`, times `routed_scaling_factor`. Load[e] is the count
    of picks that fell on expert e, over all experts."""
    x, w = ins["X"][0], ins["W"][0]
    scores = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                                    w.astype(jnp.float32),
                                    precision=_HIGHEST))
    choose = scores + ins["Bias"][0].astype(jnp.float32)
    _top, picks = lax.top_k(lax.stop_gradient(choose), int(attrs["top_k"]))
    # the picks' scores through the picks' one-hot: exact (one term a sum
    # is not 0), and its backward is a select, not a scatter
    picked = picks[..., None] == jnp.arange(w.shape[1])
    weights = jnp.sum(jnp.where(picked, scores[:, None, :], 0.0), axis=-1)
    if attrs.get("norm_topk_prob", True):
        weights = weights / (jnp.sum(weights, axis=1, keepdims=True) + 1e-6)
    weights = weights * float(attrs.get("routed_scaling_factor", 1.0))
    load = jnp.sum(picked, axis=(0, 1), dtype=jnp.int32)
    return {"TopW": weights, "TopE": picks.astype(jnp.int32), "Load": load}


@register_shape_rule("moe_route")
def _moe_route_rule(op, ins, attrs):
    x, w = _x(ins), _x(ins, "W")
    if x.shape is not None and w.shape is not None:
        if len(x.shape) != 2 or len(w.shape) != 2 \
                or (x.shape[1] not in (None, -1)
                    and w.shape[0] not in (None, -1)
                    and x.shape[1] != w.shape[0]):
            raise ShapeError("moe_route wants X (tokens, d) and W (d, "
                             "experts); got %s and %s" % (x.shape, w.shape))
    rows = x.shape[0] if x.shape is not None else None
    k = int(attrs["top_k"])
    experts = w.shape[1] if w.shape is not None and len(w.shape) == 2 \
        else None
    return {"TopW": [TensorMeta((rows, k), "float32")],
            "TopE": [TensorMeta((rows, k), "int32")],
            "Load": [TensorMeta((experts,), "int32")]}


@register_op("moe_bias_update", differentiable=False)
def _moe_bias_update(ctx, ins, attrs):
    """The loss-free balance step: every expert that received fewer picks
    than the mean expert has its bias raised by `rate`, every one that
    received more has it lowered (Bias + rate * sign(mean(Load) - Load))."""
    bias, load = ins["Bias"][0], ins["Load"][0].astype(jnp.float32)
    step = float(attrs["rate"]) * jnp.sign(jnp.mean(load) - load)
    return {"Out": bias + step.astype(bias.dtype)}


@register_shape_rule("moe_bias_update")
def _moe_bias_update_rule(op, ins, attrs):
    bias = _x(ins, "Bias")
    return {"Out": [TensorMeta(bias.shape, bias.dtype)]}


def dispatch_plan(picks, first, count):
    """Where every pair goes. picks [tokens, k] int32 over all experts.
    Returns (pos [tokens, k]: the pair's buffer row, or `rows` (past the
    end) where its expert is absent; row_pair [rows]: the pair in each row,
    -1 for padding; group_sizes [count]; tile_group [rows / tm]).

    What the sort leaves in sorted-pair order reaches row order as `count`
    shifted copies (group g's run lands on group g's tiles whole), and
    `pos` by compares against the groups: on the chip a gather of 70k
    scalars costs 0.5-0.7 ms, as much as one of 16k whole rows (PERF.md,
    PR 32), so the plan has none over the rows or the pairs."""
    tokens, k = picks.shape
    pairs = tokens * k
    tm = gmm.row_tile(pairs)
    rows = gmm.buffer_rows(pairs, count, tm)
    local = picks.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    sizes = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                    dtype=jnp.int32)
    lay = gmm.layout(sizes, rows, tm)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    rank_of = jnp.argsort(order).astype(jnp.int32)   # pair -> sorted index
    shift = lay["starts"] - (jnp.cumsum(sizes) - sizes)
    pos = rows
    for g in range(count):
        pos = jnp.where(key == g, rank_of + shift[g], pos)
    row = jnp.arange(rows, dtype=jnp.int32)
    pad = jnp.zeros((rows,), jnp.int32)
    padded = jnp.concatenate([pad, order, pad])
    row_pair = jnp.full((rows,), -1, jnp.int32)
    for g in range(count):      # [r] = order[r - shift[g]] on group g's rows
        start = lay["starts"][g]
        run = lax.dynamic_slice(padded, (rows - shift[g],), (rows,))
        row_pair = jnp.where((row >= start) & (row < start + sizes[g]), run,
                             row_pair)
    return pos.reshape(tokens, k), row_pair, sizes, lay["tile_group"]


def _rows_of_tokens(x, row_pair, k):
    """rows[r] = x[token of the pair in row r] (padding rows: token 0)."""
    return jnp.take(x, jnp.maximum(row_pair, 0) // k, axis=0, mode="clip")


def sum_of_picks(buf, pos, weights=None):
    """out[t] = sum_j weights[t, j] buf[pos[t, j]] over the picks whose
    expert is held (`weights` None: the plain sum): one plain row gather a
    pick, accumulated in float32, cast once. A pick on an absent expert
    points past the buffer and reads its last row (`clip`), which the
    `where` keeps out of the sum. (The `where` in the rows' own dtype,
    before the cast: XLA then folds the casts into the sum; cast first, it
    wrote each gather out again in float32, PR 32.)"""
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
def _gather_rows(x, pos, row_pair):
    return _rows_of_tokens(x, row_pair, pos.shape[1])


def _gather_rows_fwd(x, pos, row_pair):
    return _gather_rows(x, pos, row_pair), pos


def _gather_rows_bwd(pos, d_rows):
    return sum_of_picks(d_rows, pos), None, None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


@register_op("moe_dispatch")
def _moe_dispatch(ctx, ins, attrs):
    x, picks = ins["X"][0], ins["TopE"][0]
    first, count = _held(attrs)
    pos, row_pair, sizes, tile_group = dispatch_plan(picks, first, count)
    return {"Rows": _gather_rows(x, pos, row_pair), "Pos": pos,
            "RowPair": row_pair, "GroupSizes": sizes,
            "TileGroup": tile_group}


@register_shape_rule("moe_dispatch")
def _moe_dispatch_rule(op, ins, attrs):
    x, picks = _x(ins), _x(ins, "TopE")
    _first, count = _held(attrs)
    rows = tiles = None
    if picks.shape is not None and None not in picks.shape \
            and -1 not in picks.shape:
        pairs = picks.shape[0] * picks.shape[1]
        tm = gmm.row_tile(pairs)
        rows = gmm.buffer_rows(pairs, count, tm)
        tiles = rows // tm
    width = x.shape[1] if x.shape is not None and len(x.shape) == 2 else None
    return {"Rows": [TensorMeta((rows, width), x.dtype)],
            "Pos": [TensorMeta(picks.shape, "int32")],
            "RowPair": [TensorMeta((rows,), "int32")],
            "GroupSizes": [TensorMeta((count,), "int32")],
            "TileGroup": [TensorMeta((tiles,), "int32")]}


@register_op("moe_experts")
def _moe_experts(ctx, ins, attrs):
    """Out[r] = W2_g (silu(a) * b), [a, b] = Rows[r] W13_g, g the group of
    row r. W13 [G, d, 2F] is gate and up side by side, W2 [G, F, d]."""
    rows, w13, w2 = ins["Rows"][0], ins["W13"][0], ins["W2"][0]
    sizes, tile_group = ins["GroupSizes"][0], ins["TileGroup"][0]
    tm = rows.shape[0] // tile_group.shape[0]
    both = gmm.grouped_matmul(rows, w13, sizes, tm)
    gate, up = jnp.split(both, 2, axis=1)
    act = (jax.nn.silu(gate.astype(jnp.float32))
           * up.astype(jnp.float32)).astype(rows.dtype)
    return {"Out": gmm.grouped_matmul(act, w2, sizes, tm)}


@register_shape_rule("moe_experts")
def _moe_experts_rule(op, ins, attrs):
    rows, w13, w2 = _x(ins, "Rows"), _x(ins, "W13"), _x(ins, "W2")
    if w13.shape is not None and w2.shape is not None \
            and None not in w13.shape and None not in w2.shape:
        if len(w13.shape) != 3 or len(w2.shape) != 3 \
                or w13.shape[0] != w2.shape[0] \
                or w13.shape[2] != 2 * w2.shape[1] \
                or w13.shape[1] != w2.shape[2]:
            raise ShapeError("moe_experts wants W13 (G, d, 2F) and W2 (G, "
                             "F, d); got %s and %s" % (w13.shape, w2.shape))
    return {"Out": [TensorMeta(rows.shape, rows.dtype)]}


@jax.custom_vjp
def _combine(y, weights, pos, row_pair):
    return sum_of_picks(y, pos, weights)


def _combine_fwd(y, weights, pos, row_pair):
    return _combine(y, weights, pos, row_pair), (y, weights, pos, row_pair)


def _combine_bwd(res, d_out):
    """dy = w_row times dOut's row of each buffer row's token (one plain
    gather); the weight's gradient a pick at a time, y's row dotted with
    dOut's, under the held mask."""
    y, weights, pos, row_pair = res
    rows, k = y.shape[0], pos.shape[1]
    w_row = jnp.where(row_pair >= 0, jnp.take(
        weights.reshape(-1), jnp.maximum(row_pair, 0), mode="clip"), 0.0)
    dy = (_rows_of_tokens(d_out, row_pair, k).astype(jnp.float32)
          * w_row[:, None]).astype(y.dtype)
    dw = []
    for j in range(k):
        got = jnp.take(y, pos[:, j], axis=0, mode="clip")
        got = jnp.where((pos[:, j] < rows)[:, None], got, 0)
        dw.append(jnp.sum(got.astype(jnp.float32)
                          * d_out.astype(jnp.float32), axis=-1))
    return dy, jnp.stack(dw, axis=1), None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


@register_op("moe_combine")
def _moe_combine(ctx, ins, attrs):
    """Out[t] = sum_j TopW[t, j] Y[Pos[t, j]] over the picks whose expert
    is held, in float32."""
    return {"Out": _combine(ins["Y"][0], ins["TopW"][0], ins["Pos"][0],
                            ins["RowPair"][0])}


@register_shape_rule("moe_combine")
def _moe_combine_rule(op, ins, attrs):
    y, pos = _x(ins, "Y"), _x(ins, "Pos")
    tokens = pos.shape[0] if pos.shape is not None else None
    width = y.shape[1] if y.shape is not None and len(y.shape) == 2 else None
    return {"Out": [TensorMeta((tokens, width), y.dtype)]}
