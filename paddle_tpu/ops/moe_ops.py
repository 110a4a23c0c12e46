"""Sparse-expert op kernels: a dropless expert layer that is told which
experts it holds. Four ops, so that the device trace splits the layer by
its own names:

  moe_route     scores over ALL experts, top-k, the picks' weights (float32),
                and the picks each expert received (the step's load); X is
                whatever the layer says its router reads, not always the
                experts' input
  moe_dispatch  the (token, pick) pairs that landed on a held expert, laid
                out expert by expert in a row buffer; the rows gathered
  moe_experts   the gated MLP of every held expert over its rows: two
                grouped matmuls (ops/pallas/grouped_matmul.py)
  moe_combine   each token's weighted sum of its picks' rows
and `moe_bias_update`, the loss-free balance step on the expert bias.

Where the weights are applied: in `moe_combine`, in float32, to each pick's
row as it is added to its token's sum; backward, to each buffer row's
gradient (`w_row`), and the weight's own gradient is a row-wise dot in row
space (y . dOut's row), which each pick reads at its own row. Every crossing
between token order and expert order is a plain row gather in the rows' own
dtype. Nothing of shape (tokens, top_k, d) is written out.

The layer holds experts `first .. first + count - 1` of `num_experts` (the
expert-parallel rank's share). A pick that lands on an absent expert adds
nothing: the result is the part the held experts give. Nothing stands in for
the other ranks or their exchange, unless the layer says `absent="folded"`
(`moe_route`'s `fold_onto`): every pick is then answered, a pick on an absent
expert by the held expert congruent to it, so the layer lays out tokens x
top_k rows whatever the router does: the rows a rank's experts see when all
ranks bring a batch like this one.

Dropless: the buffer is SHAPED for the worst case (every pick on a held
expert: tokens x top_k rows, and a tile's padding a group), so no imbalance
drops a row. What a step pays for is the rows the plan laid out (the rows
in use: every group's whole tiles, a prefix of the buffer because groups
lie from row 0 tile by tile; `rows_laid_out`), a traced number: the grouped
matmuls' grid is as long as the tiles in use, and the row passes around
them (but the first, below) are `while`s over chunks of `_CHUNK_TILES`
tiles whose trip count follows it (`_by_chunks`), into a buffer nobody
initialised (`_anything`) or over the rows they have just read.
Rows past the rows in use are never written and hold anything, and so do a
tile's rows past its group's end after a grouped matmul: every read that
leaves this file's ops goes through a `where` on the pick's own mask.

What each pass touches:
  X -> buffer (`_rows_of_tokens`: `moe_dispatch`, its replay)    the buffer, X
                                                                 held in VMEM
  act(a) * b and its pullback (`_gated`, inside `moe_experts`)   the rows in use
  dOut -> dY with `w_row`, and y . dOut a row (`_combine_bwd`)   the rows in use
  buffer -> tokens (`_picked_sum`: `moe_combine`, its replay,    the held pairs
    and `moe_dispatch`'s backward)                               + one take of
                                                                 `tokens` rows
The last is a walk over the held pairs in pair order, where a token's picks
are neighbours (`sum_of_held_picks`; the plan's `held_pair`): gather their
rows, add to each place its k - 1 successors of the same token, read each
token's sum at its first place. Its own arrays are sized for half the
buffer: where the plan laid out more (`takes_bounded_form`, an observed
count, one `lax.cond`), the sum is one take a pick of every token
(`sum_of_picks`), which costs by tokens x top_k whatever is held; a step
with every pick on a held expert takes that, the spelling it always had,
and its chunked passes run over every chunk of the buffer.

Gather both ways: `moe_dispatch` also returns the inverse maps (`RowPair`:
which pair sits in each row; `HeldPair`: the held pairs in pair order), so
the backward of a gather over `Pos` is a gather over `RowPair`, and the
other way round: `_rows_of_tokens` and the picks' sum are each other's
transpose, `moe_dispatch` runs the first forward and the second backward,
`moe_combine` the second forward and the first backward. No scatter is ever
lowered: a chunk lands by `dynamic_update_slice` (`moe_route` reads the
picks' scores through the one-hot of the picks for the same reason).

Reference parity: none (the reference predates sparse experts). The
equations are the published `lfm2_moe` block's by default: sigmoid scores,
an expert bias added for the choice only, weights renormalised over the
picks, SiLU gates. `moe_route`'s `scoring` "softmax" and `moe_experts`'
`gate` "relu" are SmallThinker's: the top-k of the logits, a softmax over
the picks alone, no bias, ReLU gates. `gate` "relu2" is `nemotron_h`'s: the
experts are not gated, W2(relu(W1 x)^2) over one (G, d, F) leaf.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .pallas import grouped_matmul as gmm
from .pallas.interpret import default_interpret
from .registry import register_op, register_shape_rule
from .shape_rules import ShapeError, TensorMeta, _x

_HIGHEST = lax.Precision.HIGHEST


def _held(attrs):
    first, count = attrs["experts_held"]
    return int(first), int(count)


SCORINGS = ("sigmoid", "softmax")
#: act(a) * b over a (G, d, 2F) leaf, gate and up side by side
GATES = {"silu": jax.nn.silu, "relu": jax.nn.relu}
#: act(a) alone over a (G, d, F) leaf: the non-gated experts
PLAIN = {"relu2": lambda a: jnp.square(jax.nn.relu(a))}


@register_op("moe_route", nondiff=("Bias",))
def _moe_route(ctx, ins, attrs):
    """`scoring` "sigmoid" (the default): s = sigmoid(X W) in float32; the
    picks are the top-k of s + Bias; their weights are s at the picks.
    "softmax": the picks are the top-k of the logits X W themselves (no
    Bias), their weights the softmax over the picks' own logits. Either
    way the weights are then over their sum + 1e-6 where `norm_topk_prob`
    (after the softmax the sum is 1), times `routed_scaling_factor`.
    Load[e] is the count of picks that fell on expert e, over all
    experts. `fold_onto` = (first, count), where given, hands each pick
    AFTER its weight is taken to the expert of `first .. first + count - 1`
    that is congruent to it modulo `count` (TopE and Load are then over
    those experts alone): a rank that answers every pick with the experts
    it holds."""
    x, w = ins["X"][0], ins["W"][0]
    scoring = attrs.get("scoring", "sigmoid")
    if scoring not in SCORINGS:
        raise ValueError("moe_route: scoring %r is none of %r"
                         % (scoring, SCORINGS))
    scores = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                     precision=_HIGHEST)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(scores)
        choose = scores + ins["Bias"][0].astype(jnp.float32)
    else:
        choose = scores
    _top, picks = lax.top_k(lax.stop_gradient(choose), int(attrs["top_k"]))
    # the picks' scores through the picks' one-hot: exact (one term a sum
    # is not 0), and its backward is a select, not a scatter
    picked = picks[..., None] == jnp.arange(w.shape[1])
    weights = jnp.sum(jnp.where(picked, scores[:, None, :], 0.0), axis=-1)
    if scoring == "softmax":
        weights = jax.nn.softmax(weights, axis=-1)
    if attrs.get("norm_topk_prob", True):
        weights = weights / (jnp.sum(weights, axis=1, keepdims=True) + 1e-6)
    weights = weights * float(attrs.get("routed_scaling_factor", 1.0))
    if attrs.get("fold_onto") is not None:
        first, count = (int(n) for n in attrs["fold_onto"])
        picks = first + (picks - first) % count
        picked = picks[..., None] == jnp.arange(w.shape[1])
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
    -1 for padding; held_pair [pairs]: the held pairs in pair order (a
    token's picks are neighbours), `pairs` past them; group_sizes [count];
    tile_group [rows / tm]).

    `pos` is counted: a pair's row is its group's start plus the pairs of
    its group before it (a running count a group). The two inverse maps are
    sorted: `row_pair` from the pairs in group order, which reach row order
    as `count` shifted copies (group g's run lands on group g's tiles
    whole), `held_pair` from the pairs with the held ones first. On the chip
    a gather of 70k scalars costs 0.5-0.7 ms, as much as one of 16k whole
    rows (PERF.md, PR 32), so the plan has none over the rows or the pairs;
    and its two sorts are one shape (a new shape of sort costs the step's
    compile 12 s, PERF.md, PR 37)."""
    tokens, k = picks.shape
    pairs = tokens * k
    tm = gmm.row_tile(pairs)
    rows = gmm.buffer_rows(pairs, count, tm)
    local = picks.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    mine = key[None, :] == jnp.arange(count)[:, None]
    upto = jnp.cumsum(mine, axis=1, dtype=jnp.int32)
    sizes = upto[:, -1]
    lay = gmm.layout(sizes, rows, tm)
    pos = jnp.sum(jnp.where(mine, lay["starts"][:, None] + upto - 1, 0),
                  axis=0)
    pos = jnp.where(key < count, pos, rows)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    shift = lay["starts"] - (jnp.cumsum(sizes) - sizes)
    row = jnp.arange(rows, dtype=jnp.int32)
    pad = jnp.zeros((rows,), jnp.int32)
    padded = jnp.concatenate([pad, order, pad])
    row_pair = jnp.full((rows,), -1, jnp.int32)
    for g in range(count):      # [r] = order[r - shift[g]] on group g's rows
        start = lay["starts"][g]
        run = lax.dynamic_slice(padded, (rows - shift[g],), (rows,))
        row_pair = jnp.where((row >= start) & (row < start + sizes[g]), run,
                             row_pair)
    held_first = jnp.argsort((key >= count).astype(jnp.int32),
                             stable=True).astype(jnp.int32)
    held_pair = jnp.where(jnp.arange(pairs) < jnp.sum(sizes), held_first,
                          pairs)
    return pos.reshape(tokens, k), row_pair, held_pair, sizes, \
        lay["tile_group"]


#: tiles a pass of the bounded forms moves at a time
_CHUNK_TILES = 4


def rows_laid_out(group_sizes, tm):
    """Rows of the buffer the plan lays out for these group sizes: every
    group's whole tiles, an empty group's one (`gmm.layout`'s "tiles" times
    tm). They are a prefix of the buffer: the rows in use. numpy or jax."""
    tiles = -(-group_sizes // tm)
    return (tiles + (tiles == 0)).sum() * tm


def takes_bounded_form(in_use, rows):
    """Whether a step's buffer -> token passes walk the held pairs
    (`in_use` rows laid out of a buffer of `rows`) or take every pick of
    every token as they always did: the walk's own arrays are sized for
    half the buffer, and near that share it costs what the takes cost
    (PERF.md, PR 37: the tool's table). The one rule, for the ops' `cond`
    and for the `moe.load` record's `bounded` label alike."""
    return 2 * in_use <= rows


def _chunk_rows(rows, tm):
    """Rows a bounded pass moves at a time: up to `_CHUNK_TILES` tiles, a
    whole number of them in the buffer (a pass may write over what it
    reads: no chunk overlaps another)."""
    tiles = rows // tm
    return tm * max(n for n in range(1, _CHUNK_TILES + 1) if tiles % n == 0)


def _anything(shape, dtype, after):
    """A buffer nobody has written, allocated once `after` exists: on the
    TPU the result of a kernel that writes nothing, elsewhere zeros. What
    a bounded pass starts from. (`lax.empty` would do but for its having no
    operand: the scheduler is free to allocate every layer's buffers at the
    step's start, and the Kimi step then ran out of the chip's memory at
    compile time, PERF.md, PR 37.)"""
    if default_interpret():
        return jnp.zeros(shape, dtype)
    return pl.pallas_call(
        lambda _after, _out: None,
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        name="moe_rows_empty")(after)


def _by_chunks(outs, upto, chunk, block):
    """`outs` with `block(start, outs)`'s arrays written at rows start ..
    start + chunk, for start = 0, chunk, ... below `upto` (a traced count):
    the loop's trip count follows the rows in use. Rows past the last block
    keep what `outs` held; a block may read its own rows of `outs` before
    they are written over. A `while` that autodiff never sees: every caller
    sits inside a `custom_vjp`."""
    def step(i, outs):
        start = i * chunk
        return tuple(
            lax.dynamic_update_slice_in_dim(out, new.astype(out.dtype),
                                            start, 0)
            for out, new in zip(outs, block(start, outs)))

    return lax.fori_loop(0, -(-upto // chunk), step, tuple(outs))


def _rows_of_tokens(x, row_pair, k):
    """rows[r] = x[token of the pair in row r] (padding rows and the rows
    past the rows in use: token 0), one take the length of the buffer. The
    one pass that stays whole: XLA holds the 64-72 MiB X in VMEM for it
    (1.0 ms at Kimi's shapes, 0.47 at LFM2's, where a chunked pass over 8k
    rows in use takes 0.54 and over LFM2's 20k 0.98), and in the step the
    chunked form kept one more buffer of `rows` alive (PERF.md, PR 37)."""
    return jnp.take(x, jnp.maximum(row_pair, 0) // k, axis=0, mode="clip")


def sum_of_picks(buf, pos, weights=None):
    """out[t] = sum_j weights[t, j] buf[pos[t, j]] over the picks whose
    expert is held (`weights` None: the plain sum), as a take of every pick
    of every token: one plain row gather a pick, accumulated in float32,
    cast once. What the layer falls back on where most of the buffer is in
    use. A pick on an absent expert points past the buffer and reads its
    last row (`clip`), which the `where` keeps out of the sum. (The `where`
    in the rows' own dtype, before the cast: XLA then folds the casts into
    the sum; cast first, it wrote each gather out again in float32, PR 32.)"""
    rows = buf.shape[0]
    total = 0.0
    for j in range(pos.shape[1]):
        got = jnp.take(buf, pos[:, j], axis=0, mode="clip")
        part = jnp.where((pos[:, j] < rows)[:, None], got,
                         0).astype(jnp.float32)
        total = total + (part if weights is None
                         else part * weights[:, j, None])
    return total.astype(buf.dtype)


def sum_of_held_picks(buf, pos, held_pair, landed, weights=None):
    """`sum_of_picks` as a walk over the `landed` pairs that are held (at
    most half the buffer's rows of them: `takes_bounded_form`), in pair
    order (`held_pair`), where a token's picks are neighbours: gather their
    rows into that order a chunk at a time, add to each place its k - 1
    successors where they are the same token's (ascending pick, float32, an
    absent pick's exact 0.0 left out: the same sums), and read each token's
    sum at its first place with ONE take of `tokens` rows."""
    rows, (tokens, k) = buf.shape[0], pos.shape
    pairs = tokens * k
    chunk = _chunk_rows(rows, gmm.row_tile(pairs))
    places = -(-(rows // 2) // chunk) * chunk
    reach = chunk + k - 1               # a block reads k - 1 past its end
    pair_at = jnp.concatenate([held_pair, jnp.full(
        (max(places + k - 1 - pairs, 0),), pairs, jnp.int32)])
    pos_flat = pos.reshape(-1)
    w_flat = None if weights is None else weights.reshape(-1)

    def block(start, _outs):
        pair = lax.dynamic_slice_in_dim(pair_at, start, reach)
        got = jnp.take(buf, jnp.take(pos_flat, pair, mode="clip"), axis=0,
                       mode="clip")
        w = None if w_flat is None else jnp.take(w_flat, pair, mode="clip")
        token = pair // k
        total = 0.0
        for i in range(k):      # the mask and the product as `sum_of_picks`
            same = token[i:i + chunk] == token[:chunk]
            part = jnp.where(same[:, None], got[i:i + chunk],
                             0).astype(jnp.float32)
            total = total + (part if w is None
                             else part * w[i:i + chunk, None])
        return (total,)

    sums = _by_chunks((_anything((places, buf.shape[1]), buf.dtype, buf),),
                      landed, chunk, block)[0]
    held = jnp.sum(pos < rows, axis=1, dtype=jnp.int32)
    first = jnp.cumsum(held) - held     # the token's first place
    return jnp.where((held > 0)[:, None],
                     jnp.take(sums, first, axis=0, mode="clip"), 0)


def _picked_sum(buf, pos, held_pair, sizes, weights=None):
    """The picks' sum a token: the walk over the held pairs on a step whose
    plan laid out at most half the buffer, else the takes: one `cond` on
    the observed count."""
    in_use = rows_laid_out(sizes, gmm.row_tile(pos.size))
    return lax.cond(
        takes_bounded_form(in_use, buf.shape[0]),
        lambda: sum_of_held_picks(buf, pos, held_pair, jnp.sum(sizes),
                                  weights),
        lambda: sum_of_picks(buf, pos, weights))


@jax.custom_vjp
def _gather_rows(x, pos, row_pair, held_pair, sizes):
    return _rows_of_tokens(x, row_pair, pos.shape[1])


def _gather_rows_fwd(x, pos, row_pair, held_pair, sizes):
    return (_gather_rows(x, pos, row_pair, held_pair, sizes),
            (pos, held_pair, sizes))


def _gather_rows_bwd(res, d_rows):
    pos, held_pair, sizes = res
    return _picked_sum(d_rows, pos, held_pair, sizes), None, None, None, None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


@register_op("moe_dispatch")
def _moe_dispatch(ctx, ins, attrs):
    x, picks = ins["X"][0], ins["TopE"][0]
    first, count = _held(attrs)
    pos, row_pair, held_pair, sizes, tile_group = dispatch_plan(
        picks, first, count)
    return {"Rows": _gather_rows(x, pos, row_pair, held_pair, sizes),
            "Pos": pos, "RowPair": row_pair, "HeldPair": held_pair,
            "GroupSizes": sizes, "TileGroup": tile_group}


@register_shape_rule("moe_dispatch")
def _moe_dispatch_rule(op, ins, attrs):
    x, picks = _x(ins), _x(ins, "TopE")
    _first, count = _held(attrs)
    rows = tiles = pairs = None
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
            "HeldPair": [TensorMeta((pairs,), "int32")],
            "GroupSizes": [TensorMeta((count,), "int32")],
            "TileGroup": [TensorMeta((tiles,), "int32")]}


def _gate(both, gate="silu"):
    """act(a) * b of rows [a, b] (`gate` one of GATES), or act(a) of rows a
    (one of PLAIN: no second half), in float32, in the rows' dtype."""
    if gate in PLAIN:
        return PLAIN[gate](both.astype(jnp.float32)).astype(both.dtype)
    a, b = jnp.split(both, 2, axis=1)
    return (GATES[gate](a.astype(jnp.float32))
            * b.astype(jnp.float32)).astype(both.dtype)


def _act_width(width, gate):
    """Columns `_gate` gives for rows `width` wide."""
    return width if gate in PLAIN else width // 2


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _gated(both, sizes, tm, gate="silu"):
    """`_gate` over the rows in use, a chunk of tiles at a time. (No
    fallback to the one pass: a step with the whole buffer in use pays 60%
    more a row here, 0.7 ms a call at LFM2's shapes, and a `cond` around
    each of the layer's three such passes cost the step's compile 10 s,
    PERF.md, PR 37.)"""
    rows, width = both.shape
    chunk = _chunk_rows(rows, tm)

    def block(start, _outs):
        return (_gate(lax.dynamic_slice_in_dim(both, start, chunk), gate),)

    return _by_chunks((_anything((rows, _act_width(width, gate)),
                                 both.dtype, both),),
                      rows_laid_out(sizes, tm), chunk, block)[0]


def _gated_fwd(both, sizes, tm, gate):
    return _gated(both, sizes, tm, gate), (both, sizes)


def _gated_bwd(tm, gate, res, d_act):
    """jax's own pullback of `_gate`, over the same rows; chunk by chunk it
    lands where the chunk of `both` it was computed from lay (nothing reads
    `both` after it: no second buffer of its size)."""
    both, sizes = res
    chunk = _chunk_rows(both.shape[0], tm)

    def block(start, outs):
        _act, pullback = jax.vjp(
            functools.partial(_gate, gate=gate),
            lax.dynamic_slice_in_dim(outs[0], start, chunk))
        return pullback(lax.dynamic_slice_in_dim(d_act, start, chunk))

    return _by_chunks((both,), rows_laid_out(sizes, tm), chunk,
                      block)[0], None


_gated.defvjp(_gated_fwd, _gated_bwd)


@register_op("moe_experts")
def _moe_experts(ctx, ins, attrs):
    """Out[r] = W2_g (act(a) * b), [a, b] = Rows[r] W13_g, g the group of
    row r, for the rows the plan laid out; act is `gate`, "silu" (the
    default) or "relu". W13 [G, d, 2F] is gate and up side by side, W2
    [G, F, d]. Under a `gate` of PLAIN ("relu2") the experts are not
    gated: Out[r] = W2_g act(Rows[r] W13_g) with W13 [G, d, F]."""
    rows, w13, w2 = ins["Rows"][0], ins["W13"][0], ins["W2"][0]
    sizes, tile_group = ins["GroupSizes"][0], ins["TileGroup"][0]
    gate = attrs.get("gate", "silu")
    if gate not in GATES and gate not in PLAIN:
        raise ValueError("moe_experts: gate %r is none of %r"
                         % (gate, sorted(GATES) + sorted(PLAIN)))
    tm = rows.shape[0] // tile_group.shape[0]
    both = gmm.grouped_matmul(rows, w13, sizes, tm)
    act = _gated(both, sizes, tm, gate)
    return {"Out": gmm.grouped_matmul(act, w2, sizes, tm)}


@register_shape_rule("moe_experts")
def _moe_experts_rule(op, ins, attrs):
    rows, w13, w2 = _x(ins, "Rows"), _x(ins, "W13"), _x(ins, "W2")
    if w13.shape is not None and w2.shape is not None \
            and None not in w13.shape and None not in w2.shape:
        halves = 1 if attrs.get("gate", "silu") in PLAIN else 2
        if len(w13.shape) != 3 or len(w2.shape) != 3 \
                or w13.shape[0] != w2.shape[0] \
                or w13.shape[2] != halves * w2.shape[1] \
                or w13.shape[1] != w2.shape[2]:
            raise ShapeError("moe_experts wants W13 (G, d, %sF) and W2 (G, "
                             "F, d); got %s and %s"
                             % ("2" if halves == 2 else "", w13.shape,
                                w2.shape))
    return {"Out": [TensorMeta(rows.shape, rows.dtype)]}


@jax.custom_vjp
def _combine(y, weights, pos, row_pair, held_pair, sizes):
    return _picked_sum(y, pos, held_pair, sizes, weights)


def _combine_fwd(y, weights, pos, row_pair, held_pair, sizes):
    return (_combine(y, weights, pos, row_pair, held_pair, sizes),
            (y, weights, pos, row_pair, sizes))


def _combine_bwd(res, d_out):
    """One pass over the rows in use, a chunk of tiles at a time: dOut's
    row of each buffer row's token (a plain gather), times the pair's
    weight for dy, dotted with y's row for the weight's gradient, which
    each pick then reads at its own row (scalars) under the held mask."""
    y, weights, pos, row_pair, sizes = res
    rows, k = y.shape[0], pos.shape[1]
    tm = gmm.row_tile(pos.size)
    chunk = _chunk_rows(rows, tm)
    w_flat = weights.reshape(-1)

    def block(start, outs):
        pair = lax.dynamic_slice_in_dim(row_pair, start, chunk)
        at = jnp.maximum(pair, 0)
        got = jnp.take(d_out, at // k, axis=0,
                       mode="clip").astype(jnp.float32)
        w_row = jnp.where(pair >= 0, jnp.take(w_flat, at, mode="clip"), 0.0)
        mine = lax.dynamic_slice_in_dim(outs[0], start,
                                        chunk).astype(jnp.float32)
        return got * w_row[:, None], jnp.sum(mine * got, axis=-1)

    # dy lands chunk by chunk where y lay (nothing reads y after its dot)
    dy, dot = _by_chunks((y, _anything((rows,), jnp.float32, d_out)),
                         rows_laid_out(sizes, tm), chunk, block)
    dw = jnp.where(pos < rows, jnp.take(dot, pos.reshape(-1),
                                        mode="clip").reshape(pos.shape), 0.0)
    return dy, dw, None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


@register_op("moe_combine")
def _moe_combine(ctx, ins, attrs):
    """Out[t] = sum_j TopW[t, j] Y[Pos[t, j]] over the picks whose expert
    is held, in float32."""
    return {"Out": _combine(ins["Y"][0], ins["TopW"][0], ins["Pos"][0],
                            ins["RowPair"][0], ins["HeldPair"][0],
                            ins["GroupSizes"][0])}


@register_shape_rule("moe_combine")
def _moe_combine_rule(op, ins, attrs):
    y, pos = _x(ins, "Y"), _x(ins, "Pos")
    tokens = pos.shape[0] if pos.shape is not None else None
    width = y.shape[1] if y.shape is not None and len(y.shape) == 2 else None
    return {"Out": [TensorMeta((tokens, width), y.dtype)]}
