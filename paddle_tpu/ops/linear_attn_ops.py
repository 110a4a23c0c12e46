"""Linear-attention op kernels: `kda_attention`, the gated delta rule with a
per-channel decay (Kimi Delta Attention, arXiv:2510.26692 section 3), in its
chunked form, and the three elementwise ops a KDA layer puts around it
(`head_l2_norm`, `kda_gate`, `kda_out_norm`).

The recurrence, per batch row and head, S a (K, V) state that starts at
zeros, a_t = exp(g_t) in (0, 1] per key channel, b_t in (0, 1):

    S' = Diag(a_t) S_{t-1}
    S_t = S' - b_t k_t (k_t^T S') + b_t k_t v_t^T
    o_t = S_t^T q_t * scale

Chunked (chunks of CHUNK tokens, G the cumulative log-decay inside a chunk,
S0 the state the chunk begins with): with u_i = b_i (v_i - S'_i^T k_i) the
state is S_i = Diag(e^{G_i}) S0 + sum_{j<=i} Diag(e^{G_i-G_j}) k_j u_j^T, so

    (I + Diag(b) strictly_lower(A)) U = Diag(b) (V - (K e^G) S0),
        A_ij = sum_c k_ic k_jc e^{G_ic - G_jc}                       (1)
    O   = (Q e^G scale) S0 + lower(A^q) U,
        A^q_ij = scale sum_c q_ic k_jc e^{G_ic - G_jc}               (2)
    S_C = Diag(e^{G_C}) S0 + (K e^{G_C - G})^T U                     (3)

(1) is a unit-lower-triangular system a chunk: X = (I + Diag(b) A)^-1 is
formed once (the WY form: U = Wv - Wk S0 with Wv = X Diag(b) V and
Wk = X Diag(b) K e^G): the SUB-row blocks on the diagonal as the finite
series (I - L)(I + L^2)(I + L^4)(I + L^8), then a 2 x 2 block recursion over
them. Everything that does not read S0
(`_intra`) runs for GROUP chunks at once (its (SUB, SUB, K)-shaped decay
differences and its pullback's residuals are then a group's, not the
sequence's); (1)-(3) then walk the group's chunks in a `lax.scan` that
carries one (K, V) state a head, and an outer scan walks the groups.

No `exp` of a positive number is ever taken, because the gate is unbounded
below and a chunk's cumulative decay can pass float32's range: every decay
difference is formed as e^{G_i - G_j} with i >= j. Between two SUB-row
blocks that is e^{G_i - b} e^{b - G_j} with b the cumulative decay where
the later block begins (both exponents <= 0); inside one block it is taken
directly, (SUB, SUB, K) numbers a block.

The backward is one `jax.custom_vjp`: it keeps q, k, v, g, beta and the
state each chunk began with (T / CHUNK states of (K, V) float32 a head: no
state a token), and, a group at a time from the last, rebuilds `_intra`,
walks (1)-(3) backwards by hand and pulls `_intra` back. That pullback is
jax's own for the matmuls and the row-shaped passes, and written by hand for
the two pieces where jax's costs several times the forward:

  - the solve (`_unit_lower_inverse`): matmuls only, no row is written
    into an array (a row scatter a row over a 16-wide minor dimension), and
    the pullback reads X alone, d low = -strictly_lower(X^T dX X^T);
  - the products inside a sub-block (`_decay_products`): with D_rsc =
    e^{G_rc - G_sc} (r >= s), M = strictly_lower(d kk), N = scale d qk,
        P_rc = sum_s M_rs k_sc D_rsc        R_rc = sum_s N_rs k_sc D_rsc
        P^T_sc = sum_r M_rs k_rc D_rsc      R^T_sc = sum_r N_rs q_rc D_rsc
        dq = R,  dk = P + P^T + R^T,  dG = k (P - P^T) + q R - k R^T:
    products of the forward's shape summed over a SUB-long row axis into
    lane-dense (SUB, K) results; no (SUB, SUB, K) cotangent of a broadcast
    is formed and reduced. (The `kda_*` kernels' backward is held to these.)

Two implementations of the one algorithm. On the TPU, at head widths that
are multiples of 128, a call runs as the `kda_fwd` / `kda_bwd` Pallas
kernels of `pallas/delta_rule.py`: the state stays in VMEM across a
sequential chunk axis, nothing a chunk needs between its own steps goes to
HBM, and `_intra`'s six float32 arrays a group are never materialised. What
is written in THIS module is the XLA form: the path off the TPU and at
shapes the kernels do not tile, and the oracle the kernels are tested
against. Which of the two a call takes is decided from its own shapes and
the platform (`kernel_plan`), and by nothing else; `kda.plan`'s "kernels"
line says which.

The XLA form's parts lower under their own scopes inside the op's
(`kda_intra`, `kda_walk`, `kda_walk_back`, `kda_intra_back`), so a device
trace splits the op's time by them (the kernels are `kda_fwd` and `kda_bwd`
there, under the op's scope too). In both forms the cumulative decay, the
solve, both products and the state are float32; the other matmuls take
bfloat16 operands where the inputs are bfloat16 and give float32 results,
as the flash kernels do (any other dtype: float32 at HIGHEST).

Reference parity: none (the reference predates linear attention).
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register_op, register_shape_rule
from .shape_rules import ShapeError, TensorMeta, _known, _x

CHUNK = 64      # tokens a chunk: one state is kept a chunk
SUB = 16        # rows a sub-block (CHUNK is a multiple): decay differences
                # are re-based at each
GROUP = 16      # chunks whose state-free part is formed at once
_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


def _mm32(spec, a, b):
    return jnp.einsum(spec, a, b, precision=_HIGHEST,
                      preferred_element_type=_F32)


def _mm(spec, a, b, mxu):
    """einsum with float32 results; bfloat16 operands where `mxu` says."""
    if mxu == jnp.bfloat16:
        return jnp.einsum(spec, a.astype(mxu), b.astype(mxu),
                          preferred_element_type=_F32)
    return _mm32(spec, a, b)


def _mxu_dtype(dtype):
    return jnp.bfloat16 if jnp.dtype(dtype) == jnp.bfloat16 else _F32


def plan(q_shape, kernels=None):
    """What a call will do, for `kda.plan`: Python ints and strings only.
    `kernels` is `delta_rule.plan`'s answer where the call takes the Pallas
    kernels (its "kernels" line then says so, with the heads a grid step
    holds and its VMEM bytes); None is the XLA form."""
    b, t, h, k = q_shape
    per, groups = _groups_of(t)
    out = {"batch": b, "seq": t, "heads": h, "d_k": k, "chunk": CHUNK,
           "sub_block": SUB, "chunks": -(-t // CHUNK),
           "chunks_a_group": per, "groups": groups,
           "padded": per * groups * CHUNK - t,
           "kernels": "xla: batched matmuls a group of chunks + lax.scan "
                      "over chunks; solve: 16-row blocks as (I - L)(I + "
                      "L^2)(I + L^4)(I + L^8), 2 x 2 block recursion to "
                      "64, backward -strictly_lower(X^T dX X^T); in-block "
                      "decay products: backward by hand, three products "
                      "summed over rows, D formed again"}
    if kernels:
        # the kernels pad to whole chunks, not to whole groups
        out.update(kernels, padded=-(-t // CHUNK) * CHUNK - t)
    return out


def kernel_plan(q_shape, d_v, itemsize):
    """`delta_rule.plan` of the call where it takes the Pallas kernels:
    on the TPU (`default_interpret` is false) at shapes they tile; else
    None, the XLA form. Decided from the call's shapes and the platform,
    and by nothing else."""
    from .pallas import delta_rule
    from .pallas.interpret import default_interpret
    if default_interpret():
        return None
    return delta_rule.plan(tuple(q_shape), d_v, itemsize)


def _record_plan(q_shape, kernels):
    from ..framework import obs
    if obs.enabled():
        now = obs.now()
        obs.record("kda.plan", now, now, **plan(tuple(q_shape), kernels))


def _groups_of(t):
    """(chunks a group, groups) for a sequence of t tokens."""
    n = -(-t // CHUNK)
    per = min(GROUP, n)
    return per, -(-n // per)


def _chunks(x):
    """(B, T, H, ...) -> (groups, chunks a group, B, H, CHUNK, ...), T
    padded with zeros to whole groups (a padded token has k = 0, beta = 0,
    g = 0: it leaves the state as it is)."""
    b, t, h = x.shape[:3]
    per, groups = _groups_of(t)
    n = per * groups
    if n * CHUNK != t:
        x = jnp.pad(x, ((0, 0), (0, n * CHUNK - t)) + ((0, 0),) * (x.ndim - 2))
    x = x.reshape((b, n, CHUNK, h) + x.shape[3:])
    x = jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)      # (n, B, H, chunk, ..)
    return x.reshape((groups, per) + x.shape[1:])


def _tokens(x, t, dtype):
    """`_chunks` back: (groups, chunks a group, B, H, chunk, ...) ->
    (B, T, H, ...) in `dtype`."""
    x = x.reshape((-1,) + x.shape[2:])                  # (n, B, H, chunk, ..)
    n, b, h, c = x.shape[:4]
    x = jnp.moveaxis(jnp.moveaxis(x, 0, 2), 1, 3)       # (B, n, chunk, H, ..)
    return x.reshape((b, n * c, h) + x.shape[4:])[:, :t].astype(dtype)


def _block_inverse(low):
    """(I + low)^-1 for strictly lower triangular `low` (..., R, R), R a
    power of two, as the product (I - L)(I + L^2)(I + L^4)...(I + L^{R/2}):
    L^R = 0, so the series 1 - L + L^2 - ... ends there."""
    eye = jnp.eye(low.shape[-1], dtype=_F32)
    x, power, reach = eye - low, low, 1
    while 2 * reach < low.shape[-1]:
        power = _mm32("...rs,...sj->...rj", power, power)
        x = _mm32("...rs,...sj->...rj", x, eye + power)
        reach *= 2
    return x


@jax.custom_vjp
def _unit_lower_inverse(low):
    """X = (I + low)^-1 for strictly lower triangular `low` (..., C, C),
    float32. Its pullback reads X alone: d low = -strictly_lower(X^T dX
    X^T), two matmuls a chunk."""
    return _unit_lower_inverse_fwd(low)[0]


def _unit_lower_inverse_fwd(low):
    """The SUB-row blocks on the diagonal by `_block_inverse`, then the
    2 x 2 block recursion [[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1,
    B^-1]] up to the whole chunk (CHUNK / SUB is a power of two)."""
    size, lead, c = SUB, low.shape[:-2], low.shape[-1]

    def block(row, col):
        return low[..., row * size:(row + 1) * size,
                   col * size:(col + 1) * size]

    x = _block_inverse(jnp.stack(
        [block(n, n) for n in range(c // size)], axis=-3))
    while size < c:
        pairs = x.reshape(lead + (-1, 2, size, size))
        top, bottom = pairs[..., 0, :, :], pairs[..., 1, :, :]
        below = jnp.stack(
            [block(n + 1, n) for n in range(0, c // size, 2)], axis=-3)
        under = -_mm32("...rs,...sj->...rj", bottom,
                       _mm32("...rs,...sj->...rj", below, top))
        x = jnp.concatenate([
            jnp.concatenate([top, jnp.zeros_like(top)], axis=-1),
            jnp.concatenate([under, bottom], axis=-1)], axis=-2)
        size *= 2
    x = x.reshape(low.shape)
    return x, x


def _unit_lower_inverse_bwd(x, d_x):
    d_low = _mm32("...ji,...jk->...ik", x,
                  _mm32("...jk,...lk->...jl", d_x, x))
    return (-jnp.tril(d_low, -1),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _in_block_decay(cum_b):
    """e^{G_r - G_s} for r >= s inside a sub-block, 0 elsewhere: (.., SUB,
    SUB, K) from the cumulative decay (.., SUB, K), taken directly (the
    exponent is never positive)."""
    within = jnp.tril(jnp.ones((SUB, SUB), bool))
    return jnp.exp(jnp.where(
        within[..., None], cum_b[..., :, None, :] - cum_b[..., None, :, :],
        -jnp.inf))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _decay_products(q_b, k_b, cum_b, scale):
    """The two products inside a sub-block, D_rsc = `_in_block_decay`:
    kk_rs = sum_c k_rc k_sc D_rsc for r > s and qk_rs = scale sum_c q_rc
    k_sc D_rsc for r >= s, (.., SUB, SUB) each, float32."""
    return _decay_products_fwd(q_b, k_b, cum_b, scale)[0]


def _decay_products_fwd(q_b, k_b, cum_b, scale):
    k_cols = k_b[..., None, :, :] * _in_block_decay(cum_b)
    kk = jnp.tril(jnp.sum(k_b[..., :, None, :] * k_cols, axis=-1), -1)
    qk = jnp.sum(q_b[..., :, None, :] * k_cols, axis=-1) * scale
    return (kk, qk), (q_b, k_b, cum_b)


def _decay_products_bwd(scale, res, cots):
    """With M = strictly_lower(d kk) and N = scale d qk: P_rc = sum_s M_rs
    k_sc D_rsc, R_rc = sum_s N_rs k_sc D_rsc and (P^T + R^T)_sc = sum_r
    (M_rs k_rc + N_rs q_rc) D_rsc, three products of the forward's shape
    summed over a row axis; dq = R, dk = P + P^T + R^T and d cum = k (P -
    P^T - R^T) + q R. D is formed again (an `exp` a product element is
    cheaper than D's bytes)."""
    q_b, k_b, cum_b = res
    m = jnp.tril(cots[0], -1)[..., None]
    n = (cots[1] * scale)[..., None]
    decay = _in_block_decay(cum_b)
    k_cols = k_b[..., None, :, :] * decay
    p = jnp.sum(m * k_cols, axis=-2)
    r = jnp.sum(n * k_cols, axis=-2)
    back = jnp.sum((m * k_b[..., :, None, :] + n * q_b[..., :, None, :])
                   * decay, axis=-3)
    return r, p + back, k_b * (p - back) + q_b * r


_decay_products.defvjp(_decay_products_fwd, _decay_products_bwd)


def _intra(q, k, v, g, beta, scale, mxu):
    """All of a chunk that does not read the state it begins with, for every
    chunk of a group at once. q, k, g (n, B, H, C, K), v (.., C, V), beta
    (.., C). Returns (Wv (.., C, V), Wk (.., C, K), Q e^G scale (.., C, K),
    K e^{G_C - G} (.., C, K), lower(A^q) (.., C, C), e^{G_C} (.., K)), all
    float32."""
    q, k, v, g, beta = (x.astype(_F32) for x in (q, k, v, g, beta))
    c, sub = q.shape[-2], SUB
    ns = c // sub
    lead = q.shape[:-2]
    cum = _mm32("ij,...jk->...ik", jnp.tril(jnp.ones((c, c), _F32)), g)

    def blocks(x):
        return x.reshape(lead + (ns, sub, x.shape[-1]))

    cum_b, k_b, q_b = blocks(cum), blocks(k), blocks(q)
    # the cumulative decay where each sub-block begins
    base = cum_b[..., 0, :] - blocks(g)[..., 0, :]
    left = jnp.exp(cum_b - base[..., None, :])
    k_left, q_left = k_b * left, q_b * left * scale
    # inside a sub-block: e^{G_r - G_s}, r >= s, taken directly
    kk_diag, qk_diag = _decay_products(q_b, k_b, cum_b, scale)
    kk_rows, qk_rows = [], []
    for i in range(ns):
        before, after = i * sub, c - (i + 1) * sub
        kk, qk = [kk_diag[..., i, :, :]], [qk_diag[..., i, :, :]]
        if before:
            # against every earlier row j: e^{G_i - b} e^{b - G_j}
            k_right = k[..., :before, :] * jnp.exp(
                base[..., i:i + 1, :] - cum[..., :before, :])
            kk.insert(0, _mm32("...rk,...jk->...rj", k_left[..., i, :, :],
                               k_right))
            qk.insert(0, _mm32("...rk,...jk->...rj", q_left[..., i, :, :],
                               k_right))
        if after:
            zeros = jnp.zeros(lead + (sub, after), _F32)
            kk.append(zeros)
            qk.append(zeros)
        kk_rows.append(jnp.concatenate(kk, axis=-1))
        qk_rows.append(jnp.concatenate(qk, axis=-1))
    low = beta[..., :, None] * jnp.concatenate(kk_rows, axis=-2)
    a_qk = jnp.concatenate(qk_rows, axis=-2)
    x = _unit_lower_inverse(low)
    grow = jnp.exp(cum)
    end = cum[..., -1:, :]
    w_v = _mm("...ij,...jv->...iv", x, beta[..., None] * v, mxu)
    w_k = _mm("...ij,...jk->...ik", x, beta[..., None] * k * grow, mxu)
    return (w_v, w_k, q * grow * scale, k * jnp.exp(end - cum), a_qk,
            jnp.exp(end[..., 0, :]))


def _walk(parts, state, mxu):
    """(1)-(3) over a group's chunks in order, from `state`. Returns (the
    state after them, O (n, B, H, C, V), the state each chunk began with
    (n, B, H, K, V))."""
    def step(s, xs):
        w_v, w_k, q_bar, k_end, a_qk, decay = xs
        u = w_v - _mm("bhck,bhkv->bhcv", w_k, s, mxu)
        o = _mm("bhck,bhkv->bhcv", q_bar, s, mxu) \
            + _mm("bhcj,bhjv->bhcv", a_qk, u, mxu)
        new = decay[..., None] * s + _mm("bhck,bhcv->bhkv", k_end, u, mxu)
        return new, (o, s)

    last, (out, states) = lax.scan(step, state, parts)
    return last, out, states


def _walk_back(parts, states, d_out, d_state, mxu):
    """The pullback of `_walk`: (d of the state the group began with,
    d parts), from d O, d of the state after the group and the saved
    states."""
    def step(ds, xs):
        w_v, w_k, q_bar, k_end, a_qk, decay, s, do = xs
        u = w_v - _mm("bhck,bhkv->bhcv", w_k, s, mxu)
        du = _mm("bhcj,bhcv->bhjv", a_qk, do, mxu) \
            + _mm("bhck,bhkv->bhcv", k_end, ds, mxu)
        d_parts = (du,
                   -_mm("bhcv,bhkv->bhck", du, s, mxu),
                   _mm("bhcv,bhkv->bhck", do, s, mxu),
                   _mm("bhcv,bhkv->bhck", u, ds, mxu),
                   _mm("bhcv,bhjv->bhcj", do, u, mxu),
                   jnp.sum(s * ds, axis=-1))
        before = _mm("bhck,bhcv->bhkv", q_bar, do, mxu) \
            + decay[..., None] * ds \
            - _mm("bhck,bhcv->bhkv", w_k, du, mxu)
        return before, d_parts

    return lax.scan(step, d_state, tuple(parts) + (states, d_out),
                    reverse=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _kda(q, k, v, g, beta, scale):
    return _kda_fwd(q, k, v, g, beta, scale)[0]


def _kda_fwd(q, k, v, g, beta, scale):
    mxu = _mxu_dtype(q.dtype)

    def group(state, xs):
        with jax.named_scope("kda_intra"):
            parts = _intra(*xs, scale=scale, mxu=mxu)
        with jax.named_scope("kda_walk"):
            last, out, states = _walk(parts, state, mxu)
        return last, (out, states)

    start = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]), _F32)
    _last, (out, states) = lax.scan(
        group, start, tuple(_chunks(x) for x in (q, k, v, g, beta)))
    return _tokens(out, q.shape[1], v.dtype), (q, k, v, g, beta, states)


def _kda_bwd(scale, res, d_out):
    """A group at a time, last group first: rebuild the group's `_intra`
    with jax's pullback of it, walk its chunks backwards from the saved
    states, pull back; only d of the state crosses groups."""
    *inputs, states = res
    mxu = _mxu_dtype(inputs[0].dtype)

    def group(d_state, xs):
        *xs, states, d_out = xs
        with jax.named_scope("kda_intra"):
            parts, pull = jax.vjp(functools.partial(_intra, scale=scale,
                                                    mxu=mxu), *xs)
        with jax.named_scope("kda_walk_back"):
            d_state, d_parts = _walk_back(parts, states,
                                          d_out.astype(_F32), d_state, mxu)
        with jax.named_scope("kda_intra_back"):
            return d_state, pull(d_parts)

    _d0, grads = lax.scan(
        group, jnp.zeros_like(states[0, 0]),
        tuple(_chunks(x) for x in inputs) + (states, _chunks(d_out)),
        reverse=True)
    return tuple(_tokens(dx, x.shape[1], x.dtype)
                 for dx, x in zip(grads, inputs))


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda_attention(q, k, v, g, beta, scale=None):
    """The gated delta rule of the module docstring. q, k, g (B, T, H, K),
    v (B, T, H, V), beta (B, T, H); g is the log of the decay (<= 0), in
    float32. Returns o (B, T, H, V) in v's dtype. `scale` defaults to
    K^-1/2."""
    scale = float(q.shape[-1]) ** -0.5 if scale is None else float(scale)
    kernels = kernel_plan(q.shape, v.shape[-1], jnp.dtype(q.dtype).itemsize)
    _record_plan(q.shape, kernels)
    if kernels:
        from .pallas import delta_rule
        return delta_rule.kda(q, k, v, g, beta, scale)
    return _kda(q, k, v, g, beta, scale)


@register_op("kda_attention")
def _kda_attention(ctx, ins, attrs):
    return {"Out": kda_attention(
        ins["Q"][0], ins["K"][0], ins["V"][0], ins["G"][0], ins["Beta"][0],
        scale=attrs.get("scale"))}


@register_shape_rule("kda_attention")
def _kda_attention_rule(op, ins, attrs):
    q, k, v = _x(ins, "Q"), _x(ins, "K"), _x(ins, "V")
    g, beta = _x(ins, "G"), _x(ins, "Beta")
    if all(_known(m.shape) for m in (q, k, v, g, beta)):
        if (len(q.shape) != 4 or q.shape != k.shape or g.shape != q.shape
                or v.shape[:3] != q.shape[:3] or len(v.shape) != 4
                or beta.shape != q.shape[:3]):
            raise ShapeError(
                "kda_attention wants Q, K, G (B,T,H,K), V (B,T,H,V) and "
                "Beta (B,T,H); got %s" % ([m.shape for m in
                                           (q, k, v, g, beta)],))
    return {"Out": [TensorMeta(v.shape, v.dtype)]}


def _heads(x, head_dim):
    b, t, width = x.shape
    return x.reshape(b, t, width // head_dim, head_dim)


def _heads_meta(m, head_dim, dtype=None):
    shape = None
    if m.shape is not None and len(m.shape) == 3:
        b, t, width = m.shape
        shape = (b, t, width // head_dim if width not in (None, -1)
                 else None, head_dim)
    return TensorMeta(shape, dtype or m.dtype)


@register_op("head_l2_norm")
def _head_l2_norm(ctx, ins, attrs):
    """X (B, T, H*D) -> (B, T, H, D): each head's D numbers over their
    norm, x / sqrt(sum x^2 + epsilon), in float32, back in X's dtype."""
    x = ins["X"][0]
    xf = _heads(x.astype(_F32), int(attrs["head_dim"]))
    return {"Out": (xf * lax.rsqrt(
        jnp.sum(jnp.square(xf), axis=-1, keepdims=True)
        + attrs.get("epsilon", 1e-6))).astype(x.dtype)}


@register_shape_rule("head_l2_norm")
def _head_l2_norm_rule(op, ins, attrs):
    return {"Out": [_heads_meta(_x(ins), int(attrs["head_dim"]))]}


@register_op("kda_gate")
def _kda_gate(ctx, ins, attrs):
    """The log-decay: X (B, T, H*K), ALog (H,), DtBias (H*K,) ->
    g = -exp(ALog[h]) * softplus(X + DtBias), (B, T, H, K) in float32."""
    d = int(attrs["head_dim"])
    x = _heads(ins["X"][0].astype(_F32) + ins["DtBias"][0].astype(_F32), d)
    return {"Out": -jnp.exp(ins["ALog"][0].astype(_F32))[:, None]
            * jax.nn.softplus(x)}


@register_shape_rule("kda_gate")
def _kda_gate_rule(op, ins, attrs):
    return {"Out": [_heads_meta(_x(ins), int(attrs["head_dim"]),
                                "float32")]}


@register_op("kda_out_norm")
def _kda_out_norm(ctx, ins, attrs):
    """X (B, T, H, V), Gate (B, T, H*V), Scale (V,) -> (B, T, H*V): an RMS
    norm over each head's V numbers with the learned scale, times
    sigmoid(Gate); float32 inside, X's dtype out."""
    x, gate = ins["X"][0], ins["Gate"][0]
    xf = x.astype(_F32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                       + attrs.get("epsilon", 1e-5)) \
        * ins["Scale"][0].astype(_F32)
    y = y.reshape(gate.shape) * jax.nn.sigmoid(gate.astype(_F32))
    return {"Out": y.astype(x.dtype)}


@register_shape_rule("kda_out_norm")
def _kda_out_norm_rule(op, ins, attrs):
    x, gate = _x(ins), _x(ins, "Gate")
    if _known(x.shape) and _known(gate.shape):
        if len(x.shape) != 4 or tuple(gate.shape) != (
                x.shape[0], x.shape[1], x.shape[2] * x.shape[3]):
            raise ShapeError("kda_out_norm wants X (B,T,H,V) and Gate "
                             "(B,T,H*V); got %s and %s"
                             % (x.shape, gate.shape))
    return {"Out": [TensorMeta(gate.shape, x.dtype)]}
