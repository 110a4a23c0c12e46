"""State-space and gated-layer op kernels: `selective_scan` (the Mamba-1
recurrence; Pallas on the TPU, a chunked `lax.scan` elsewhere),
`mamba2_scan` (the Mamba-2 recurrence, one scalar decay a head, in its
chunked matmul form) with `mamba2_gate_norm` behind it, `causal_conv1d`
(depthwise, along time) and `rms_norm`.

`mamba2_scan`, per batch row and head h of P channels, S an (N, P) state
that starts at zeros, B_t and C_t the N numbers of the head's GROUP (head h
reads group h // (H / G)), dt_t = softplus(Dt_t + DtBias_h) > 0,
a_t = exp(-dt_t exp(ALog_h)) in (0, 1):

    S_t = a_t S_{t-1} + dt_t B_t x_t^T        y_t = S_t^T C_t + D_h x_t

Chunked (the SSD form of Dao & Gu 2024, arXiv:2405.21060 section 6; chunks
of `chunk_size` tokens, L the cumulative log-decay inside a chunk, S0 the
state a chunk begins with):

    y_i   = e^{L_i} S0^T C_i + sum_{j<=i} (C_i . B_j) e^{L_i - L_j} dt_j x_j
    S_end = e^{L_last} S0 + sum_j e^{L_last - L_j} dt_j B_j x_j^T

so a chunk is three matmuls (C B^T a group, the masked scores times x, the
chunk's own state) and the chunks are joined by a recurrence over
T / chunk_size states of (N, P) a head, carried in float32. Every decay is
formed as e^{L_i - L_j} with i >= j: no `exp` of a positive number. T is
padded to whole chunks with tokens of dt = 0 (decay 1, nothing written).

Two implementations of the one algorithm. On the TPU, at chunks of 128
tokens, a state that is a multiple of 128 and a group whose heads stand as
whole 128-lane tiles side by side, a call runs as the `ssd_fwd` / `ssd_bwd`
Pallas kernels of `pallas/ssd.py`: the heads' states stay in VMEM across a
sequential chunk axis and the (chunk, chunk) decay scores never reach HBM.
What is written in THIS module (`_ssd`) is the XLA form: the path off the
TPU and at shapes the kernels do not tile, and the oracle the kernels are
tested against. Which of the two a call takes is decided from its own shapes
and the platform (`kernel_plan`), and by nothing else; with obs on a
lowering records `ssd.plan`, whose "kernels" line says which.

Either backward is one `jax.custom_vjp`: it keeps x, dt, A, B, C and the
state each chunk began with (no state a token, none of the (chunk, chunk)
score matrices), and restarts from those states. In the XLA form the
in-chunk part is pulled back by jax from the same functions the forward
ran (under the scopes `ssd_states`, `ssd_outputs` and their `_back`s in a
device trace), the recurrence over chunks backwards by hand (lambda_c =
dS0_c + a_c lambda_{c+1}); `ssd_bwd` walks the chunks in reverse with
lambda in VMEM and pulls each chunk back by hand. In both the decays, the
cumulative sums and the state are float32; the matmuls take bfloat16
operands where the inputs are bfloat16 and give float32 results, as the
flash and `kda_*` kernels do (any other dtype: float32 at HIGHEST).

Reference parity: none — the reference predates state-space layers; the
equations are Gu & Dao 2023 (arXiv:2312.00752) section 3, Dao & Gu 2024 and
the RMSNorm of Zhang & Sennrich 2019. Gradients come from the generic
`grad_of` op: the scans' custom VJPs or jax's own.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from .linear_attn_ops import _mm     # float32 sums, bfloat16 or HIGHEST
from .registry import register_op, register_shape_rule
from .shape_rules import ShapeError, TensorMeta, _x


@register_op("selective_scan")
def _selective_scan(ctx, ins, attrs):
    from .pallas.selective_scan import selective_scan
    return {"Out": selective_scan(
        ins["X"][0], ins["Delta"][0], ins["A"][0], ins["B"][0], ins["C"][0],
        ins["D"][0])}


@register_shape_rule("selective_scan")
def _selective_scan_rule(op, ins, attrs):
    x, delta, a = _x(ins), _x(ins, "Delta"), _x(ins, "A")
    b, c, d = _x(ins, "B"), _x(ins, "C"), _x(ins, "D")
    known = [m.shape for m in (x, delta, a, b, c, d)]
    if all(s is not None and None not in s and -1 not in s[1:]
           for s in known):
        _bt, e, n = x.shape[:2], x.shape[2], a.shape[1]
        if (len(x.shape) != 3 or tuple(delta.shape) != tuple(x.shape)
                or tuple(a.shape) != (e, n)
                or tuple(b.shape[1:]) != (x.shape[1], n)
                or tuple(c.shape) != tuple(b.shape)
                or tuple(d.shape) != (e,)):
            raise ShapeError(
                "selective_scan wants X, Delta (B,T,E), A (E,N), B, C "
                "(B,T,N), D (E,); got %s" % (known,))
    return {"Out": [TensorMeta(x.shape, x.dtype)]}


_F32 = jnp.float32


def _log_decay(dt, a):
    """(L (B, C, H, Q): the cumulative log-decay inside each chunk,
    inclusive; its last column is the chunk's whole decay.)"""
    return jnp.cumsum(dt * a[None, None, None, :], axis=2).transpose(
        0, 1, 3, 2)


def _by_group(x, groups):
    """(B, C, Q, H, P) -> (B, C, Q, G, H / G, P): head h is of group
    h // (H / G)."""
    b, c, q, h, p = x.shape
    return x.reshape(b, c, q, groups, h // groups, p)


def _chunk_states(x, dt, a, b):
    """(each chunk's own state sum_j e^{L_last - L_j} dt_j B_j x_j^T,
    (B, C, H, N, P) float32; each chunk's whole decay e^{L_last},
    (B, C, H)). x (B, C, Q, H, P), dt (B, C, Q, H) float32, a (H,),
    b (B, C, Q, G, N)."""
    mxu = x.dtype
    groups = b.shape[3]
    log = _log_decay(dt, a)
    w = jnp.exp(log[..., -1:] - log) * dt.transpose(0, 1, 3, 2)
    xw = x.astype(_F32) * w.transpose(0, 1, 3, 2)[..., None]
    own = _mm("bcjgn,bcjghp->bcghnp", b, _by_group(xw, groups), mxu)
    return own.reshape(own.shape[:2] + (-1,) + own.shape[4:]), \
        jnp.exp(log[..., -1])


def _chunk_outputs(x, dt, a, b, c, s0):
    """y (B, C, Q, H, P) float32 of every chunk from the state it begins
    with, s0 (B, C, H, N, P) float32."""
    mxu = x.dtype
    q, groups = x.shape[2], b.shape[3]
    log = _log_decay(dt, a)                             # (B, C, H, Q)
    seen = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    decay = jnp.exp(jnp.where(seen, log[..., :, None] - log[..., None, :],
                              -jnp.inf))                # (B, C, H, Q, Q)
    cb = _mm("bcign,bcjgn->bcgij", c, b, mxu)
    scores = decay * dt.transpose(0, 1, 3, 2)[..., None, :]
    scores = scores.reshape(scores.shape[:2] + (groups, -1, q, q)) \
        * cb[:, :, :, None]
    xg = _by_group(x, groups)
    within = _mm("bcghij,bcjghp->bcighp", scores, xg, mxu)
    s0g = s0.reshape(s0.shape[:2] + (groups, -1) + s0.shape[3:])
    before = _mm("bcign,bcghnp->bcighp", c, s0g, mxu)
    before = before * jnp.exp(log).transpose(0, 1, 3, 2).reshape(
        before.shape[:5])[..., None]
    return (within + before).reshape(x.shape)


def _walk_chunks(own, whole):
    """The state each chunk begins with: s0_0 = 0, s0_{c+1} = whole_c s0_c
    + own_c. own (B, C, H, N, P), whole (B, C, H), float32."""
    def step(s, args):
        own_c, whole_c = args
        return whole_c[..., None, None] * s + own_c, s

    _last, s0 = lax.scan(step, jnp.zeros_like(own[:, 0]),
                         (own.swapaxes(0, 1), whole.swapaxes(0, 1)))
    return s0.swapaxes(0, 1)


@jax.custom_vjp
def _ssd(x, dt, a, b, c):
    """The chunked recurrence without its skip term: x (B, C, Q, H, P),
    dt (B, C, Q, H) float32, a (H,) float32 negative, b, c (B, C, Q, G, N)
    -> y (B, C, Q, H, P) float32."""
    return _ssd_fwd(x, dt, a, b, c)[0]


def _ssd_fwd(x, dt, a, b, c):
    with jax.named_scope("ssd_states"):
        s0 = _walk_chunks(*_chunk_states(x, dt, a, b))
    with jax.named_scope("ssd_outputs"):
        y = _chunk_outputs(x, dt, a, b, c, s0)
    return y, (x, dt, a, b, c, s0)


def _ssd_bwd(res, dy):
    """From the states the chunks began with: the outputs' pullback gives
    each chunk's d s0; the recurrence runs backwards by hand (lambda_c =
    d s0_c + whole_c lambda_{c+1}; d own_c = lambda_{c+1}; d whole_c =
    <lambda_{c+1}, s0_c>); the chunks' own states are pulled back last."""
    x, dt, a, b, c, s0 = res
    with jax.named_scope("ssd_outputs_back"):
        _y, pull = jax.vjp(_chunk_outputs, x, dt, a, b, c, s0)
        dx, ddt, da, db, dc, ds0 = pull(dy)
    with jax.named_scope("ssd_states_back"):
        (own, whole), pull = jax.vjp(_chunk_states, x, dt, a, b)

        def step(lam, args):
            ds0_c, s0_c, whole_c = args
            return ds0_c + whole_c[..., None, None] * lam, \
                (lam, jnp.sum(lam * s0_c, axis=(-2, -1)))

        _first, (d_own, d_whole) = lax.scan(
            step, jnp.zeros_like(own[:, 0]),
            (ds0.swapaxes(0, 1), s0.swapaxes(0, 1), whole.swapaxes(0, 1)),
            reverse=True)
        more = pull((d_own.swapaxes(0, 1), d_whole.swapaxes(0, 1)))
    return (dx + more[0], ddt + more[1], da + more[2], db + more[3], dc)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_plan(x_shape, groups, state, chunk, kernels=None):
    """What a call will do, for `ssd.plan`: Python ints and strings only.
    `kernels` is `pallas/ssd.plan`'s answer where the call takes the Pallas
    kernels (its "kernels" line then says so, with the heads a grid step
    holds and its VMEM bytes); None is the XLA form."""
    b, t, h, p = x_shape
    chunks = -(-t // chunk)
    out = {"batch": b, "seq": t, "heads": h, "head_dim": p,
           "groups": groups, "state": state, "chunk": chunk,
           "chunks": chunks, "padded": chunks * chunk - t,
           "state_bytes_kept": 4 * b * chunks * h * state * p,
           "kernels": "xla: C B^T a group and chunk, masked decay scores "
                      "times x, the chunks' own states, a lax.scan over "
                      "the chunks' float32 states; custom_vjp from the "
                      "states the chunks began with"}
    out.update(kernels or {})
    return out


def kernel_plan(x_shape, groups, state, chunk, itemsize):
    """`pallas/ssd.plan` of the call where it takes the Pallas kernels: on
    the TPU (`default_interpret` is false) at shapes they tile; else None,
    the XLA form (`_ssd`). Decided from the call's shapes and the
    platform, and by nothing else."""
    from .pallas import ssd
    from .pallas.interpret import default_interpret
    if default_interpret():
        return None
    return ssd.plan(tuple(x_shape), groups, state, chunk, itemsize)


def mamba2_scan(x, dt, dt_bias, a_log, b, c, d, chunk=128):
    """y_t = S_t^T C_t + D x_t over S_t = a_t S_{t-1} + dt_t B_t x_t^T
    (the module docstring): x (B, T, H, P), dt (B, T, H) before its bias and
    softplus, dt_bias, a_log, d (H,), b, c (B, T, G, N), H a multiple of G.
    Returns y (B, T, H, P) in x's dtype."""
    bsz, t, h, p = x.shape
    groups, state = b.shape[2], b.shape[3]
    kernels = kernel_plan(x.shape, groups, state, chunk,
                          jnp.dtype(x.dtype).itemsize)
    from ..framework import obs
    if obs.enabled():
        now = obs.now()
        obs.record("ssd.plan", now, now, **ssd_plan(
            tuple(x.shape), groups, state, chunk, kernels))
    step = jax.nn.softplus(dt.astype(_F32) + dt_bias.astype(_F32))
    a = -jnp.exp(a_log.astype(_F32))
    chunks = -(-t // chunk)
    pad = chunks * chunk - t

    def cut(m):
        if pad:     # a padded token has dt = 0: decay 1, nothing written
            m = jnp.pad(m, ((0, 0), (0, pad)) + ((0, 0),) * (m.ndim - 2))
        return m.reshape((bsz, chunks, chunk) + m.shape[2:])

    operands = (cut(x), cut(step), a, cut(b), cut(c))
    if kernels:
        from .pallas import ssd
        y = ssd.ssd(*operands)
    else:
        y = _ssd(*operands)
    y = y.reshape(bsz, chunks * chunk, h, p)[:, :t]
    y = y + d.astype(_F32)[None, None, :, None] * x.astype(_F32)
    return y.astype(x.dtype)


@register_op("mamba2_scan")
def _mamba2_scan(ctx, ins, attrs):
    return {"Out": mamba2_scan(
        ins["X"][0], ins["Dt"][0], ins["DtBias"][0], ins["ALog"][0],
        ins["B"][0], ins["C"][0], ins["D"][0],
        chunk=int(attrs.get("chunk_size", 128)))}


@register_shape_rule("mamba2_scan")
def _mamba2_scan_rule(op, ins, attrs):
    x, dt, b, c = _x(ins), _x(ins, "Dt"), _x(ins, "B"), _x(ins, "C")
    heads = [_x(ins, slot) for slot in ("DtBias", "ALog", "D")]
    known = [m.shape for m in [x, dt, b, c] + heads]
    if all(s is not None and None not in s and -1 not in s[1:]
           for s in known):
        if (len(x.shape) != 4 or len(b.shape) != 4
                or tuple(dt.shape[1:]) != tuple(x.shape[1:3])
                or tuple(b.shape[1:]) != tuple(c.shape[1:])
                or b.shape[1] != x.shape[1] or x.shape[2] % b.shape[2]
                or any(tuple(m.shape) != (x.shape[2],) for m in heads)):
            raise ShapeError(
                "mamba2_scan wants X (B,T,H,P), Dt (B,T,H), DtBias, ALog, "
                "D (H,), B, C (B,T,G,N) with H a multiple of G; got %s"
                % (known,))
    return {"Out": [TensorMeta(x.shape, x.dtype)]}


@register_op("mamba2_gate_norm")
def _mamba2_gate_norm(ctx, ins, attrs):
    """Y = GroupRMS(X * silu(Z)) * Scale: the gate first, then an RMS norm
    over each of `groups` equal runs of the last axis (Mamba-2's
    `norm_before_gate` false), in float32, back in X's dtype."""
    x, z = ins["X"][0], ins["Z"][0]
    groups = int(attrs.get("groups", 1))
    y = x.astype(_F32) * jax.nn.silu(z.astype(_F32))
    runs = y.reshape(y.shape[:-1] + (groups, -1))
    runs = runs * lax.rsqrt(jnp.mean(jnp.square(runs), axis=-1,
                                     keepdims=True)
                            + attrs.get("epsilon", 1e-5))
    y = runs.reshape(y.shape) * ins["Scale"][0].astype(_F32)
    return {"Y": y.astype(x.dtype)}


@register_shape_rule("mamba2_gate_norm")
def _mamba2_gate_norm_rule(op, ins, attrs):
    x, z = _x(ins), _x(ins, "Z")
    if x.shape is not None and z.shape is not None \
            and None not in x.shape[1:] and -1 not in x.shape[1:]:
        if tuple(x.shape[1:]) != tuple(z.shape[1:]) \
                or x.shape[-1] % int(attrs.get("groups", 1)):
            raise ShapeError("mamba2_gate_norm wants X and Z of one shape, "
                             "the last axis a multiple of `groups`; got %s "
                             "and %s" % (x.shape, z.shape))
    return {"Y": [TensorMeta(x.shape, x.dtype)]}


@register_op("causal_conv1d")
def _causal_conv1d(ctx, ins, attrs):
    """Depthwise causal convolution along time: Out[b, t, e] = Bias[e] +
    sum_k W[k, e] * X[b, t - (K-1) + k, e], reading zeros before t = 0.
    X: (B, T, E), W: (K, E). K shifted multiply-adds in float32 (K is 3 or
    4 in the models here, with or without a bias; a silu behind it is the
    layer's `act`: a `conv_general_dilated` with E groups of one channel
    would send a bandwidth-bound pass to the MXU's layout)."""
    x, w = ins["X"][0], ins["W"][0]
    k, t = w.shape[0], x.shape[1]
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    wf = w.astype(jnp.float32)
    out = sum(xf[:, i:i + t] * wf[i] for i in range(k))
    if ins.get("Bias"):
        out = out + ins["Bias"][0].astype(jnp.float32)
    return {"Out": out.astype(x.dtype)}


@register_shape_rule("causal_conv1d")
def _causal_conv1d_rule(op, ins, attrs):
    x, w = _x(ins), _x(ins, "W")
    if x.shape is not None and w.shape is not None:
        if len(x.shape) != 3 or len(w.shape) != 2 \
                or (x.shape[2] is not None and w.shape[1] is not None
                    and x.shape[2] != w.shape[1]):
            raise ShapeError("causal_conv1d wants X (B,T,E) and W (K,E), K "
                             "the width (3 or 4 in the models here); got %s "
                             "and %s" % (x.shape, w.shape))
    return {"Out": [TensorMeta(x.shape, x.dtype)]}


@register_op("rms_norm")
def _rms_norm(ctx, ins, attrs):
    """Y = X / sqrt(mean(X^2 over the last axis) + epsilon) * Scale, in
    float32, back in X's dtype."""
    x = ins["X"][0]
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                       + attrs.get("epsilon", 1e-5))
    if ins.get("Scale"):
        y = y * ins["Scale"][0].astype(jnp.float32)
    return {"Y": y.astype(x.dtype)}


@register_shape_rule("rms_norm")
def _rms_norm_rule(op, ins, attrs):
    x = _x(ins)
    return {"Y": [TensorMeta(x.shape, x.dtype)]}
