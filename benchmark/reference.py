"""The plain reference's shared half: matmul at a stated precision, plain
Adam, and the driver that follows a cell's first three steps block of rows
by block of rows. float32 `jax.numpy`, `highest` matmul precision, no
kernels; imports nothing of paddle_tpu and takes nothing it has made.

The family file (`families/<family>.py`) supplies the model: `reference_loss`
(one block's contribution to the batch's loss) and `block_of` (a row slice of
a batch). The lower precisions exist for the control that `correct` has to
fail: "bfloat16" rounds every matmul operand to bfloat16, "float8" is the
usual fp8 training recipe (operands to e4m3 and the incoming gradient to e5m2,
each scaled per tensor to its format's range, float32 accumulation).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float32", "bfloat16", "float8")
CHECK_STEPS = 3
_HIGHEST = jax.lax.Precision.HIGHEST


def _round_to(x, dtype):
    """x rounded through `dtype`, scaled per tensor so its largest
    magnitude meets the format's largest finite value (fp8 formats only)."""
    if dtype == jnp.bfloat16:
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    top = float(jnp.finfo(dtype).max)
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    scale = top / amax
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


def matmul_at(precision):
    """mm(a, b) -> a @ b (batched like jnp.matmul) at `precision`."""
    if precision not in PRECISIONS:
        raise ValueError("unknown precision %r (have %r)"
                         % (precision, PRECISIONS))

    def exact(a, b):
        return jnp.matmul(a, b, precision=_HIGHEST)

    if precision == "float32":
        return exact
    fwd_t, bwd_t = ((jnp.bfloat16, jnp.bfloat16) if precision == "bfloat16"
                    else (jnp.float8_e4m3fn, jnp.float8_e5m2))

    @jax.custom_vjp
    def mm(a, b):
        return exact(_round_to(a, fwd_t), _round_to(b, fwd_t))

    def mm_fwd(a, b):
        qa, qb = _round_to(a, fwd_t), _round_to(b, fwd_t)
        return exact(qa, qb), (qa, qb)

    def mm_bwd(res, g):
        qa, qb = res
        qg = _round_to(g, bwd_t)
        da = exact(qg, jnp.swapaxes(qb, -1, -2))
        db = exact(jnp.swapaxes(qa, -1, -2), qg)
        return _unbroadcast(da, qa.shape), _unbroadcast(db, qb.shape)

    mm.defvjp(mm_fwd, mm_bwd)
    return mm


def _unbroadcast(x, shape):
    """Sum the leading batch dims jnp.matmul broadcast over."""
    while x.ndim > len(shape):
        x = x.sum(axis=0)
    for i, (have, want) in enumerate(zip(x.shape, shape)):
        if want == 1 and have != 1:
            x = x.sum(axis=i, keepdims=True)
    return x


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def gelu(x):
    """erf GELU (the published BERT form; the program uses it for GPT too)."""
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / np.sqrt(2.0)))


def cross_entropy(logits, labels):
    """Per-row -log softmax(logits)[label]."""
    logp = logits - jax.scipy.special.logsumexp(logits, axis=-1,
                                                keepdims=True)
    return -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]


def adam_update(params, grads, m1, m2, step, opt):
    """One Adam step in the form the configuration states (`optimizer` in
    the config file): lr_t = lr*sqrt(1-b2^t)/(1-b1^t),
    p -= lr_t * m / (sqrt(v) + eps). `step` counts from 1."""
    b1, b2, eps, lr = (opt["beta1"], opt["beta2"], opt["epsilon"],
                       opt["learning_rate"])
    lr_t = lr * jnp.sqrt(1.0 - b2 ** step) / (1.0 - b1 ** step)
    m1 = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, m1, grads)
    m2 = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g,
                                m2, grads)
    params = jax.tree_util.tree_map(
        lambda p, m, v: p - lr_t * m / (jnp.sqrt(v) + eps), params, m1, m2)
    return params, m1, m2


@jax.jit
def leaf_norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def delta_norms(new, old):
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        new[k].astype(jnp.float32) - old[k].astype(jnp.float32))))
        for k in old}


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)
                                       - b.astype(jnp.float32))))


def follow(family, config, traffic, params, batches, precision="float32",
           mesh=None, compare_with=None, keep_first_gradient=False):
    """Follow the cell's first CHECK_STEPS steps from `params` (a dict of
    float32 arrays, the seeded weights) over `batches` (host batches, the
    window's own). On a multi-chip cell `mesh` spreads each block's rows
    over the chips (weights replicated), only to shorten the wait.
    `compare_with` is {who: {leaf: that side's first gradient, on the
    host}}: the result's "grad_diff_norms"[who][leaf] is the norm of its
    difference from this run's first gradient. `keep_first_gradient` brings
    this run's own first gradient back to the host (for a control, which is
    then compared like a program). Returns {"losses": [..], "grad_norms": {leaf: norm of
    the first gradient}, "delta_norms": {leaf: norm of the parameters'
    change after the steps}} as Python floats."""
    mm = matmul_at(precision)
    opt = config["optimizer"]
    rows = family.batch_rows(traffic)
    block = int(traffic["reference_block_rows"])
    if rows % block:
        raise ValueError("reference_block_rows %d does not divide the "
                         "batch's %d rows" % (block, rows))

    def block_loss(p, blk):
        return family.reference_loss(p, blk, config, traffic, mm)

    value_and_grad = jax.jit(jax.value_and_grad(block_loss))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=(0,))
    update = jax.jit(functools.partial(adam_update, opt=opt),
                     donate_argnums=(0, 2, 3), static_argnums=(4,))
    place_block = lambda blk: blk
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        params = jax.device_put(params, NamedSharding(mesh, PartitionSpec()))
        rows_sh = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
        place_block = lambda blk: jax.device_put(blk, rows_sh)
    start = params
    params = jax.tree_util.tree_map(jnp.copy, params)
    m1 = jax.tree_util.tree_map(jnp.zeros_like, params)
    m2 = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms, diffs, first = [], None, {}, None
    with jax.default_matmul_precision("highest"):
        for step in range(CHECK_STEPS):
            total, grads = 0.0, None
            for lo in range(0, rows, block):
                blk = place_block(family.block_of(batches[step], lo,
                                                  lo + block))
                part, g = value_and_grad(params, blk)
                grads = g if grads is None else add(grads, g)
                total = total + part
            losses.append(float(total))
            if step == 0:
                grad_norms = leaf_norms(grads)
                for who, theirs in (compare_with or {}).items():
                    diffs[who] = {k: float(_diff_norm(g, theirs[k]))
                                  for k, g in grads.items()}
                if keep_first_gradient:
                    first = {k: np.asarray(g) for k, g in grads.items()}
            params, m1, m2 = update(params, grads, m1, m2, step + 1)
        deltas = delta_norms(params, start)
    out = {"losses": losses,
           "grad_norms": {k: float(v) for k, v in grad_norms.items()},
           "delta_norms": {k: float(v) for k, v in deltas.items()},
           "grad_diff_norms": diffs}
    if keep_first_gradient:
        out["first_gradient"] = first
    return out
