"""Optimizer update op kernels.

Reference parity: paddle/fluid/operators/optimizers/{sgd_op,momentum_op,
adam_op,adagrad_op,rmsprop_op,adamax_op,lamb_op,lars_momentum_op,ftrl_op,
decayed_adagrad_op,...}.cc.

These ops are appended by paddle_tpu.optimizer.*.minimize() and run INSIDE
the same jitted step as forward/backward — XLA fuses the whole update, and
because the Executor donates parameter buffers the update is in-place in HBM.
All slot names match the reference so programs read identically.
"""
import jax.numpy as jnp
from jax import lax

from .registry import register_op


def _p(ins, slot):
    return ins[slot][0]


@register_op("sgd")
def _sgd(ctx, ins, attrs):
    p, g, lr = _p(ins, "Param"), _p(ins, "Grad"), _p(ins, "LearningRate")
    return {"ParamOut": p - lr.reshape(()).astype(p.dtype) * g}


@register_op("momentum")
def _momentum(ctx, ins, attrs):
    p, g, v = _p(ins, "Param"), _p(ins, "Grad"), _p(ins, "Velocity")
    lr = _p(ins, "LearningRate").reshape(()).astype(p.dtype)
    mu = attrs["mu"]
    v_new = mu * v + g
    if attrs.get("use_nesterov", False):
        p_new = p - (g + mu * v_new) * lr
    else:
        p_new = p - lr * v_new
    return {"ParamOut": p_new, "VelocityOut": v_new}


@register_op("lars_momentum")
def _lars_momentum(ctx, ins, attrs):
    p, g, v = _p(ins, "Param"), _p(ins, "Grad"), _p(ins, "Velocity")
    lr = _p(ins, "LearningRate").reshape(()).astype(p.dtype)
    mu = attrs["mu"]
    coeff = attrs.get("lars_coeff", 0.001)
    decay = attrs.get("lars_weight_decay", 0.0005)
    eps = attrs.get("epsilon", 1e-9)
    pn = jnp.sqrt(jnp.sum(jnp.square(p)))
    gn = jnp.sqrt(jnp.sum(jnp.square(g)))
    local_lr = jnp.where(pn > 0,
                         lr * coeff * pn / (gn + decay * pn + eps), lr)
    v_new = mu * v + local_lr * (g + decay * p)
    return {"ParamOut": p - v_new, "VelocityOut": v_new}


@register_op("adam")
def _adam(ctx, ins, attrs):
    p, g = _p(ins, "Param"), _p(ins, "Grad")
    m1, m2 = _p(ins, "Moment1"), _p(ins, "Moment2")
    b1p = _p(ins, "Beta1Pow").reshape(()).astype(jnp.float32)
    b2p = _p(ins, "Beta2Pow").reshape(()).astype(jnp.float32)
    lr = _p(ins, "LearningRate").reshape(()).astype(jnp.float32)
    b1, b2 = attrs.get("beta1", 0.9), attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    gf = g.astype(jnp.float32)
    m1n = b1 * m1 + (1 - b1) * gf
    m2n = b2 * m2 + (1 - b2) * gf * gf
    lr_t = lr * jnp.sqrt(1 - b2p) / (1 - b1p)
    p_new = p.astype(jnp.float32) - lr_t * m1n / (jnp.sqrt(m2n) + eps)
    if attrs.get("lazy_mode") and g.ndim >= 2:
        # reference lazy-mode adam (adam_op.h sparse path): rows absent
        # from the batch — all-zero grad rows for an embedding's dense
        # scatter-add gradient — keep their param AND moments untouched
        touched = jnp.any(gf != 0, axis=tuple(range(1, g.ndim)),
                          keepdims=True)
        m1n = jnp.where(touched, m1n, m1)
        m2n = jnp.where(touched, m2n, m2)
        p_new = jnp.where(touched, p_new, p.astype(jnp.float32))
    return {"ParamOut": p_new.astype(p.dtype), "Moment1Out": m1n,
            "Moment2Out": m2n,
            "Beta1PowOut": (b1p * b1).reshape(ins["Beta1Pow"][0].shape),
            "Beta2PowOut": (b2p * b2).reshape(ins["Beta2Pow"][0].shape)}


@register_op("adamw")
def _adamw(ctx, ins, attrs):
    outs = _adam(ctx, ins, attrs)
    coeff = attrs.get("coeff", 0.01)
    lr = _p(ins, "LearningRate").reshape(()).astype(jnp.float32)
    p = _p(ins, "Param")
    decayed = (outs["ParamOut"].astype(jnp.float32) -
               lr * coeff * p.astype(jnp.float32))
    g = _p(ins, "Grad")
    if attrs.get("lazy_mode") and g.ndim >= 2:
        # untouched rows must stay frozen — no decoupled decay either
        touched = jnp.any(g.astype(jnp.float32) != 0,
                          axis=tuple(range(1, g.ndim)), keepdims=True)
        decayed = jnp.where(touched, decayed,
                            outs["ParamOut"].astype(jnp.float32))
    outs["ParamOut"] = decayed.astype(p.dtype)
    return outs


@register_op("adagrad")
def _adagrad(ctx, ins, attrs):
    p, g, m = _p(ins, "Param"), _p(ins, "Grad"), _p(ins, "Moment")
    lr = _p(ins, "LearningRate").reshape(()).astype(p.dtype)
    eps = attrs.get("epsilon", 1e-6)
    m_new = m + g * g
    return {"ParamOut": p - lr * g / (jnp.sqrt(m_new) + eps),
            "MomentOut": m_new}


@register_op("decayed_adagrad")
def _decayed_adagrad(ctx, ins, attrs):
    p, g, m = _p(ins, "Param"), _p(ins, "Grad"), _p(ins, "Moment")
    lr = _p(ins, "LearningRate").reshape(()).astype(p.dtype)
    decay = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    m_new = decay * m + (1 - decay) * g * g
    return {"ParamOut": p - lr * g / (jnp.sqrt(m_new) + eps),
            "MomentOut": m_new}


@register_op("rmsprop")
def _rmsprop(ctx, ins, attrs):
    p, g = _p(ins, "Param"), _p(ins, "Grad")
    ms, mom = _p(ins, "MeanSquare"), _p(ins, "Moment")
    lr = _p(ins, "LearningRate").reshape(()).astype(p.dtype)
    rho = attrs.get("decay", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    momentum = attrs.get("momentum", 0.0)
    ms_new = rho * ms + (1 - rho) * g * g
    if attrs.get("centered", False):
        mg = _p(ins, "MeanGrad")
        mg_new = rho * mg + (1 - rho) * g
        mom_new = momentum * mom + lr * g / jnp.sqrt(
            ms_new - mg_new * mg_new + eps)
        return {"ParamOut": p - mom_new, "MeanSquareOut": ms_new,
                "MomentOut": mom_new, "MeanGradOut": mg_new}
    mom_new = momentum * mom + lr * g / jnp.sqrt(ms_new + eps)
    return {"ParamOut": p - mom_new, "MeanSquareOut": ms_new,
            "MomentOut": mom_new}


@register_op("adamax")
def _adamax(ctx, ins, attrs):
    p, g = _p(ins, "Param"), _p(ins, "Grad")
    m, inf = _p(ins, "Moment"), _p(ins, "InfNorm")
    b1p = _p(ins, "Beta1Pow").reshape(()).astype(jnp.float32)
    lr = _p(ins, "LearningRate").reshape(()).astype(p.dtype)
    b1, b2 = attrs.get("beta1", 0.9), attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-8)
    m_new = b1 * m + (1 - b1) * g
    inf_new = jnp.maximum(b2 * inf, jnp.abs(g))
    lr_t = lr / (1 - b1p)
    return {"ParamOut": p - lr_t * m_new / (inf_new + eps),
            "MomentOut": m_new, "InfNormOut": inf_new}


@register_op("lamb")
def _lamb(ctx, ins, attrs):
    p, g = _p(ins, "Param"), _p(ins, "Grad")
    m1, m2 = _p(ins, "Moment1"), _p(ins, "Moment2")
    b1p = _p(ins, "Beta1Pow").reshape(()).astype(jnp.float32)
    b2p = _p(ins, "Beta2Pow").reshape(()).astype(jnp.float32)
    lr = _p(ins, "LearningRate").reshape(()).astype(jnp.float32)
    b1, b2 = attrs.get("beta1", 0.9), attrs.get("beta2", 0.999)
    eps = attrs.get("epsilon", 1e-6)
    wd = attrs.get("weight_decay", 0.01)
    gf = g.astype(jnp.float32)
    pf = p.astype(jnp.float32)
    m1n = b1 * m1 + (1 - b1) * gf
    m2n = b2 * m2 + (1 - b2) * gf * gf
    m1h = m1n / (1 - b1p)
    m2h = m2n / (1 - b2p)
    r = m1h / (jnp.sqrt(m2h) + eps) + wd * pf
    pn = jnp.sqrt(jnp.sum(pf * pf))
    rn = jnp.sqrt(jnp.sum(r * r))
    ratio = jnp.where((pn > 0) & (rn > 0), pn / rn, 1.0)
    p_new = pf - lr * ratio * r
    return {"ParamOut": p_new.astype(p.dtype), "Moment1Out": m1n,
            "Moment2Out": m2n,
            "Beta1PowOut": (b1p * b1).reshape(ins["Beta1Pow"][0].shape),
            "Beta2PowOut": (b2p * b2).reshape(ins["Beta2Pow"][0].shape)}


@register_op("ftrl")
def _ftrl(ctx, ins, attrs):
    p, g = _p(ins, "Param"), _p(ins, "Grad")
    sq, lin = _p(ins, "SquaredAccumulator"), _p(ins, "LinearAccumulator")
    lr = _p(ins, "LearningRate").reshape(()).astype(p.dtype)
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    power = attrs.get("lr_power", -0.5)
    sq_new = sq + g * g
    sigma = (jnp.power(sq_new, -power) - jnp.power(sq, -power)) / lr
    lin_new = lin + g - sigma * p
    quad = jnp.power(sq_new, -power) / lr + 2 * l2
    pre = jnp.clip(lin_new, -l1, l1) - lin_new
    p_new = jnp.where(jnp.abs(lin_new) > l1, pre / quad, 0.0)
    return {"ParamOut": p_new, "SquaredAccumOut": sq_new,
            "LinearAccumOut": lin_new}


@register_op("dpsgd", uses_rng=True)
def _dpsgd(ctx, ins, attrs):
    import jax
    p, g = _p(ins, "Param"), _p(ins, "Grad")
    lr = _p(ins, "LearningRate").reshape(()).astype(p.dtype)
    clip = attrs.get("clip", 10.0)
    sigma = attrs.get("sigma", 1.0)
    gn = jnp.sqrt(jnp.sum(jnp.square(g)))
    g = g * jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12))
    noise = sigma * clip * jax.random.normal(ctx.rng(), g.shape, g.dtype)
    return {"ParamOut": p - lr * (g + noise)}


@register_op("average_accumulates", differentiable=False)
def _average_accumulates(ctx, ins, attrs):
    """Sliding-window parameter-sum accumulators for ModelAverage.

    Reference parity: paddle/fluid/operators/average_accumulates_op.h.
    All branching is jnp.where on scalar counters so the whole update stays
    inside the fused jitted step (no host round-trip per step).
    """
    p = _p(ins, "param")
    s1, s2, s3 = _p(ins, "in_sum_1"), _p(ins, "in_sum_2"), _p(ins, "in_sum_3")
    num_acc = _p(ins, "in_num_accumulates")
    old_acc = _p(ins, "in_old_num_accumulates")
    num_upd = _p(ins, "in_num_updates")
    rate = attrs["average_window"]
    min_w = attrs["min_average_window"]
    max_w = attrs["max_average_window"]
    k_max = 16384  # spill sum_1 into sum_2 to bound accumulation error
    num_upd = num_upd + 1
    num_acc = num_acc + 1
    s1 = s1 + p.astype(s1.dtype)
    spill = (num_upd % k_max == 0).reshape(())
    s2 = jnp.where(spill, s2 + s1, s2)
    s1 = jnp.where(spill, jnp.zeros_like(s1), s1)
    # reference truncates num_updates*average_window to integer before the
    # comparison (average_accumulates_op.h std::min<int64_t>)
    window = jnp.minimum(
        jnp.int32(max_w),
        (num_upd.astype(jnp.float32) * rate).astype(jnp.int32))
    trigger = ((num_acc >= min_w) & (num_acc >= window)).reshape(())
    s3 = jnp.where(trigger, s1 + s2, s3)
    s1 = jnp.where(trigger, jnp.zeros_like(s1), s1)
    s2 = jnp.where(trigger, jnp.zeros_like(s2), s2)
    old_acc = jnp.where(trigger, num_acc, old_acc)
    num_acc = jnp.where(trigger, jnp.zeros_like(num_acc), num_acc)
    return {"out_sum_1": s1, "out_sum_2": s2, "out_sum_3": s3,
            "out_num_accumulates": num_acc,
            "out_old_num_accumulates": old_acc,
            "out_num_updates": num_upd}


@register_op("adadelta")
def _adadelta(ctx, ins, attrs):
    """Ref adadelta_op.cc: accumulate squared grads and squared updates
    with decay rho; step = -sqrt(E[dx^2]+eps)/sqrt(E[g^2]+eps) * g."""
    p, g = _p(ins, "Param"), _p(ins, "Grad")
    eg = _p(ins, "AvgSquaredGrad")
    ex = _p(ins, "AvgSquaredUpdate")
    rho = attrs.get("rho", 0.95)
    eps = attrs.get("epsilon", 1e-6)
    eg_new = rho * eg + (1 - rho) * g * g
    update = -jnp.sqrt(ex + eps) / jnp.sqrt(eg_new + eps) * g
    ex_new = rho * ex + (1 - rho) * update * update
    return {"ParamOut": p + update, "AvgSquaredGradOut": eg_new,
            "AvgSquaredUpdateOut": ex_new}
