"""Trace engine: turn a Program into one JAX computation.

Reference parity: paddle/fluid/framework/executor.cc op loop +
grad_op_desc_maker.h. Instead of dispatching per-op kernels at runtime, we
*trace* every op's JAX kernel once under jax.jit, producing a single fused XLA
HLO computation for the whole program (forward + backward + optimizer). This
is the TPU-native realization of the reference ParallelExecutor's fused-graph
goal (framework/details/build_strategy.cc).

Autodiff: backward.append_backward emits generic ``grad_of`` ops. When the
forward op is traced we also capture its jax.vjp; the paired grad op later
calls that vjp, so the forward subgraph is computed ONCE and residuals are
shared — same cost model as the reference's explicit grad kernels.
"""
import numpy as np
import jax
import jax.numpy as jnp

from ..ops.registry import get_op
from .program import Program  # noqa: F401  (for type reference)

EMPTY_VAR = "@EMPTY@"
STEP_VAR = "@STEP_COUNTER@"
GRAD_OP_TYPE = "grad_of"


def zero_cotangent(v):
    if jnp.issubdtype(jnp.result_type(v), jnp.inexact):
        return jnp.zeros_like(v)
    return np.zeros(np.shape(v), dtype=jax.dtypes.float0)


class _VjpRecord(object):
    __slots__ = ("vjp_fn", "outs", "in_slots")

    def __init__(self, vjp_fn, outs, in_slots):
        self.vjp_fn = vjp_fn
        self.outs = outs          # {slot: [arrays]} forward outputs
        self.in_slots = in_slots  # [(slot, idx)] aligned with vjp grads


class TraceContext(object):
    """Per-trace state: PRNG derivation, vjp pairing, program access."""

    def __init__(self, program, base_key, want_vjp=frozenset()):
        self.program = program
        self.base_key = base_key
        self.want_vjp = want_vjp
        self.vjp_cache = {}
        self._op_key = base_key
        self._op_rng_count = 0
        self.outer_env = None  # set while tracing a uses_subblock op
        # quantized data-parallel gradient sync: when the compiler traces
        # the step inside a shard_map with quantize_collectives on, every
        # parameter gradient is synced (quantize -> psum -> dequantize)
        # the moment it is produced — see _maybe_sync_param_grads. The
        # scope also binds the sync axis so program-level collective ops
        # (c_allreduce_*) are live inside the quantized step.
        from ..ops import collective_ops as _cops
        self.grad_sync = _cops.current_grad_sync()
        self.synced_grads = set()
        self.bound_axes = () if self.grad_sync is None \
            else (self.grad_sync.axis_name,)
        # once-per-k quantized sync for grad-merge windows: when the
        # sync context opts in (BuildStrategy.quantize_merge_sync) and
        # the program carries GradientMergeOptimizer structure, the raw
        # per-step grads accumulate LOCALLY (exact fp32) and the sync
        # moves to the gated merged gradient under lax.cond — see
        # _maybe_sync_param_grads / _detect_merge_plan
        if self.grad_sync is not None and \
                getattr(self.grad_sync, "merge_window", False):
            self.merge_deferred, self.merge_gated = \
                _detect_merge_plan(program)
        else:
            self.merge_deferred, self.merge_gated = frozenset(), {}

    def begin_op(self, rng_tag):
        """rng_tag is the op's structural position (block, index) hash —
        stable across program rebuilds, unlike the global desc_id."""
        self._op_key = jax.random.fold_in(self.base_key, rng_tag % (2**31))
        self._op_rng_count = 0

    def rng(self):
        """Deterministic per-op PRNG key; stable across shardings/devices."""
        k = jax.random.fold_in(self._op_key, self._op_rng_count)
        self._op_rng_count += 1
        return k

    def trace_block(self, block, env):
        trace_block(block, env, self)


def _lookup(env, name, op):
    try:
        return env[name]
    except KeyError:
        raise KeyError(
            "op {%s} needs input var %r which has no value; it was neither "
            "fed, nor in scope, nor produced by an earlier op" % (op.type, name))


def _gather_inputs(op, env):
    return {slot: [_lookup(env, n, op) for n in names if n != EMPTY_VAR]
            for slot, names in op.inputs.items()}


def _bind_outputs(op, outs, env):
    for slot, names in op.outputs.items():
        vals = outs.get(slot)
        if vals is None:
            continue
        if not isinstance(vals, (list, tuple)):
            vals = [vals]
        if len(vals) != len(names):
            raise RuntimeError(
                "op {%s} slot %r produced %d values for %d vars" %
                (op.type, slot, len(vals), len(names)))
        for name, val in zip(names, vals):
            if name != EMPTY_VAR:
                env[name] = val


def _rng_tag(block, idx):
    return (block.idx + 1) * 1000003 + idx


GRAD_SUFFIX = "@GRAD"


def _detect_merge_plan(program):
    """Find GradientMergeOptimizer structure per persistable param:

        g = w@GRAD
        acc_new    = elementwise_add(acc, g)        # acc: *.grad_acc*
        apply_grad = scale(acc_new, 1/k)
        gated      = where(is_apply, apply_grad, zeros)
        <optimizer op consumes gated as Grad>

    Returns (deferred, gated): ``deferred`` is the raw grad names whose
    every-step sync is skipped; ``gated`` maps the where-output name ->
    {"raw": raw grad name, "pred": is_apply var name, "k": merge factor
    or None}. Cached per (program, version) — attrs-only stamping does
    not invalidate it, but minimize()/append_op bump the version."""
    cached = getattr(program, "_merge_plan_cache", None)
    if cached is not None and cached[0] == program._version:
        return cached[1], cached[2]
    blk = program.global_block()
    producer = {}
    for op in blk.ops:
        for nm in op.output_names():
            producer[nm] = op
    deferred, gated = set(), {}
    for op in blk.ops:
        if op.attrs.get("op_role") != "optimize" or "Grad" not in op.inputs \
                or "Param" not in op.inputs:
            continue
        pname = op.inputs["Param"][0]
        gname = op.inputs["Grad"][0]
        raw = pname + GRAD_SUFFIX
        if gname == raw:
            continue
        where_op = producer.get(gname)
        if where_op is None or where_op.type != "where":
            continue
        scale_op = None
        for slot in ("X", "Y"):
            cand = producer.get(where_op.inputs.get(slot, [""])[0])
            if cand is not None and cand.type == "scale":
                scale_op = cand
                break
        if scale_op is None:
            continue
        add_op = producer.get(scale_op.inputs["X"][0])
        if add_op is None or add_op.type != "elementwise_add":
            continue
        add_ins = add_op.input_names()
        if raw not in add_ins:
            continue
        acc = next((n for n in add_ins if n != raw), None)
        acc_var = blk._find_var_recursive(acc) if acc else None
        if acc_var is None or not getattr(acc_var, "persistable", False) \
                or ".grad_acc" not in acc:
            continue
        s = float(scale_op.attrs.get("scale", 1.0))
        k = None
        if 0.0 < s < 1.0 and abs(1.0 / s - round(1.0 / s)) < 1e-6:
            k = int(round(1.0 / s))
        deferred.add(raw)
        gated[gname] = {"raw": raw,
                        "pred": where_op.inputs["Condition"][0], "k": k}
    out = (frozenset(deferred), gated)
    program._merge_plan_cache = (program._version,) + out
    return out


def _maybe_sync_param_grads(op, env, ctx):
    """Quantized data-parallel gradient sync (ctx.grad_sync, installed by
    CompiledProgram under BuildStrategy.quantize_collectives).

    Fires on the FINAL binding of a persistable var's gradient — either
    the grad op binding ``w@GRAD`` directly, or the ``sum`` op merging
    ``w@GRAD@RENAME@k`` contributions — and replaces it in env with the
    synced value. Every consumer (grad clip, regularizer, gradient-merge
    accumulation, optimizer) then sees the globally-synced gradient,
    matching pjit's implicit-psum semantics; gradient-merge buffers
    accumulate the already-synced fp32 value, so accumulation stays
    exact and only the cross-host sync is quantized. Once per grad name
    per trace (ctx.synced_grads)."""
    sync = ctx.grad_sync
    if sync is None:
        return
    blk = ctx.program.global_block()
    for names in op.outputs.values():
        for n in names:
            if n in ctx.synced_grads or n not in env:
                continue
            spec = ctx.merge_gated.get(n)
            if spec is not None and spec["pred"] in env:
                # merge BOUNDARY: the gated merged gradient syncs under
                # lax.cond on the program's own apply predicate — the
                # k-1 non-apply steps skip the collective entirely
                ctx.synced_grads.add(n)
                env[n] = sync.sync_merged(spec["raw"], env[n],
                                          env[spec["pred"]], spec["k"])
                continue
            if not n.endswith(GRAD_SUFFIX):
                continue
            var = blk._find_var_recursive(n[:-len(GRAD_SUFFIX)])
            if var is None or not getattr(var, "persistable", False):
                continue
            if n in ctx.merge_deferred:
                # raw per-step grad of a merged param: accumulate
                # LOCALLY (exact fp32), sync once at the boundary above
                ctx.synced_grads.add(n)
                continue
            ctx.synced_grads.add(n)
            env[n] = sync.sync(n, env[n])


def trace_block(block, env, ctx):
    for i, op in enumerate(block.ops):
        trace_op(op, env, ctx, _rng_tag(block, i))


def op_scope(op):
    """``<role>/<op type>``: the name every op is lowered under
    (``jax.named_scope``), so that the compiled step's ``op_name``s — and
    with them the device trace — start with the program's own structure:
    ``jit(step)/forward/fc/...``, ``jit(step)/backward/fc/...``,
    ``jit(step)/optimize/adam/...``. The role is the op's ``op_role``
    (forward where it has none); a ``grad_of`` op is ``backward/<fwd
    type>``. Metadata only: nothing of the computation changes."""
    if op.type == GRAD_OP_TYPE:
        return "backward/" + op.attrs["fwd_type"]
    return "%s/%s" % (op.attrs.get("op_role", "forward"), op.type)


def trace_op(op, env, ctx, rng_tag=0):
    with jax.named_scope(op_scope(op)):
        if op.type == GRAD_OP_TYPE:
            return _trace_grad_op(op, env, ctx)
        return _trace_forward_op(op, env, ctx, rng_tag)


def _trace_forward_op(op, env, ctx, rng_tag):
    opdef = get_op(op.type)
    ins = _gather_inputs(op, env)
    ctx.begin_op(rng_tag)

    prev_outer = ctx.outer_env
    if opdef.uses_subblock:
        ctx.outer_env = env
    try:
        if op.desc_id in ctx.want_vjp and opdef.differentiable:
            outs = _trace_with_vjp(op, opdef, ins, ctx, rng_tag=rng_tag)
        else:
            outs = opdef.fn(ctx, ins, op.attrs)
    finally:
        ctx.outer_env = prev_outer
    _bind_outputs(op, outs, env)
    _maybe_sync_param_grads(op, env, ctx)


def _split_diff(opdef, ins):
    """Partition inputs into differentiable (flat list) and closed-over."""
    flat, slots = [], []
    for slot in sorted(ins):
        if slot in opdef.nondiff:
            continue
        for i, v in enumerate(ins[slot]):
            flat.append(v)
            slots.append((slot, i))
    return flat, slots


def _trace_with_vjp(op, opdef, ins, ctx, desc_id=None, rng_tag=0):
    desc_id = op.desc_id if desc_id is None else desc_id
    flat, in_slots = _split_diff(opdef, ins)

    def pure(*flat_vals):
        ins2 = {s: list(vs) for s, vs in ins.items()}
        for (slot, i), v in zip(in_slots, flat_vals):
            ins2[slot][i] = v
        ctx.begin_op(rng_tag)  # reset rng so replays are identical
        outs = opdef.fn(ctx, ins2, op.attrs)
        return {s: (list(v) if isinstance(v, (list, tuple)) else [v])
                for s, v in outs.items()}

    outs, vjp_fn = jax.vjp(pure, *flat)
    ctx.vjp_cache[desc_id] = _VjpRecord(vjp_fn, outs, in_slots)
    return outs


def _trace_grad_op(op, env, ctx):
    fwd_id = op.attrs["fwd_id"]
    rec = ctx.vjp_cache.get(fwd_id)
    if rec is None:
        # Forward op is not in this program (e.g. a pruned/partial program):
        # recompute its vjp from the forward inputs the grad op carries.
        # Inside one jitted train step this never happens — the pairing above
        # shares residuals, matching the reference's fwd/bwd kernel split.
        opdef = get_op(op.attrs["fwd_type"])
        fwd_ins = {slot[len("X:"):]: [_lookup(env, n, op) for n in names]
                   for slot, names in op.inputs.items()
                   if slot.startswith("X:")}
        fwd_op_attrs = op.attrs.get("fwd_attrs", {})

        class _FwdProxy(object):
            attrs = fwd_op_attrs
            type = op.attrs["fwd_type"]
            desc_id = fwd_id
        _trace_with_vjp(_FwdProxy, opdef, fwd_ins, ctx, desc_id=fwd_id)
        rec = ctx.vjp_cache[fwd_id]

    # Build cotangents matching the forward output structure.
    cot = {}
    for slot, fwd_vals in rec.outs.items():
        og_names = op.inputs.get("OG:" + slot, [EMPTY_VAR] * len(fwd_vals))
        cot[slot] = [env[n] if (n != EMPTY_VAR and n in env)
                     else zero_cotangent(v)
                     for n, v in zip(og_names, fwd_vals)]
    grads = rec.vjp_fn(cot)

    outs = {}
    for (slot, i), g in zip(rec.in_slots, grads):
        names = op.outputs.get("IG:" + slot)
        if not names or i >= len(names) or names[i] == EMPTY_VAR:
            continue
        if g is None or (hasattr(g, "dtype") and g.dtype == jax.dtypes.float0):
            continue
        outs.setdefault("IG:" + slot, {})[i] = g
    # normalize to aligned lists
    result = {}
    for slot, names in op.outputs.items():
        if slot not in outs:
            continue
        vals = [outs[slot].get(i, None) for i in range(len(names))]
        # drop positions with no grad by marking EMPTY binding
        result[slot] = [v if v is not None else None for v in vals]
        for i, v in enumerate(vals):
            if v is None and names[i] != EMPTY_VAR:
                raise RuntimeError(
                    "grad_of(%s): no gradient produced for %r (slot %s); "
                    "is the input non-differentiable?" %
                    (op.attrs["fwd_type"], names[i], slot))
    _bind_outputs(op, result, env)
    _maybe_sync_param_grads(op, env, ctx)
