"""Pipeline parallelism over the "pp" mesh axis.

Reference parity: fluid PipelineOptimizer + section_worker (device_worker.cc)
— the reference runs program "sections" on different GPUs connected by
queues. TPU-native: every chip on the pp axis holds ONE stage's weights;
a shard_map SPMD program runs `n_micro + n_stage - 1` ticks of lax.scan,
rotating microbatch activations around the ring with lax.ppermute (GPipe
schedule: the skew fills/drains the bubble). All chips execute the same
code — stage identity comes from lax.axis_index — which is exactly how XLA
wants MPMD expressed as SPMD.

This is a library-level facility (like ring_attention): stage functions are
JAX callables (e.g. built from dygraph layers or op kernels); the static
Program path reaches it through fleet strategy pp_stage_fns.
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _pvary(x, axis_names):
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    # per-axis so an already-varying axis (e.g. zeros_like of pp-sharded
    # params) is simply skipped
    for a in axis_names:
        if not a:
            continue
        try:
            x = lax.pcast(x, (a,), to="varying")
        except ValueError:      # already varying on this axis
            pass
    return x


def _data_spec(dp_axis):
    """Spec for (n_micro, micro_batch, ...) data: micro dim replicated,
    batch dim sharded over dp when a dp axis is in play."""
    return P(None, dp_axis) if dp_axis else P()


def pipeline_forward_local(stage_fn, n_stage, n_micro, axis_name="pp",
                           dp_axis=None, replicate_out=True):
    """The GPipe forward BODY — runs INSIDE a shard_map over the pp(xdp)
    mesh. Returns ``fwd(params_me, x_local) -> outputs``: params_me is
    THIS stage's params (no leading stage dim), x_local all microbatches
    (dp-sharded batch dim), outputs the last stage's results replicated
    over pp (psum of the one-hot contribution). Exposed so callers that
    already live inside one shard_map scope (the CompiledProgram pp
    path, which also traces the optimizer section in the same scope)
    can compose it; :func:`pipeline_forward` wraps it for library use.

    replicate_out=False skips the final pp psum and returns each
    shard's LOCAL outputs buffer (real results only on the last stage)
    — what a caller that differentiates INSIDE the shard_map needs:
    with VMA checking off the psum's transpose miscounts the replicated
    cotangent, so the loss must be masked to the last stage instead
    (see pipeline_gpipe_local)."""
    ticks = n_micro + n_stage - 1
    perm = [(i, (i + 1) % n_stage) for i in range(n_stage)]
    vary_axes = (axis_name, dp_axis)

    def fwd(params_me, x_local):
        stage = lax.axis_index(axis_name)
        h_shape = x_local.shape[1:]
        carry_in = _pvary(jnp.zeros(h_shape, x_local.dtype), vary_axes)
        outputs = _pvary(jnp.zeros((n_micro,) + h_shape, x_local.dtype),
                         vary_axes)

        def tick(state, t):
            carry, outputs = state
            # stage 0 ingests microbatch t (if any); others use carry
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            inject = lax.dynamic_index_in_dim(x_local, mb_idx, 0,
                                              keepdims=False)
            h_in = jnp.where(stage == 0, inject, carry)
            h_out = stage_fn(params_me, h_in)
            # last stage records its result for microbatch t - (n_stage-1)
            out_idx = jnp.clip(t - (n_stage - 1), 0, n_micro - 1)
            is_valid = (t >= n_stage - 1) & (stage == n_stage - 1)
            cur = lax.dynamic_index_in_dim(outputs, out_idx, 0,
                                           keepdims=False)
            upd = jnp.where(is_valid, h_out, cur)
            outputs = lax.dynamic_update_index_in_dim(outputs, upd,
                                                      out_idx, 0)
            # rotate activations forward around the ring
            carry = lax.ppermute(h_out, axis_name, perm)
            return (carry, outputs), None

        (carry, outputs), _ = lax.scan(tick, (carry_in, outputs),
                                       jnp.arange(ticks))
        if not replicate_out:
            return outputs
        # only the last stage holds real outputs; broadcast to all so the
        # result is replicated (psum of one-hot contribution)
        contrib = jnp.where(stage == n_stage - 1, outputs,
                            jnp.zeros_like(outputs))
        return lax.psum(contrib, axis_name)

    return fwd


def pipeline_forward(stage_fn, params_stacked, x_micro, mesh,
                     axis_name="pp", dp_axis=None):
    """Run a GPipe forward over the pp ring.

    stage_fn(stage_params, h) -> h        (same signature every stage)
    params_stacked: pytree with leading dim n_stage (stage-sharded on pp)
    x_micro: (n_micro, micro_batch, ...) microbatched input
    dp_axis: optional second mesh axis the micro-batch dim is sharded over
    (dp x pp: params replicated over dp, XLA psums their grads there).
    Returns (n_micro, micro_batch, ...) outputs of the LAST stage.
    """
    n_stage = mesh.shape[axis_name]
    n_micro = x_micro.shape[0]
    fwd = pipeline_forward_local(stage_fn, n_stage, n_micro, axis_name,
                                 dp_axis)

    def local_fn(params_local, x_local):
        params_me = jax.tree.map(lambda p: p[0], params_local)
        return fwd(params_me, x_local)

    fn = shard_map(
        local_fn, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis_name), params_stacked),
                  _data_spec(dp_axis)),
        out_specs=_data_spec(dp_axis))
    return fn(params_stacked, x_micro)


def pipeline_gpipe_local(stage_fn, loss_fn, n_stage, n_micro,
                         axis_name="pp", dp_axis=None):
    """GPipe loss+grads BODY for single-shard_map callers (the
    CompiledProgram pp path): ``step(params_me, x_local, y_local) ->
    (loss, grads_me)`` with autodiff run INSIDE the shard_map scope
    (vjp of the local forward; ppermute/psum transpose to the reverse
    ring). loss_fn(h_m, y_m) -> scalar per-microbatch loss; loss/grads
    are the mean over microbatches, pp-replicated. Like
    :func:`pipeline_1f1b_local` the dp reduction is LEFT TO THE CALLER
    (grads come back dp-varying) so a quantized or otherwise custom dp
    gradient sync can slot in."""
    # NO final psum in the differentiated forward: with VMA checking off
    # the psum transpose miscounts a replicated cotangent. The loss is
    # masked to the last stage instead — its cotangent rides the reverse
    # ppermute ring back through the stages, and the scalar loss is
    # pp-psum'd OUTSIDE the grad computation.
    fwd = pipeline_forward_local(stage_fn, n_stage, n_micro, axis_name,
                                 dp_axis, replicate_out=False)

    def step(params_me, x_local, y_local):
        stage = lax.axis_index(axis_name)
        is_last = stage == n_stage - 1
        # dp-varying params keep each shard's cotangent local; the
        # caller runs ONE dp reduction for the whole step (same trick
        # as pipeline_1f1b_step's params_vjp)
        params_vjp = params_me if dp_axis is None else jax.tree.map(
            lambda p: _pvary(p, (dp_axis,)), params_me)

        def total(ps):
            out = fwd(ps, x_local)
            losses = jax.vmap(loss_fn)(out, y_local)
            local = jnp.mean(losses.astype(jnp.float32))
            # non-last stages ran loss_fn on their (zeros) local buffer:
            # mask it out — where's vjp seeds the untaken side with zero
            return jnp.where(is_last, local, 0.0)

        loss, grads = jax.value_and_grad(total)(params_vjp)
        loss = lax.psum(loss, axis_name)
        return loss, grads

    return step


def pipeline_loss_and_grads(stage_fn, loss_fn, params_stacked, x_micro,
                            y_micro, mesh, axis_name="pp", dp_axis=None):
    """Differentiable pipeline step: mean loss over microbatches and grads
    for every stage's params (stage-sharded like the params). With dp_axis
    the micro-batch dim is dp-sharded; AD's shard_map transpose inserts the
    dp psum on parameter grads automatically."""

    def total_loss(params_stacked):
        out = pipeline_forward(stage_fn, params_stacked, x_micro, mesh,
                               axis_name, dp_axis=dp_axis)
        return loss_fn(out, y_micro)

    return jax.value_and_grad(total_loss)(params_stacked)


def stack_stage_params(per_stage_params):
    """[stage0_tree, stage1_tree, ...] -> one tree with leading stage dim
    (requires homogeneous stages, the GPipe-on-SPMD contract)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)


def pipeline_1f1b_step(stage_fn, loss_fn, params_stacked, x_micro, y_micro,
                       mesh, axis_name="pp", dp_axis=None):
    """1F1B pipeline schedule (reference PipelineOptimizer's successor
    schedule; fluid's section_worker runs plain GPipe).

    Each scan tick performs ONE forward micro-step and ONE backward
    micro-step per stage, so at most ~2*n_stage microbatch activations are
    stashed per stage — GPipe-via-autodiff (pipeline_loss_and_grads) stashes
    all n_micro. Backward uses per-tick jax.vjp on the stashed stage INPUT
    (rematerialization: one extra forward per micro-step, the standard TPU
    trade of FLOPs for HBM).

    Schedule (stage s of n, tick k):
      forward  of microbatch  mf = k - s
      backward of microbatch  mb = k - (n-1) - (n-1-s)
    The last stage backpropagates a microbatch in the same tick its forward
    completes; grads ride the reverse ring one stage per tick, exactly one
    tick behind the stage above — the classic 1F1B steady state.

    loss_fn(h_out, y_one_micro) -> scalar per-microbatch loss; the returned
    loss/grads correspond to  mean_m loss_fn(chain(x_m), y_m).

    Returns (loss, grads_stacked) with grads sharded like params_stacked.
    """
    n_stage = mesh.shape[axis_name]
    n_micro = x_micro.shape[0]
    step = pipeline_1f1b_local(stage_fn, loss_fn, n_stage, n_micro,
                               axis_name, dp_axis)

    def local_fn(params_local, x_local, y_local):
        params_me = jax.tree.map(lambda p: p[0], params_local)
        loss, grads = step(params_me, x_local, y_local)
        if dp_axis is not None:
            # one batched dp reduction for the whole step (see the
            # params_vjp note inside the local body)
            loss = lax.pmean(loss, dp_axis)
            grads = jax.tree.map(lambda g: lax.pmean(g, dp_axis), grads)
        grads = jax.tree.map(lambda g: g[None], grads)
        return loss, grads

    fn = shard_map(
        local_fn, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis_name), params_stacked),
                  _data_spec(dp_axis),
                  jax.tree.map(lambda _: _data_spec(dp_axis), y_micro)),
        out_specs=(P(), jax.tree.map(lambda _: P(axis_name), params_stacked)))
    return fn(params_stacked, x_micro, y_micro)


def pipeline_1f1b_local(stage_fn, loss_fn, n_stage, n_micro,
                        axis_name="pp", dp_axis=None):
    """The 1F1B schedule BODY — runs INSIDE a shard_map over the pp(xdp)
    mesh: ``step(params_me, x_local, y_local) -> (loss, grads_me)``.
    params_me/grads_me carry NO leading stage dim (this shard's stage);
    loss is the microbatch mean, pp-replicated via psum. The dp
    reduction is deliberately LEFT TO THE CALLER — grads (and loss)
    come back dp-varying so a custom sync (e.g. the quantized
    collectives' quantize->psum->dequantize) can replace the plain
    pmean. :func:`pipeline_1f1b_step` wraps this with the shard_map +
    pmean defaults."""
    ticks = n_micro + 2 * (n_stage - 1)
    slots = 2 * n_stage
    perm_fwd = [(i, (i + 1) % n_stage) for i in range(n_stage)]
    perm_bwd = [(i, (i - 1) % n_stage) for i in range(n_stage)]

    vary_axes = (axis_name, dp_axis)

    def step(params_me, x_local, y_local):
        stage = lax.axis_index(axis_name)
        h_shape = x_local.shape[1:]
        dtype = x_local.dtype
        zero_h = jnp.zeros(h_shape, dtype)

        def fwd_of(h_in):
            return stage_fn(params_me, h_in)

        # params_me is REPLICATED over dp, so a vjp against it would make
        # shard_map's AD insert a param-sized dp psum EVERY tick. Marking
        # the params dp-varying first keeps each tick's cotangent local;
        # one psum after the scan does the whole reduction.
        params_vjp = params_me if dp_axis is None else jax.tree.map(
            lambda p: _pvary(p, (dp_axis,)), params_me)

        init = dict(
            fwd_carry=_pvary(zero_h, vary_axes),
            bwd_carry=_pvary(zero_h, vary_axes),
            stash=_pvary(jnp.zeros((slots,) + h_shape, dtype), vary_axes),
            grad_acc=jax.tree.map(
                lambda p: _pvary(jnp.zeros_like(p), vary_axes), params_me),
            loss_acc=_pvary(jnp.zeros((), jnp.float32), vary_axes),
        )

        def tick(state, k):
            mf = k - stage
            fwd_valid = (mf >= 0) & (mf < n_micro)
            mf_c = jnp.clip(mf, 0, n_micro - 1)
            mb = k - (n_stage - 1) - (n_stage - 1 - stage)
            bwd_valid = (mb >= 0) & (mb < n_micro)
            mb_c = jnp.clip(mb, 0, n_micro - 1)

            # ---- forward micro-step ------------------------------------
            inject = lax.dynamic_index_in_dim(x_local, mf_c, 0,
                                              keepdims=False)
            h_in = jnp.where(stage == 0, inject, state["fwd_carry"])
            h_out = fwd_of(h_in)
            stash = jnp.where(
                fwd_valid,
                lax.dynamic_update_index_in_dim(
                    state["stash"], h_in, mf_c % slots, 0),
                state["stash"])

            # last stage: per-micro loss + gradient seed, both this tick
            # (y may be a pytree of several label/aux feeds — tree.map
            # also handles the single-array case)
            y_m = jax.tree.map(
                lambda y: lax.dynamic_index_in_dim(y, mf_c, 0,
                                                   keepdims=False), y_local)
            loss_m, loss_vjp = jax.vjp(lambda h: loss_fn(h, y_m), h_out)
            is_last = stage == n_stage - 1
            loss_acc = state["loss_acc"] + jnp.where(
                fwd_valid & is_last,
                loss_m.astype(jnp.float32).reshape(()), 0.0)
            (g_seed,) = loss_vjp(jnp.ones_like(loss_m))

            # ---- backward micro-step (rematerialized vjp) --------------
            h_in_b = lax.dynamic_index_in_dim(stash, mb_c % slots, 0,
                                              keepdims=False)
            _, stage_vjp = jax.vjp(stage_fn, params_vjp, h_in_b)
            g_out = jnp.where(is_last, g_seed, state["bwd_carry"])
            dparams, dh_in = stage_vjp(g_out.astype(dtype))
            grad_acc = jax.tree.map(
                lambda a, g: a + jnp.where(bwd_valid, g, 0.0),
                state["grad_acc"], dparams)

            return dict(
                fwd_carry=lax.ppermute(h_out, axis_name, perm_fwd),
                bwd_carry=lax.ppermute(
                    jnp.where(bwd_valid, dh_in, jnp.zeros_like(dh_in)),
                    axis_name, perm_bwd),
                stash=stash, grad_acc=grad_acc, loss_acc=loss_acc), None

        state, _ = lax.scan(tick, init, jnp.arange(ticks))
        loss = lax.psum(state["loss_acc"], axis_name) / n_micro
        grads = jax.tree.map(lambda g: g / n_micro, state["grad_acc"])
        return loss, grads

    return step
