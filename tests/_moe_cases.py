"""What the expert layer's test files share (`test_moe_layer.py`,
`test_moe_rows_in_use.py`, `test_moe_program.py`, `test_moe_ops.py`): the ops
composed as `layers.moe_ffn` composes them, the dense masked sum they are
held to, a layer's seeded weights, a grouped matmul that leaves NaN where
the kernels promise nothing, one run of a Program, and a program's ops by
digest (`test_nemotron_h.py` reads that one too)."""
import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.ops.pallas import grouped_matmul as gm
from paddle_tpu.ops.registry import get_op


def _op(name, ins, attrs=None):
    return get_op(name).fn(None, {k: [v] for k, v in ins.items()},
                           attrs or {})


def moe_by_ops(x, w_r, bias, w13, w2, top_k, held, routed_picks=None,
               norm=True):
    route = _op("moe_route", {"X": x, "W": w_r, "Bias": bias},
                {"top_k": top_k, "norm_topk_prob": norm})
    picks = route["TopE"] if routed_picks is None else routed_picks
    d = _op("moe_dispatch", {"X": x, "TopE": picks},
            {"experts_held": list(held)})
    y = _op("moe_experts", {"Rows": d["Rows"], "W13": w13, "W2": w2,
                            "GroupSizes": d["GroupSizes"],
                            "TileGroup": d["TileGroup"]})["Out"]
    out = _op("moe_combine", {"Y": y, "TopW": route["TopW"],
                              "Pos": d["Pos"], "RowPair": d["RowPair"],
                              "HeldPair": d["HeldPair"],
                              "GroupSizes": d["GroupSizes"]})
    return out["Out"], d["GroupSizes"], picks


def moe_dense(x, w_r, bias, w13, w2, top_k, held, routed_picks=None,
              norm=True):
    """Every held expert over every token, times the token's weight for
    it (0 where it did not pick it)."""
    scores = jax.nn.sigmoid(jnp.dot(x, w_r, precision="highest"))
    _t, picks = jax.lax.top_k(scores + bias, top_k)
    weights = jnp.take_along_axis(scores, picks, 1)
    if norm:
        weights = weights / (weights.sum(1, keepdims=True) + 1e-6)
    if routed_picks is not None:
        picks = routed_picks
    out = jnp.zeros_like(x)
    for g in range(held[1]):
        gate = jnp.sum(weights * (picks == held[0] + g), axis=1)
        a, b = jnp.split(jnp.dot(x, w13[g], precision="highest"), 2, axis=1)
        out += gate[:, None] * jnp.dot(jax.nn.silu(a) * b, w2[g],
                                       precision="highest")
    return out


def _layer_weights(tokens=48, d=16, ff=8, experts=8, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    return {"x": jax.random.normal(k[0], (tokens, d)),
            "w_r": jax.random.normal(k[1], (d, experts)),
            "bias": 0.3 * jax.random.normal(k[2], (experts,)),
            "w13": 0.5 * jax.random.normal(k[3], (experts, d, 2 * ff)),
            "w2": 0.5 * jax.random.normal(k[4], (experts, ff, d))}


def _share(p, held):
    lo, hi = held[0], held[0] + held[1]
    return p["x"], p["w_r"], p["bias"], p["w13"][lo:hi], p["w2"][lo:hi]


def _close(got, want, tol, what):
    """max |got - want| within `tol` of the larger of 1 and max |want|."""
    scale = max(1.0, float(jnp.max(jnp.abs(want))))
    gap = float(jnp.max(jnp.abs(got - want)))
    assert gap <= tol * scale, "%s: %.3g over %.3g" % (what, gap, scale)


def _grouped_matmul_that_leaves_nan(x, w, group_sizes, tm, interpret=None):
    """The XLA form, with what the kernels promise nothing about made as
    bad as it can be: the rows of no group (a tile's padding, the tail
    past the tiles in use) come back NaN from the product and from its
    dX, whatever went in."""
    lay = gm.layout(group_sizes, x.shape[0], tm)
    row = jnp.arange(x.shape[0])
    inside = ((row % tm) < lay["tile_end"][row // tm])[:, None]

    @jax.custom_vjp
    def product(x_, w_):
        return jnp.where(inside, gm.grouped_matmul_xla(
            x_, w_, group_sizes, tm), jnp.nan)

    def fwd(x_, w_):
        return product(x_, w_), (x_, w_)

    def bwd(res, dy):
        _out, vjp = jax.vjp(lambda a, b: gm.grouped_matmul_xla(
            a, b, group_sizes, tm), *res)
        dx, dw = vjp(jnp.where(inside, dy, 0.0))
        return jnp.where(inside, dx, jnp.nan), dw

    product.defvjp(fwd, bwd)
    return product(x, w)


def _run(build, feed):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        fetch = build()
    exe = pt.Executor()
    exe.run(startup)
    names = sorted(fetch)
    out = exe.run(main, feed=feed, fetch_list=[fetch[n] for n in names])
    return dict(zip(names, out)), main


def _op_digest(main):
    """Every op of the program by block, type and slot sizes, the expert
    layer's ops (and their `grad_of`s) with their plain attrs too; names
    left out (they count up with the process)."""
    import hashlib
    import json
    rows = []
    for blk in main.blocks:
        for op in blk.ops:
            attrs = {k: v for k, v in sorted(op.attrs.items())
                     if isinstance(v, (int, float, str, bool, type(None)))} \
                if op.type.startswith("moe_") else {}
            rows.append([blk.idx, op.type,
                         {k: len(v) for k, v in sorted(op.inputs.items())},
                         {k: len(v) for k, v in sorted(op.outputs.items())},
                         attrs])
    return len(rows), hashlib.sha256(
        json.dumps(rows, sort_keys=True).encode()).hexdigest()[:16]
