"""The weighted form of `fused_mlm_head_loss` (ops/head_loss.py): the loss
and its gradients formed block by block over the token axis in the forward
pass. Against `jax.grad` of the plain chain; what its jaxpr may and may not
hold; the GPT and Phi programs trained through it against the same
programs built with the per-token form, on one device and under the CPU
mesh; `head.plan`; and the guard that BERT's step is the parent's.
"""
import hashlib
import json
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import layers, optimizer
from paddle_tpu.framework import obs
from paddle_tpu.framework.compiler import BuildStrategy, CompiledProgram
from paddle_tpu.framework.scope import Scope, scope_guard
from paddle_tpu.ops import head_loss
from paddle_tpu.ops.registry import get_op

V, D = 48, 16


def _inputs(t, seed=0, third_zero=False):
    rng = np.random.RandomState(seed)
    h = jnp.asarray(rng.randn(t, D).astype(np.float32))
    w = jnp.asarray(rng.randn(V, D).astype(np.float32) * 0.3)
    b = jnp.asarray(rng.randn(V).astype(np.float32) * 0.1)
    lbl = jnp.asarray(rng.randint(0, V, (t,)).astype(np.int32))
    tw = np.ones((t, 1), np.float32)
    if third_zero:
        tw[rng.permutation(t)[:t // 3]] = 0.0
    return h, w, b, lbl, jnp.asarray(tw)


def plain_chain(h, w, b, lbl, tw):
    """matmul + log_softmax + gather + weighted sum, in float32."""
    logits = h.astype(jnp.float32) @ w.astype(jnp.float32).T
    if b is not None:
        logits = logits + b
    ce = -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                              lbl[:, None], axis=-1)
    return jnp.sum(tw * ce)


def _value_and_grads(fn, h, w, b, cot):
    """loss and (dHidden, dWeight[, dBias]) under a cotangent `cot`."""
    args = (h, w) if b is None else (h, w, b)
    loss, vjp = jax.vjp(fn, *args)
    return loss, vjp(jnp.asarray(cot, loss.dtype))


def _close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.abs(got - want).max() / max(np.abs(want).max(), 1e-30) <= tol


# (token rows, block rows or None for `block_rows`' own choice)
BLOCKINGS = {"one_block": (512, None), "four_blocks": (64, 16),
             "rows_no_multiple_of_512": (520, None)}


@pytest.mark.parametrize("cot", [1.0, 3.0])
@pytest.mark.parametrize("third_zero", [False, True],
                         ids=["weights_one", "a_third_zero"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("blocking", sorted(BLOCKINGS))
def test_weighted_form_against_grad_of_the_plain_chain(blocking, bias,
                                                       third_zero, cot):
    t, rows = BLOCKINGS[blocking]
    h, w, b, lbl, tw = _inputs(t, third_zero=third_zero)
    b = b if bias else None
    assert t // (rows or head_loss.block_rows(t)) == \
        (4 if blocking == "four_blocks" else 1)

    def mine(h, w, b=None):
        return head_loss.weighted_head_loss(h, w, b, lbl, tw, False, rows)

    def plain(h, w, b=None):
        return plain_chain(h, w, b, lbl, tw)

    loss, grads = _value_and_grads(mine, h, w, b, cot)
    want_loss, want = _value_and_grads(plain, h, w, b, cot)
    _close(loss, want_loss, 1e-6)
    assert len(grads) == (3 if bias else 2)
    for g, o, x in zip(grads, want, (h, w, b)):
        assert g.shape == x.shape and g.dtype == x.dtype
        _close(g, o, 1e-6)
    # the primal rule (what a forward-only program runs) is the same loss
    _close(mine(h, w, b), want_loss, 1e-6)


@pytest.mark.parametrize("rows", [None, 16], ids=["one_block", "four_blocks"])
def test_cast_bf16_against_the_float32_oracle(rows):
    """bf16 operands at every dot, float32 accumulation and softmax: within
    test_flash_attention's bf16 tolerance (1e-2 of the oracle's range)."""
    h, w, b, lbl, tw = _inputs(64, seed=3, third_zero=True)
    hb = h.astype(jnp.bfloat16)

    def mine(h, w, b):
        return head_loss.weighted_head_loss(h, w, b, lbl, tw, True, rows)

    loss, grads = _value_and_grads(mine, hb, w, b, 1.0)
    want_loss, want = _value_and_grads(
        lambda h, w, b: plain_chain(h, w, b, lbl, tw), h, w, b, 1.0)
    assert [g.dtype for g in grads] == [jnp.bfloat16, jnp.float32,
                                        jnp.float32]
    for g, o in zip((loss,) + tuple(grads), (want_loss,) + tuple(want)):
        g, o = np.asarray(g, np.float32), np.asarray(o)
        assert np.abs(g - o).max() / max(np.abs(o).max(), 1.0) < 1e-2


def test_block_rows_come_from_the_row_count():
    assert head_loss.block_rows(16384) == 4096      # the GPT cells
    assert head_loss.block_rows(8192) == 4096       # the Phi cell
    assert head_loss.block_rows(5120) == 2560
    assert head_loss.block_rows(1536) == 1536
    assert head_loss.block_rows(512) == 512
    assert head_loss.block_rows(5121) == 5121       # no multiple of 512
    assert head_loss.block_rows(40) == 40


# ---------------------------------------------------------------------------
# what the traced op holds
# ---------------------------------------------------------------------------

class _Ctx(object):
    def rng(self):
        return jax.random.PRNGKey(0)


def _op(h, w, lbl, tw=None, cast_bf16=False):
    ins = {"Hidden": [h], "Weight": [w], "Label": [lbl[:, None]]}
    if tw is not None:
        ins["TokenWeight"] = [tw]
    return get_op("fused_mlm_head_loss").fn(
        _Ctx(), ins, {"cast_bf16": cast_bf16})["Loss"]


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _dots(fn, *args):
    """(narrower operand dtype, preferred_element_type, result dtype) of
    every dot_general `fn` traces, sorted."""
    out = []
    for eqn in _eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name == "dot_general":
            lhs, rhs = (v.aval.dtype for v in eqn.invars)
            out.append((str(min(lhs, rhs, key=lambda d: d.itemsize)),
                        str(eqn.params["preferred_element_type"]),
                        str(eqn.outvars[0].aval.dtype)))
    return sorted(out)


def _with_vjp(fn):
    def run(h, w):
        out, vjp = jax.vjp(fn, h, w)
        return out, vjp(jnp.ones(out.shape, out.dtype))
    return run


@pytest.mark.parametrize("cast_bf16", [False, True])
def test_every_dot_has_the_per_token_forms_dtypes(cast_bf16):
    """Three dots (projection, dHidden, dWeight), each with the operand
    width the per-token form gives the MXU (its backward dots take the
    float32 dlogits beside a bf16 operand: the narrower one counts) and the
    same float32 accumulation."""
    h, w, _b, lbl, tw = _inputs(64)
    if cast_bf16:
        h = h.astype(jnp.bfloat16)
    weighted = _dots(_with_vjp(
        lambda h, w: _op(h, w, lbl, tw, cast_bf16)), h, w)
    per_token = _dots(_with_vjp(
        lambda h, w: _op(h, w, lbl, None, cast_bf16)), h, w)
    assert weighted == per_token and len(weighted) == 3
    assert weighted[0] == ("bfloat16" if cast_bf16 else "float32",
                           "float32", "float32")


def _largest_array(fn, *args):
    return max(int(np.prod(v.aval.shape, dtype=np.int64))
               for eqn in _eqns(jax.make_jaxpr(fn)(*args).jaxpr)
               for v in eqn.outvars if hasattr(v.aval, "shape"))


def test_no_array_of_rows_by_vocab_at_four_blocks():
    t = 256
    h, w, _b, lbl, tw = _inputs(t)

    def weighted(rows):
        return _with_vjp(lambda h, w: head_loss.weighted_head_loss(
            h, w, None, lbl, tw, False, rows))

    assert _largest_array(weighted(t // 4), h, w) < t * V
    # the walk over sub-jaxprs does see such an array where there is one
    assert _largest_array(weighted(t), h, w) >= t * V
    assert _largest_array(_with_vjp(lambda h, w: _op(h, w, lbl)), h, w) \
        >= t * V


def test_a_forward_only_trace_has_the_projection_and_no_other_dot():
    h, w, _b, lbl, tw = _inputs(64)
    dots = _dots(lambda h, w: head_loss.weighted_head_loss(
        h, w, None, lbl, tw, False, 16), h, w)
    assert len(dots) == 1


# ---------------------------------------------------------------------------
# the programs that pass the op their token weights
# ---------------------------------------------------------------------------

def per_token_head(hidden, weight, label, bias=None, cast_bf16=False,
                   token_weight=None, _weighted=layers.fused_mlm_head_loss):
    """In `layers.fused_mlm_head_loss`' place: the per-token form, reduced
    by the same weights outside the op."""
    ce = _weighted(hidden, weight, label, bias=bias, cast_bf16=cast_bf16)
    return layers.reduce_sum(layers.elementwise_mul(ce, token_weight))


def _gpt(tp=False):
    from paddle_tpu.models import gpt
    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=2, ff_size=64, max_position=128,
                        dropout=0.0, tp=tp)
    batch, seq = 64, 128        # 8192 rows: two blocks of 4096

    def build():
        return gpt.gpt_pretrain_program(
            cfg, batch, seq, optimizer_fn=optimizer.Adam(1e-3).minimize)

    feed = gpt.synthetic_batch(cfg, batch, seq, seed=5)
    feed["loss_mask"][:, ::3] = 0.0
    return build, feed


def _phi():
    from paddle_tpu.models import phi4flash as pm
    cfg = pm.Phi4FlashConfig(
        vocab_size=96, hidden_size=64, num_heads=4, num_kv_heads=2,
        head_dim=16, ff_size=128, ssm_inner=128, ssm_state=4, ssm_dt_rank=4,
        window=8, layer_kinds=["memory", "full", "gmu", "cross"],
        published_layer_index=[16, 17, 18, 19], recompute=True)
    batch, seq = 128, 64        # 8192 rows: two blocks of 4096

    def build():
        return pm.phi4flash_pretrain_program(
            cfg, batch, seq, optimizer_fn=optimizer.Adam(1e-3).minimize)

    toks = np.random.RandomState(5).randint(
        0, 96, (batch, seq + 1)).astype(np.int64)
    feed = {"token_ids": toks[:, :-1, None], "labels": toks[:, 1:, None],
            "loss_mask": np.ones((batch, seq, 1), np.float32)}
    feed["loss_mask"][:, ::3] = 0.0
    return build, feed


def _train(build, feed, mesh_axes=None, steps=3, want_hlo=False):
    """`steps` Adam steps on one batch -> (losses, head.plan labels of
    the lowering[, the compiled step's HLO])."""
    obs.clear()
    obs.enable()
    try:
        with scope_guard(Scope()):
            main, startup, _feeds, fetch = build()
            program = main
            if mesh_axes:
                bs = BuildStrategy()
                bs.mesh_axes = dict(mesh_axes)
                program = CompiledProgram(main, bs)
            exe = pt.Executor()
            exe.run(startup)
            losses = [float(np.asarray(exe.run(
                program, feed=feed, fetch_list=[fetch["loss"]])[0]).reshape(()))
                for _ in range(steps)]
            plans = [p["labels"] for p in obs.spans(name="head.plan")]
            if want_hlo:
                return losses, plans, exe.dump_hlo(
                    program, feed=feed, fetch_list=[fetch["loss"]])["compiled"]
    finally:
        obs.disable()
        obs.clear()
    return losses, plans


@pytest.mark.parametrize("model", ["gpt", "phi4flash"])
def test_program_trains_to_the_per_token_forms_losses(model, monkeypatch):
    build, feed = _gpt() if model == "gpt" else _phi()
    losses, plans = _train(build, feed)
    vocab = 128 if model == "gpt" else 96
    assert plans == [dict(rows=8192, vocab=vocab, block_rows=4096, blocks=2,
                          form="weighted", operand_dtype="float32")]
    monkeypatch.setattr(layers, "fused_mlm_head_loss", per_token_head)
    want, want_plans = _train(build, feed)
    assert [p["form"] for p in want_plans] == ["per_token"]
    assert losses[-1] < losses[0]
    np.testing.assert_allclose(losses, want, rtol=1e-6, atol=0)


def _shapes(hlo, dtype="f32"):
    return set(tuple(int(n) for n in dims.split(","))
               for dims in re.findall(r"\b%s\[([\d,]+)\]" % dtype, hlo))


@pytest.mark.parametrize("mesh_axes,block_on_a_device", [
    ({"dp": 8}, (512, 128)), ({"dp": 4, "mp": 2}, (1024, 64))],
    ids=["dp8", "dp4_mp2"])
def test_under_a_mesh_every_block_keeps_the_token_axis_sharded(
        mesh_axes, block_on_a_device, monkeypatch):
    """The same losses as the per-token form under the same mesh; and in
    the partitioned step a block's logits are (4096 / dp, vocab / mp) on a
    device: a block of contiguous rows would be one device's alone."""
    build, feed = _gpt(tp="mp" in mesh_axes)
    losses, plans, hlo = _train(build, feed, mesh_axes, want_hlo=True)
    assert [(p["form"], p["blocks"]) for p in plans][:1] == [("weighted", 2)]
    shapes = _shapes(hlo)
    assert block_on_a_device in shapes
    assert not any(len(s) == 2 and s[0] * s[1] > np.prod(block_on_a_device)
                   and s[1] in (128, 64) for s in shapes), sorted(shapes)
    monkeypatch.setattr(layers, "fused_mlm_head_loss", per_token_head)
    want, _plans = _train(build, feed, mesh_axes)
    np.testing.assert_allclose(losses, want, rtol=1e-6, atol=0)


def test_the_profiler_prints_the_heads_plan_under_its_table(capsys):
    from paddle_tpu import profiler
    h, w, _b, lbl, tw = _inputs(64)
    obs.clear()
    obs.enable()
    try:
        jax.make_jaxpr(lambda h, w: _op(h, w, lbl, tw, True))(h, w)
        jax.make_jaxpr(lambda h, w: _op(h, w, lbl))(h, w)
        profiler.print_kernel_plans()
    finally:
        obs.disable()
        obs.clear()
    assert capsys.readouterr().out.splitlines() == [
        "head.plan block_rows=64 blocks=1 form=weighted "
        "operand_dtype=bfloat16 rows=64 vocab=48",
        "head.plan block_rows=64 blocks=1 form=per_token "
        "operand_dtype=float32 rows=64 vocab=48"]


def test_a_forward_only_program_forms_no_gradient():
    from paddle_tpu.models import gpt
    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=48, num_layers=1,
                        num_heads=2, ff_size=64, max_position=16)
    with scope_guard(Scope()):
        main, startup, _feeds, fetch = gpt.gpt_pretrain_program(
            cfg, 2, 16, is_test=True)
        assert fetch["loss"].shape == (1,)
        exe = pt.Executor()
        exe.run(startup)
        feed = gpt.synthetic_batch(cfg, 2, 16)
        loss = exe.run(main, feed=feed, fetch_list=[fetch["loss"]])[0]
        lowered = exe.dump_hlo(main, feed=feed, fetch_list=[fetch["loss"]],
                               include_compiled=False)["lowered"]
    assert loss.shape == (1,) and np.isfinite(loss).all()
    # of the model's widths only the vocabulary is 128: one dot touches
    # it, the projection
    assert len(re.findall(r"dot_general.*128", lowered)) == 1


def test_the_shape_rule_knows_both_forms():
    from paddle_tpu.framework import analysis

    def verify(weight_rows):
        main = pt.Program()
        blk = main.global_block()
        for name, shape, dtype in (("h", [8, D], "float32"),
                                   ("e", [V, D], "float32"),
                                   ("l", [8, 1], "int64"),
                                   ("w", [weight_rows, 1], "float32")):
            blk.create_var(name=name, shape=shape, dtype=dtype, is_data=True)
        blk.create_var(name="o", shape=None, dtype=None)
        blk.append_op("fused_mlm_head_loss",
                      inputs={"Hidden": ["h"], "Weight": ["e"],
                              "Label": ["l"], "TokenWeight": ["w"]},
                      outputs={"Loss": ["o"]})
        return analysis.verify_program(main, feeds=["h", "e", "l", "w"],
                                       fetch_list=["o"])

    assert not verify(8).errors()
    errors = verify(4).errors()
    assert errors and "TokenWeight" in errors[0].message


# ---------------------------------------------------------------------------
# BERT's step (the per-token form, through `layers.mean`) is the parent
# commit's: the digest of its jaxpr was taken there (PR 26), less one dead
# equation that PR 29 took out with the Pallas gate of
# `softmax_with_cross_entropy` (`_:i32[4] = squeeze[dimensions=(1,)] kq`: the
# next-sentence label, squeezed before the gate asked whether it was on;
# 166,118 characters with it). jit drops dead equations, and the lowered
# StableHLO of this step is the parent's to the byte (PERF.md, PR 29). After
# a deliberate change to what BERT lowers, print the new one with
# `python tests/test_fused_head_blocks.py` and say in PERF.md why.
# ---------------------------------------------------------------------------

BERT_STEP = {"sha256": "8e83920bc2ca135b0fa0e7460e2a526e90f0c9dc46196c214b92bfa7b5"
                       "96c235", "chars": 166075}


def bert_step_text():
    from paddle_tpu.models import bert
    cfg = bert.BertConfig(vocab_size=128, hidden_size=32, num_layers=2,
                          num_heads=2, ff_size=64, max_position=32,
                          hidden_dropout=0.0, attn_dropout=0.0,
                          dtype="bfloat16")
    with scope_guard(Scope()):
        main, startup, _feeds, fetch = bert.bert_pretrain_program(
            cfg, 4, 16, max_preds_per_seq=4,
            optimizer_fn=optimizer.Adam(1e-4).minimize)
        exe = pt.Executor()
        exe.run(startup)
        feed = exe._convert_feed(main, bert.synthetic_batch(
            cfg, 4, 16, max_preds_per_seq=4))
        scope = pt.global_scope()
        state_names, uses_rng = exe._prepare_state(main, feed, scope)
        step = exe._make_step(main, sorted(feed), [fetch["loss"].name],
                              state_names, uses_rng)
        state = tuple(scope.find_var(n) for n in state_names)
        text = str(jax.make_jaxpr(step)(
            state, tuple(feed[k] for k in sorted(feed))))
    # source positions move with every edit of a file; nothing else does
    return re.sub(r"/[^\s:\"']*\.py:\d+", "", text)


def test_berts_step_lowers_as_the_parent_commit_did():
    text = bert_step_text()
    assert (len(text), hashlib.sha256(text.encode()).hexdigest()) \
        == (BERT_STEP["chars"], BERT_STEP["sha256"])


if __name__ == "__main__":
    text = bert_step_text()
    print(json.dumps({"sha256": hashlib.sha256(text.encode()).hexdigest(),
                      "chars": len(text)}))
