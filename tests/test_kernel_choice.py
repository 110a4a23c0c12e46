"""Which kernel a call gets is decided from the call's own shapes, in one
pure function (`flash_attention.attention_path`), and by nothing else:
the table of that function for every attention call the benchmark's cells
make, the `impl` attr's closed set, the doors that are gone (environment
variables, `BuildStrategy` fields) shown to move nothing, the whole train
step's jaxpr held to the parent commit's by digest, both forms of the LM
head against the plain chain, and `default_interpret`.

After a deliberate change to a step's lowering, print the new digests with
`python tests/test_kernel_choice.py` and say in PERF.md why.
"""
import hashlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, optimizer
from paddle_tpu.distributed import mesh as mesh_mod
from paddle_tpu.framework import compiler
from paddle_tpu.framework.scope import Scope, scope_guard
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas.interpret import default_interpret
from paddle_tpu.ops.registry import get_op


# ---------------------------------------------------------------------------
# the table: which path and which tiles each attention call gets
# ---------------------------------------------------------------------------

def site(name, q, k=None, v=None, dtype="bfloat16", mask=None, causal=True,
         window=None, interpret=False, impl="auto", blocks=None, why=None,
         backward="fused", visited=None, through_op=False, **explicit):
    """One row. `blocks` means "flash", with the `backward` the call gets:
    "fused" runs two kernels (forward, `flash_bwd`) and "split: <rule>"
    three (forward, dK/dV, dQ); a pair stands for the tile of each. `why`
    names the rule that sends the call to XLA. `visited`: (tiles the
    forward kernel runs, tiles of its grid), as PERF.md prints them.
    `through_op` also traces the registry op at these shapes."""
    if isinstance(blocks, tuple) and isinstance(blocks[0], int):
        blocks = (blocks,) * (2 if backward == "fused" else 3)
    k = k or q
    return pytest.param(dict(
        q=q, k=k, v=v or k, dtype=dtype, mask=mask, causal=causal,
        window=window, interpret=interpret, impl=impl, blocks=blocks,
        why=why, backward=backward if blocks else None, visited=visited,
        through_op=through_op, explicit=explicit), id=name)


PHI_Q, PHI_K, PHI_V = (2, 20, 8192, 64), (2, 10, 8192, 64), (2, 10, 8192, 128)

SITES = [
    # the four cells' attention calls at their true shapes, on the device
    site("bert-base.s128-b256", (256, 12, 128, 64), mask="key", causal=False,
         why="short", through_op=True),
    site("gpt2.t1024-b16", (16, 12, 1024, 64), through_op=True,
         blocks=((1024, 1024), (512, 512)), visited=(1, 1)),
    site("gpt2.t4096-b4", (4, 12, 4096, 64), through_op=True,
         blocks=(1024, 1024), visited=(10, 16)),
    site("phi4-mini-flash.t8192-b1/window", PHI_Q, PHI_K, PHI_V, window=512,
         through_op=True, blocks=(512, 512), backward="split: window",
         visited=(31, 256)),
    site("phi4-mini-flash.t8192-b1/full", PHI_Q, PHI_K, PHI_V,
         through_op=True, blocks=(1024, 1024), visited=(36, 64)),
    site("phi4-mini-flash.t8192-b1/cross", PHI_Q, PHI_K, PHI_V,
         through_op=True, blocks=(1024, 1024), visited=(36, 64)),
    # the other cells whose query heads share key/value heads (PR 49: the
    # fused kernel sums dK/dV over the group; a window keeps the pair)
    site("smallthinker-21b-a3b.t16384-b2/global", (2, 28, 16384, 128),
         (2, 4, 16384, 128), blocks=(1024, 1024), visited=(136, 256)),
    site("smallthinker-21b-a3b.t16384-b2/window", (2, 28, 16384, 128),
         (2, 4, 16384, 128), window=4096, blocks=(1024, 1024),
         backward="split: window", visited=(70, 256)),
    site("lfm2-8b-a1b.t8192-b2", (2, 32, 8192, 64), (2, 8, 8192, 64),
         blocks=(1024, 1024), visited=(36, 64)),
    site("nemotron-twotower-30b-a3b.t8192-b2", (2, 32, 8192, 128),
         (2, 2, 8192, 128), blocks=(1024, 1024), visited=(36, 64)),
    # the cells that wait (PERF.md §7)
    site("bert-base.s512-b32", (32, 12, 512, 64), mask="key", causal=False,
         through_op=True, blocks=(512, 512), visited=(1, 1)),
    site("bert-large.s128", (256, 16, 128, 64), mask="key", causal=False,
         why="short", through_op=True),
    # the 256 x 256 rule's edge, under "auto"
    site("256x256", (1, 2, 256, 64), causal=False, why="short"),
    site("128x512", (1, 2, 128, 64), (1, 2, 512, 64), causal=False,
         why="short"),
    site("128x1024", (1, 2, 128, 64), (1, 2, 1024, 64), causal=False,
         blocks=(128, 1024)),
    site("256x257", (1, 2, 256, 64), (1, 2, 257, 64), causal=False,
         interpret=True, blocks=(256, 257)),
    # the tile guards
    site("causal_tq_gt_tk", (1, 2, 512, 16), (1, 2, 256, 16), why="no_keys"),
    site("d_not_a_multiple_of_8", (1, 2, 512, 20), why="no_tile"),
    site("dv_not_a_multiple_of_8", (1, 2, 512, 16), (1, 2, 512, 16),
         (1, 2, 512, 12), why="no_tile"),
    site("tile_under_8", (1, 2, 1028, 16), interpret=True, why="no_tile"),
    site("tile_under_128_interpreted", (1, 2, 1088, 16), interpret=True,
         blocks=(64, 64)),
    site("tile_under_128_on_the_device", (1, 2, 1088, 16), why="lanes"),
    # what the caller says
    site("impl_xla_at_t4096", (4, 12, 4096, 64), impl="xla",
         through_op=True),
    site("impl_flash_at_t128", (2, 4, 128, 64), impl="flash",
         through_op=True, blocks=(128, 128), visited=(1, 1)),
    site("impl_flash_at_t64_on_the_device", (2, 4, 64, 64), impl="flash",
         why="lanes", through_op=True),
    site("explicit_tiles", (1, 2, 64, 16), interpret=True, impl="flash",
         blocks=(16, 32), visited=(6, 8), block_q=16, block_k=32),
    # the tile rule's other arms (test_flash_attention holds the rule)
    site("float32_t1024", (1, 2, 1024, 64), dtype="float32",
         blocks=(512, 512)),
    site("window_64_t512", (1, 2, 512, 16), window=64, blocks=(256, 256),
         backward="split: window", visited=(3, 4)),
    # which backward: the first rule that holds keeps the two kernels;
    # grouped heads alone keep no call off the fused kernel (PR 49)
    site("grouped_heads", (1, 4, 1024, 64), (1, 2, 1024, 64),
         blocks=((1024, 1024), (512, 512))),
    site("grouped_heads_and_a_window", (1, 4, 1024, 64), (1, 2, 1024, 64),
         window=256, blocks=(256, 256), backward="split: window"),
    # unequal widths alone keep no call off the fused kernel (PR 43): the
    # two Kimi cells' latent attention, and what weighs is dQ's row at its
    # 256 lanes (fused to Tq = 24,576 where D = 64 or 128 is to 32,768)
    site("value_width_differs", (1, 2, 1024, 64), (1, 2, 1024, 64),
         (1, 2, 1024, 128), blocks=((1024, 1024), (512, 512))),
    site("kimi.t8192-b2/latent", (2, 16, 8192, 192), (2, 16, 8192, 192),
         (2, 16, 8192, 128), through_op=True, blocks=(1024, 1024),
         visited=(36, 64)),
    site("value_width_differs_and_grouped_heads", (1, 4, 1024, 192),
         (1, 2, 1024, 192), (1, 2, 1024, 128),
         blocks=((1024, 1024), (512, 512))),
    site("value_width_differs_and_a_window", (1, 2, 1024, 192),
         (1, 2, 1024, 192), (1, 2, 1024, 128), window=256,
         blocks=(256, 256), backward="split: window"),
    site("dq_row_of_24576_at_192_fits", (1, 1, 24576, 192),
         (1, 1, 24576, 192), (1, 1, 24576, 128), blocks=(1024, 1024)),
    site("dq_row_of_32768_at_192_passes_the_ceiling", (1, 1, 32768, 192),
         (1, 1, 32768, 192), (1, 1, 32768, 128), blocks=(1024, 1024),
         backward="split: vmem"),
    site("dq_row_of_32768_at_64_with_values_of_128_fits", (1, 1, 32768, 64),
         (1, 1, 32768, 64), (1, 1, 32768, 128), blocks=(1024, 1024)),
    site("dq_row_of_32768_fits", (1, 1, 32768, 64), blocks=(1024, 1024)),
    site("dq_row_of_65536_passes_the_ceiling", (1, 1, 65536, 64),
         blocks=(1024, 1024), backward="split: vmem"),
    site("dq_row_of_65536_in_float32_passes_it", (1, 1, 65536, 128),
         dtype="float32", blocks=(512, 512), backward="split: vmem"),
    # a group's dK/dV rows are weighed too: the tile is halved where the
    # rows leave it less room, and the pair runs where no tile fits
    site("group_of_8_at_32768_halves_the_fused_tile", (1, 8, 32768, 128),
         (1, 1, 32768, 128), blocks=((1024, 1024), (512, 1024))),
    site("group_of_8_at_65536_passes_the_ceiling", (1, 8, 65536, 128),
         (1, 1, 65536, 128), blocks=(1024, 1024), backward="split: vmem"),
    site("not_causal_with_a_key_mask_is_fused_too", (2, 4, 2048, 64),
         mask="key", causal=False, blocks=(1024, 1024)),
]


def _op_jaxpr(c, monkeypatch):
    """The registry op traced at the row's shapes (nothing runs)."""
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET",
                       "1" if c["interpret"] else "0")
    (b, _h, tq, _d), tk = c["q"], c["k"][2]
    ins = {n: [jax.ShapeDtypeStruct(c[n.lower()], c["dtype"])]
           for n in ("Q", "K", "V")}
    if c["mask"] == "key":
        ins["Mask"] = [jax.ShapeDtypeStruct((b, 1, 1, tk), c["dtype"])]
    attrs = {"causal": c["causal"], "window": c["window"],
             "impl": c["impl"]}
    kern = get_op("scaled_dot_product_attention").fn
    names = sorted(ins)
    return str(jax.make_jaxpr(lambda *a: kern(
        None, {n: [x] for n, x in zip(names, a)}, attrs))(
        *(ins[n][0] for n in names)))


@pytest.mark.parametrize("c", SITES)
def test_attention_path_and_tiles_come_from_the_call(c, monkeypatch):
    flash = c["blocks"] is not None
    if c["impl"] != "xla":
        got = fa.attention_path(c["q"], c["k"], c["v"], c["dtype"],
                                c["causal"], c["window"], c["interpret"],
                                auto=c["impl"] == "auto", **c["explicit"])
        assert got == ("flash" if flash else "xla", c["blocks"], c["why"],
                       c["backward"])
        # pure: strings and ints in, the same answer again
        assert got == fa.attention_path(
            list(c["q"]), list(c["k"]), list(c["v"]), jnp.dtype(c["dtype"]),
            c["causal"], c["window"], c["interpret"],
            auto=c["impl"] == "auto", **c["explicit"])
    if c["visited"]:
        fwd = fa.plan(c["q"], c["k"], c["v"], c["causal"], c["window"],
                      c["blocks"])["fwd"]
        (bq, bk), tq, tk = c["blocks"][0], c["q"][2], c["k"][2]
        assert (fwd["tiles_visited"], (tq // bq) * (tk // bk)) \
            == c["visited"]
    if c["through_op"]:
        text = _op_jaxpr(c, monkeypatch)
        assert ("name=flash_fwd" in text) == flash
        assert text.count("pallas_call[") == (1 if flash else 0)


@pytest.mark.parametrize("value", ["flsh", "", "pallas"])
def test_an_unknown_impl_raises(value):
    q = jnp.zeros((1, 2, 16, 8), jnp.float32)
    kern = get_op("scaled_dot_product_attention").fn
    with pytest.raises(ValueError) as err:
        kern(None, {"Q": [q], "K": [q], "V": [q]}, {"impl": value})
    for known in ("auto", "flash", "xla", "ring", "ulysses"):
        assert repr(known) in str(err.value)
    assert repr(value) in str(err.value)


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_sequence_parallel_impls_need_their_mesh(impl, monkeypatch):
    monkeypatch.setattr(mesh_mod, "_mesh", None)
    q = jnp.zeros((1, 2, 16, 8), jnp.float32)
    kern = get_op("scaled_dot_product_attention").fn
    with pytest.raises(ValueError, match="needs init_mesh"):
        kern(None, {"Q": [q], "K": [q], "V": [q]}, {"impl": impl})

# ---------------------------------------------------------------------------
# the whole train step, traced abstractly, as text
# ---------------------------------------------------------------------------

def _bert():
    from paddle_tpu.models import bert
    cfg = bert.BertConfig(vocab_size=128, hidden_size=32, num_layers=2,
                          num_heads=2, ff_size=64, max_position=32,
                          hidden_dropout=0.0, attn_dropout=0.0,
                          dtype="bfloat16")
    main, startup, _feeds, fetch = bert.bert_pretrain_program(
        cfg, 4, 16, max_preds_per_seq=4,
        optimizer_fn=optimizer.Adam(1e-4).minimize)
    return main, startup, fetch["loss"], bert.synthetic_batch(
        cfg, 4, 16, max_preds_per_seq=4)


def _gpt():
    from paddle_tpu.models import gpt
    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=2, ff_size=64, max_position=512,
                        dropout=0.0, dtype="bfloat16", recompute=True)
    main, startup, _feeds, fetch = gpt.gpt_pretrain_program(
        cfg, 2, 512, optimizer_fn=optimizer.Adam(1e-4).minimize)
    return main, startup, fetch["loss"], gpt.synthetic_batch(cfg, 2, 512)


def _phi():
    from paddle_tpu.models import phi4flash as pm
    cfg = pm.Phi4FlashConfig(
        vocab_size=96, hidden_size=64, num_heads=4, num_kv_heads=2,
        head_dim=16, ff_size=128, ssm_inner=128, ssm_state=4, ssm_dt_rank=4,
        window=64, layer_kinds=["mamba", "window", "memory", "full", "gmu",
                                "cross"],
        published_layer_index=[0, 1, 16, 17, 18, 19], recompute=True,
        dtype="bfloat16")
    main, startup, _feeds, fetch = pm.phi4flash_pretrain_program(
        cfg, 2, 512, optimizer_fn=optimizer.Adam(1e-4).minimize)
    toks = np.random.RandomState(0).randint(0, 96, (2, 513)).astype(np.int64)
    return main, startup, fetch["loss"], {
        "token_ids": toks[:, :-1, None], "labels": toks[:, 1:, None],
        "loss_mask": np.ones((2, 512, 1), np.float32)}


MODELS = {"bert": _bert, "gpt": _gpt, "phi4flash": _phi}


def _clean(text):
    # source positions move with every edit of a file; nothing else does
    return re.sub(r"/[^\s:\"']*\.py:\d+", "", text)


def executor_step_text(model):
    """The jaxpr of the step `Executor` jits for the tiny `model`."""
    with scope_guard(Scope()):
        main, startup, loss, batch = MODELS[model]()
        exe = pt.Executor()
        exe.run(startup)
        feed = exe._convert_feed(main, batch)
        scope = pt.global_scope()
        state_names, uses_rng = exe._prepare_state(main, feed, scope)
        step = exe._make_step(main, sorted(feed), [loss.name], state_names,
                              uses_rng)
        state = tuple(scope.find_var(n) for n in state_names)
        return _clean(str(jax.make_jaxpr(step)(
            state, tuple(feed[k] for k in sorted(feed)))))


def compiled_step_text(model, monkeypatch, strategy=None):
    """The jaxpr of what a dp2 `CompiledProgram` hands `jax.jit`, traced
    under its mesh. `strategy(bs)` may set further fields first."""
    handed = []
    jit = jax.jit

    def recording_jit(fn, *args, **kwargs):
        if "in_shardings" in kwargs and kwargs.get("donate_argnums") == (0,):
            handed.append((fn, kwargs["in_shardings"][0][0].mesh))
        return jit(fn, *args, **kwargs)

    with scope_guard(Scope()):
        main, startup, loss, batch = MODELS[model]()
        exe = pt.Executor()
        exe.run(startup)
        bs = compiler.BuildStrategy()
        bs.mesh_axes = {"dp": 2}
        if strategy is not None:
            strategy(bs)
        scope = pt.global_scope()
        feed = exe._convert_feed(main, batch)
        state_names, _ = exe._prepare_state(main, feed, scope)
        avals = (tuple(jax.ShapeDtypeStruct(np.shape(v), v.dtype)
                       for v in (scope.find_var(n) for n in state_names)),
                 tuple(jax.ShapeDtypeStruct(np.shape(feed[k]), feed[k].dtype)
                       for k in sorted(feed)))
        with monkeypatch.context() as patch:
            patch.setattr(jax, "jit", recording_jit)
            exe.run(compiler.CompiledProgram(main, bs), feed=batch,
                    fetch_list=[loss])
        (fn, mesh), = handed
        with mesh:
            return _clean(str(jax.make_jaxpr(fn)(*avals)))


def digest(text):
    return {"sha256": hashlib.sha256(text.encode()).hexdigest(),
            "chars": len(text)}



# ---------------------------------------------------------------------------
# the step is the parent commit's (PR 28, e0886ae), under both executors
# ---------------------------------------------------------------------------

# GPT's was re-recorded in PR 30: its attention calls are plain causal ones
# and differentiate through one `flash_bwd` where the parent ran
# `flash_bwd_dkv` + `flash_bwd_dq` (181,920 characters at the parent).
# Phi's was re-recorded in PR 49: its full and cross layers (4 query heads
# on 2 key heads) go through the fused kernel too, the window layer keeps
# the two (491,187 characters at the parent)
GPT_STEP = {"sha256": "19dfc12216236a754b9404fec054bee688a6f738e0d9618a2965c4cc4f"
                      "a5843c", "chars": 171662}
PHI_STEP = {"sha256": "bd863e314b3a6914ba2d1b0996eb9838279859100df48ca915c8737438"
                      "ab6621", "chars": 481776}
# BERT's is the parent's less one dead equation: test_fused_head_blocks.py,
# which holds the "bert-executor" case, says which
BERT_STEP = {"sha256": "8e83920bc2ca135b0fa0e7460e2a526e90f0c9dc46196c214b92bfa7b5"
                       "96c235", "chars": 166075}
STEPS = {"bert": BERT_STEP, "gpt": GPT_STEP, "phi4flash": PHI_STEP}


@pytest.mark.parametrize("model,path", [
    ("gpt", "executor"), ("phi4flash", "executor"),
    ("bert", "compiled_dp2"), ("gpt", "compiled_dp2"),
    ("phi4flash", "compiled_dp2")])
def test_step_lowers_as_the_parent_commit_did(model, path, monkeypatch):
    text = executor_step_text(model) if path == "executor" \
        else compiled_step_text(model, monkeypatch)
    assert digest(text) == STEPS[model]


def _set(name, value):
    return lambda bs: setattr(bs, name, value)


@pytest.mark.parametrize("door", [
    "PADDLE_TPU_ATTN_IMPL=xla", "PADDLE_TPU_FLASH_BLOCK_Q=128",
    "PADDLE_TPU_FLASH_BLOCK_K=128", "PADDLE_TPU_PALLAS_TUNE_CACHE=<file>",
    "use_pallas", "kernel_policy", "pallas_tune_cache"])
def test_nothing_outside_the_call_moves_the_step(door, monkeypatch,
                                                 tmp_path):
    """Each retired name, set: a tiny GPT step at T=512 is the step it is
    without. (The first three moved it at the parent commit.)"""
    tune = tmp_path / "tune.json"
    tune.write_text("{}")
    strategy = {"use_pallas": _set("use_pallas", frozenset({"adam"})),
                "kernel_policy": _set("kernel_policy", "pallas"),
                "pallas_tune_cache": _set("pallas_tune_cache", str(tune)),
                }.get(door)
    if strategy is not None:
        text = compiled_step_text("gpt", monkeypatch, strategy)
    else:
        name, value = door.split("=")
        monkeypatch.setenv(name, value.replace("<file>", str(tune)))
        text = executor_step_text("gpt")
    assert digest(text) == GPT_STEP


# ---------------------------------------------------------------------------
# both forms of the head op, through the registry, against the plain chain
# ---------------------------------------------------------------------------

def _head_grads(fused, weighted, bias, cast, feed):
    t, d, v = feed["hx"].shape[0], feed["hx"].shape[1], 96
    with scope_guard(Scope()):
        main, startup = pt.Program(), pt.Program()
        with pt.program_guard(main, startup):
            x = layers.data("hx", [d], dtype="float32")
            lbl = layers.data("hl", [1], dtype="int64")
            w = layers.data("hw", [1], dtype="float32")
            h = layers.fc(x, size=d, act="tanh",
                          param_attr=pt.ParamAttr(name="head_fc_w"),
                          bias_attr=pt.ParamAttr(name="head_fc_b"))
            emb = layers.create_parameter(
                [v, d], "float32", name="head_emb",
                default_initializer=pt.initializer.Normal(0.0, 0.3))
            b = layers.create_parameter(
                [v], "float32", name="head_bias",
                default_initializer=pt.initializer.Normal(0.0, 0.3)) \
                if bias else None
            if fused:
                loss = layers.fused_mlm_head_loss(
                    h, emb, lbl, bias=b, cast_bf16=cast,
                    token_weight=w if weighted else None)
                if not weighted:
                    loss = layers.reduce_sum(
                        layers.elementwise_mul(loss, w))
            else:
                logits = layers.matmul(h, emb, transpose_y=True)
                if bias:
                    logits = layers.elementwise_add(logits, b, axis=-1)
                loss = layers.reduce_sum(layers.elementwise_mul(
                    layers.softmax_with_cross_entropy(logits, lbl), w))
            pgs = pt.append_backward(loss)
        exe = pt.Executor()
        exe.run(startup)
        outs = exe.run(main, feed=feed,
                       fetch_list=[loss.name] + [g.name for _p, g in pgs])
        return dict(zip(["loss"] + [p.name for p, _g in pgs],
                        (np.asarray(o, np.float32) for o in outs)))


@pytest.mark.parametrize("cast", [False, True], ids=["float32", "cast_bf16"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
@pytest.mark.parametrize("form", ["per_token", "weighted"])
def test_head_forms_through_the_registry(form, bias, cast):
    """`Program` -> `append_backward` -> `Executor`: the loss and every
    parameter's gradient of the op, in the form `TokenWeight` selects,
    against matmul + softmax_with_cross_entropy in float32."""
    rng = np.random.RandomState(3)
    t, d = 64, 16
    feed = {"hx": rng.randn(t, d).astype(np.float32),
            "hl": rng.randint(0, 96, (t, 1)).astype(np.int64),
            "hw": (rng.rand(t, 1) / t).astype(np.float32)}
    want = _head_grads(False, False, bias, False, feed)
    got = _head_grads(True, form == "weighted", bias, cast, feed)
    assert sorted(got) == sorted(want) and len(want) == (5 if bias else 4)
    tol = 3e-2 if cast else 2e-5
    for name in want:
        scale = max(float(np.max(np.abs(want[name]))), 1e-6)
        np.testing.assert_allclose(got[name] / scale, want[name] / scale,
                                   atol=tol, err_msg=name)


# ---------------------------------------------------------------------------
# default_interpret
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case,want", [
    ("variable_0", False), ("variable_1", True),
    ("pinned_cpu_device", True), ("default_backend", False)])
def test_default_interpret(case, want, monkeypatch):
    """The variable wins; else interpret wherever the computation will not
    land on a TPU: a pinned default device before the process's backend."""
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    assert default_interpret() is True          # this process: the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if case.startswith("variable"):
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", case[-1])
        assert default_interpret() is want
    elif case == "pinned_cpu_device":
        with jax.default_device(jax.devices("cpu")[0]):
            assert default_interpret() is want
    else:
        assert default_interpret() is want


if __name__ == "__main__":
    out = {}
    for model in MODELS:
        out["%s-executor" % model] = digest(executor_step_text(model))
        with pytest.MonkeyPatch.context() as mp:
            out["%s-compiled_dp2" % model] = digest(
                compiled_step_text(model, mp))
    print(json.dumps(out, indent=1))
