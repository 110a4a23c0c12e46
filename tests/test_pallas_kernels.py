"""The Pallas kernels as the traced step sees them (interpret mode on CPU):
every `pallas_call` carries a name of the program's choosing and that name
reaches the jaxpr; the model heads emit the fused head op, and the op
trains as the matmul + softmax_with_cross_entropy chain it replaced does.
The kernels' own oracles are in test_flash_attention.py, test_flash_modes.py,
test_flash_fused_backward.py and test_selective_scan.py; which path a call
takes, in test_kernel_choice.py.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import layers, optimizer
from paddle_tpu.framework.compiler import BuildStrategy, CompiledProgram
from paddle_tpu.framework.scope import Scope, scope_guard

pytestmark = pytest.mark.pallas


# ---------------------------------------------------------------------------
# fused_mlm_head_loss model-head wiring: the registry op bert/gpt heads emit
# ---------------------------------------------------------------------------

def _head_program(t=32, d=16, v=512, fused=True):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("hx", [d], dtype="float32")
        lbl = layers.data("hl", [1], dtype="int64")
        h = layers.fc(x, size=d, act="tanh",
                      param_attr=pt.ParamAttr(name="head_fc_w"),
                      bias_attr=pt.ParamAttr(name="head_fc_b"))
        emb = layers.create_parameter([v, d], "float32", name="head_emb")
        bias = layers.create_parameter([v], "float32", name="head_bias")
        if fused:
            ce = layers.fused_mlm_head_loss(h, emb, lbl, bias=bias)
        else:
            logits = layers.elementwise_add(
                layers.matmul(h, emb, transpose_y=True), bias, axis=-1)
            ce = layers.softmax_with_cross_entropy(logits, lbl)
        loss = layers.mean(ce)
        optimizer.SGD(0.05).minimize(loss)
    return main, startup, loss


def test_fused_head_op_registry_wiring_parity(rng):
    """The fused_mlm_head_loss registry op trains as the plain chain it
    replaced (matmul + bias + softmax_with_cross_entropy) does, under a
    dp8 `CompiledProgram`."""
    t, d, v = 32, 16, 512
    xv = rng.rand(t, d).astype(np.float32)
    lv = rng.randint(0, v, (t, 1)).astype(np.int64)

    def run(fused, steps=4):
        with scope_guard(Scope()):
            main, startup, loss = _head_program(t, d, v, fused)
            exe = pt.Executor()
            exe.run(startup)
            bs = BuildStrategy()
            bs.mesh_axes = {"dp": 8}
            comp = CompiledProgram(main, bs)
            out = [float(exe.run(comp, feed={"hx": xv, "hl": lv},
                                 fetch_list=[loss])[0][0])
                   for _ in range(steps)]
            w = pt.global_scope().get_numpy("head_emb").copy()
        return out, w

    ref, w_ref = run(False)
    got, w_got = run(True)
    assert ref[-1] < ref[0]
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(w_got, w_ref, rtol=1e-3, atol=1e-5)


def test_bert_and_gpt_heads_emit_the_fused_op():
    """models/bert + models/gpt pretrain programs route their LM heads
    through fused_mlm_head_loss (the ROADMAP 'registry op still
    receives materialized logits' gap is closed at the MODEL level)."""
    from paddle_tpu.models import bert as bert_mod
    from paddle_tpu.models import gpt as gpt_mod
    cfg = bert_mod.BertConfig(vocab_size=128, hidden_size=16,
                              num_layers=1, num_heads=2, ff_size=32,
                              max_position=32)
    main, _, _, _ = bert_mod.bert_pretrain_program(cfg, 2, 8,
                                                   max_preds_per_seq=2)
    ops = [op.type for op in main.global_block().ops]
    assert "fused_mlm_head_loss" in ops
    gcfg = gpt_mod.GPTConfig(vocab_size=128, hidden_size=16,
                             num_layers=1, num_heads=2, ff_size=32,
                             max_position=32)
    gmain, _, _, _ = gpt_mod.gpt_pretrain_program(gcfg, 2, 8)
    gops = [op.type for op in gmain.global_block().ops]
    assert "fused_mlm_head_loss" in gops


# ---------------------------------------------------------------------------
# every kernel carries a name of the program's choosing
# ---------------------------------------------------------------------------

def _literal_names(value):
    """The names a `name=` argument can take: a string constant, or either
    arm of a conditional between two (one call site that serves two
    kernels); [None] for anything computed."""
    import ast
    if isinstance(value, ast.Constant):
        return [value.value]
    if isinstance(value, ast.IfExp):
        return _literal_names(value.body) + _literal_names(value.orelse)
    return [None]


def _pallas_call_sites():
    """(file, line, name or None) of every pl.pallas_call( in ops/pallas,
    one entry a name the site can give."""
    import ast
    import glob
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "paddle_tpu", "ops", "pallas")
    sites = []
    for path in sorted(glob.glob(os.path.join(root, "*.py"))):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and isinstance(
                    node.func, ast.Attribute) \
                    and node.func.attr == "pallas_call":
                names = next((_literal_names(kw.value)
                              for kw in node.keywords if kw.arg == "name"),
                             [None])
                sites.extend((os.path.basename(path), node.lineno, name)
                             for name in names)
    return sites


_SITES = _pallas_call_sites()


@pytest.mark.parametrize("site", _SITES,
                         ids=["%s:%d:%s" % s for s in _SITES])
def test_every_pallas_call_has_a_stable_name(site):
    """The device trace tells the kernels apart by `name=` (it becomes the
    instruction's name and a component of its op_name), not by whatever
    JAX scope they were traced in."""
    fname, _line, name = site
    assert name and name.isidentifier(), site
    stem = fname[:-3]
    assert name.startswith({"flash_attention": "flash_",
                            "selective_scan": "ssm_scan_",
                            "grouped_matmul": "moe_gmm_"}.get(stem, stem))


def test_pallas_call_names_are_distinct():
    names = [s[2] for s in _SITES]
    # three flash + the fused backward; two scan; grouped matmul's three
    assert len(names) == 9
    assert len(set(names)) == len(names), names


def test_a_kernel_name_reaches_the_traced_step():
    from paddle_tpu.ops.pallas import flash_attention as fa
    q = jnp.ones((1, 2, 64, 16), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q: fa.flash_attention(
        q, q, q, causal=True, interpret=True))(q)
    assert "name=flash_fwd" in str(jaxpr)
