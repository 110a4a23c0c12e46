"""Pallas kernel library oracle batteries (interpret mode on CPU).

Every kernel is checked fwd+bwd against its pure-JAX reference — the
same oracle pattern as test_flash_attention — plus:
  * the no-materialization property of the fused MLM head (no
    [tokens, vocab] aval anywhere in the fwd or bwd jaxpr),
  * use_pallas dispatch through the op registry / CompiledProgram
    (loss-curve parity vs the XLA lowering, compile-cache-token
    regression: toggling use_pallas re-lowers),
  * autotune cache round-trip, tuned-config override and the
    XLA-fallback verdict routing, and the tools/autotune.py --dry-run
    CLI smoke (the sweep harness itself can never rot untested).
"""
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import layers, optimizer
from paddle_tpu.framework.compiler import BuildStrategy, CompiledProgram
from paddle_tpu.framework.scope import Scope, scope_guard
from paddle_tpu.ops import pallas_dispatch as pd
from paddle_tpu.ops.pallas.blockwise_ce import (
    blockwise_softmax_cross_entropy, fused_mlm_head_loss, fit_blocks)
from paddle_tpu.ops.pallas.fused_adam import fused_adam
from paddle_tpu.ops.pallas.layer_norm import fused_layer_norm
from paddle_tpu.ops.pallas import autotune as at

pytestmark = pytest.mark.pallas

ALL_OPS = frozenset(pd.PALLAS_OPS)


# ---------------------------------------------------------------------------
# blockwise cross-entropy
# ---------------------------------------------------------------------------

def _ce_ref(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 5e-2)])
def test_blockwise_ce_fwd_bwd_parity(rng, dtype, tol):
    t, v = 48, 320
    logits = jnp.asarray(rng.randn(t, v), dtype)
    labels = jnp.asarray(rng.randint(0, v, (t,)), jnp.int32)
    cot = jnp.asarray(rng.randn(t).astype(np.float32))

    loss = blockwise_softmax_cross_entropy(logits, labels, block_t=8,
                                           block_v=64)
    np.testing.assert_allclose(np.asarray(loss),
                               np.asarray(_ce_ref(logits, labels)),
                               atol=tol, rtol=tol)

    gp = jax.grad(lambda lg: jnp.sum(blockwise_softmax_cross_entropy(
        lg, labels, block_t=8, block_v=64) * cot))(logits)
    gx = jax.grad(lambda lg: jnp.sum(
        _ce_ref(lg, labels) * cot))(logits)
    assert gp.dtype == logits.dtype
    np.testing.assert_allclose(np.asarray(gp, np.float32),
                               np.asarray(gx, np.float32),
                               atol=tol, rtol=tol)


def test_blockwise_ce_untileable_returns_none(rng):
    # vocab < 8: no tile fits -> the caller's XLA fallback
    logits = jnp.asarray(rng.randn(16, 7).astype(np.float32))
    labels = jnp.zeros((16,), jnp.int32)
    assert blockwise_softmax_cross_entropy(logits, labels) is None
    assert fit_blocks(16, 7, 128, 512, True) is None
    assert fit_blocks(4, 64, 128, 512, True) is None
    # an odd axis still tiles as ONE block when >= 8 (interpret mode)
    assert fit_blocks(16, 31, 128, 512, True) == (16, 31)
    assert fit_blocks(16, 64, 128, 512, True) == (16, 64)
    # compiled Mosaic needs the 128-lane alignment
    assert fit_blocks(16, 64, 128, 512, False) is None


# ---------------------------------------------------------------------------
# fused MLM head
# ---------------------------------------------------------------------------

def _head_ref(h, w, b, labels):
    logits = h.astype(jnp.float32) @ w.astype(jnp.float32) + b[None, :]
    return _ce_ref(logits, labels)


def test_fused_head_fwd_bwd_parity(rng):
    t, d, v = 32, 64, 256
    h = jnp.asarray(rng.randn(t, d).astype(np.float32) * 0.3)
    w = jnp.asarray(rng.randn(d, v).astype(np.float32) * 0.2)
    b = jnp.asarray(rng.randn(v).astype(np.float32) * 0.1)
    labels = jnp.asarray(rng.randint(0, v, (t,)), jnp.int32)
    cot = jnp.asarray(rng.randn(t).astype(np.float32))

    loss = fused_mlm_head_loss(h, w, labels, bias=b, block_t=8,
                               block_v=64)
    np.testing.assert_allclose(np.asarray(loss),
                               np.asarray(_head_ref(h, w, b, labels)),
                               atol=1e-5, rtol=1e-5)

    gp = jax.grad(lambda *a: jnp.sum(fused_mlm_head_loss(
        a[0], a[1], labels, bias=a[2], block_t=8, block_v=64) * cot),
        argnums=(0, 1, 2))(h, w, b)
    gx = jax.grad(lambda *a: jnp.sum(_head_ref(*a, labels) * cot),
                  argnums=(0, 1, 2))(h, w, b)
    for a, c in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   atol=1e-5, rtol=1e-5)


def _collect_shapes(jaxpr, acc):
    for v in list(jaxpr.invars) + list(jaxpr.outvars) + \
            list(jaxpr.constvars):
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "shape"):
            acc.add(tuple(aval.shape))
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                acc.add(tuple(aval.shape))
        for p in eqn.params.values():
            _recurse_param(p, acc)


def _recurse_param(p, acc):
    if isinstance(p, (list, tuple)):
        for x in p:
            _recurse_param(x, acc)
    elif hasattr(p, "jaxpr"):          # ClosedJaxpr
        _collect_shapes(p.jaxpr, acc)
    elif hasattr(p, "eqns"):           # raw Jaxpr
        _collect_shapes(p, acc)


def test_fused_head_never_materializes_logits(rng):
    """The acceptance property: no (tokens, vocab) aval ANYWHERE in the
    fwd or bwd jaxpr of the fused head — the logits tensor does not
    exist. The un-fused reference is the positive control (its jaxpr
    does carry the (T, V) intermediate)."""
    t, d, v = 64, 32, 512          # (64, 512) identifies the logits
    h = jnp.asarray(rng.randn(t, d).astype(np.float32))
    w = jnp.asarray(rng.randn(d, v).astype(np.float32))
    b = jnp.asarray(rng.randn(v).astype(np.float32))
    labels = jnp.asarray(rng.randint(0, v, (t,)), jnp.int32)

    def pallas_loss(h, w, b):
        return jnp.sum(fused_mlm_head_loss(h, w, labels, bias=b,
                                           block_t=8, block_v=64))

    def ref_loss(h, w, b):
        return jnp.sum(_head_ref(h, w, b, labels))

    for fn in (pallas_loss,
               jax.grad(pallas_loss, argnums=(0, 1, 2))):
        shapes = set()
        _collect_shapes(jax.make_jaxpr(fn)(h, w, b).jaxpr, shapes)
        assert (t, v) not in shapes, \
            "fused head materialized a (%d, %d) logits buffer" % (t, v)
    control = set()
    _collect_shapes(jax.make_jaxpr(ref_loss)(h, w, b).jaxpr, control)
    assert (t, v) in control  # the detector actually detects


# ---------------------------------------------------------------------------
# fused adam
# ---------------------------------------------------------------------------

def _adam_ref(p, g, m1, m2, lr_t, b1=0.9, b2=0.999, eps=1e-8):
    gf = g.astype(jnp.float32)
    m1n = b1 * m1 + (1 - b1) * gf
    m2n = b2 * m2 + (1 - b2) * gf * gf
    pn = p.astype(jnp.float32) - lr_t * m1n / (jnp.sqrt(m2n) + eps)
    return pn.astype(p.dtype), m1n, m2n


@pytest.mark.parametrize("shape,dtype", [
    ((40, 64), jnp.float32),      # 2-D, divides evenly
    ((2100,), jnp.float32),       # ragged: exercises lane padding
    ((33, 65), jnp.bfloat16),     # bf16 param, f32 moments
])
def test_fused_adam_parity(rng, shape, dtype):
    p = jnp.asarray(rng.randn(*shape), dtype)
    g = jnp.asarray(rng.randn(*shape).astype(np.float32))
    m1 = jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.1)
    m2 = jnp.asarray(np.abs(rng.randn(*shape)).astype(np.float32) * 0.1)
    lr_t = jnp.float32(0.01)
    out = fused_adam(p, g, m1, m2, lr_t, block_rows=8)
    assert out is not None and out[0].dtype == p.dtype
    ref = _adam_ref(p, g, m1, m2, lr_t)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-6
    for a, c in zip(out, ref):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(c, np.float32), atol=tol)


def test_fused_adam_small_param_falls_back():
    z = jnp.zeros((64,), jnp.float32)
    assert fused_adam(z, z, z, z, jnp.float32(0.1)) is None


# ---------------------------------------------------------------------------
# fused layer norm
# ---------------------------------------------------------------------------

def _ln_ref(x, sc, bi, eps=1e-5):
    m = jnp.mean(x, -1, keepdims=True)
    v = jnp.var(x, -1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + eps) * sc[None, :] + bi[None, :]


def test_fused_layer_norm_fwd_bwd_parity(rng):
    r, c = 36, 96                  # ragged rows: exercises row padding
    x = jnp.asarray(rng.randn(r, c).astype(np.float32))
    sc = jnp.asarray(rng.randn(c).astype(np.float32))
    bi = jnp.asarray(rng.randn(c).astype(np.float32))
    cot = jnp.asarray(rng.randn(r, c).astype(np.float32))

    y = fused_layer_norm(x, sc, bi, block_rows=8)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(_ln_ref(x, sc, bi)),
                               atol=1e-5, rtol=1e-5)
    gp = jax.grad(lambda *a: jnp.sum(
        fused_layer_norm(*a, block_rows=8) * cot),
        argnums=(0, 1, 2))(x, sc, bi)
    gx = jax.grad(lambda *a: jnp.sum(_ln_ref(*a) * cot),
                  argnums=(0, 1, 2))(x, sc, bi)
    for a, c_ in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c_),
                                   atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# dispatch scope + registry wiring
# ---------------------------------------------------------------------------

def test_scope_enables_and_restores():
    assert pd.enabled("adam") is None
    cfg = pd.PallasConfig({"adam"})
    with pd.scope(cfg):
        assert pd.enabled("adam") is cfg
        assert pd.enabled("layer_norm") is None
        with pd.scope(pd.PallasConfig({"layer_norm"})):
            assert pd.enabled("adam") is None
            assert pd.enabled("layer_norm") is not None
        assert pd.enabled("adam") is cfg
    assert pd.enabled("adam") is None
    with pytest.raises(ValueError):
        pd.PallasConfig({"nonexistent_op"})


def test_registry_ce_wiring_parity(rng):
    """The softmax_with_cross_entropy op under the dispatch scope: same
    Softmax/Loss as the XLA lowering, incl. ignore_index; soft_label
    stays on the XLA path."""
    from paddle_tpu.ops.registry import get_op
    fn = get_op("softmax_with_cross_entropy").fn
    logits = jnp.asarray(rng.randn(16, 128).astype(np.float32))
    label = jnp.asarray(rng.randint(0, 128, (16, 1)).astype(np.int64))
    label = label.at[3, 0].set(-100)   # ignored token
    ins = {"Logits": [logits], "Label": [label]}
    base = fn(None, ins, {"ignore_index": -100})
    with pd.scope(pd.PallasConfig({"softmax_with_cross_entropy"})):
        pal = fn(None, ins, {"ignore_index": -100})
        soft = fn(None, {"Logits": [logits],
                         "Label": [jax.nn.softmax(logits)]},
                  {"soft_label": True})
    for slot in ("Softmax", "Loss"):
        np.testing.assert_allclose(np.asarray(pal[slot]),
                                   np.asarray(base[slot]), atol=1e-6)
    assert float(np.asarray(pal["Loss"])[3, 0]) == 0.0
    assert soft["Loss"].shape == (16, 1)


def test_registry_layer_norm_wiring_parity(rng):
    from paddle_tpu.ops.registry import get_op
    fn = get_op("layer_norm").fn
    x = jnp.asarray(rng.randn(4, 8, 32).astype(np.float32))
    sc = jnp.asarray(rng.randn(256).astype(np.float32))
    bi = jnp.asarray(rng.randn(256).astype(np.float32))
    ins = {"X": [x], "Scale": [sc], "Bias": [bi]}
    base = fn(None, ins, {"begin_norm_axis": 1})
    with pd.scope(pd.PallasConfig({"layer_norm"})):
        pal = fn(None, ins, {"begin_norm_axis": 1})
        # no Scale/Bias -> XLA path even under the scope
        plain = fn(None, {"X": [x]}, {"begin_norm_axis": 1})
    for slot in ("Y", "Mean", "Variance"):
        np.testing.assert_allclose(np.asarray(pal[slot]),
                                   np.asarray(base[slot]),
                                   atol=1e-5, rtol=1e-5)
        assert pal[slot].shape == base[slot].shape
    assert plain["Y"].shape == x.shape


def _build_train(classes=128):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [64], dtype="float32")
        y = layers.data("y", [1], dtype="int64")
        h = layers.fc(x, size=128, act="relu")
        h = layers.layer_norm(h)
        logits = layers.fc(h, size=classes)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
        optimizer.Adam(1e-3).minimize(loss)
    return main, startup, loss


def _run_train(use_pallas, feed, steps=3, tune_cache=None):
    with scope_guard(Scope()):
        main, startup, loss = _build_train()
        exe = pt.Executor()
        exe.run(startup)
        bs = BuildStrategy()
        bs.mesh_axes = {"dp": min(8, len(jax.devices()))}
        bs.use_pallas = use_pallas
        bs.pallas_tune_cache = tune_cache
        comp = CompiledProgram(main, bs)
        curve = [float(np.asarray(
            exe.run(comp, feed=feed, fetch_list=[loss])[0]).reshape(()))
            for _ in range(steps)]
    return curve


def _feed(rng, n=16):
    return {"x": rng.rand(n, 64).astype(np.float32),
            "y": rng.randint(0, 128, (n, 1)).astype(np.int64)}


def test_compiled_program_pallas_parity(rng):
    """All three kernels engaged through BuildStrategy.use_pallas on a
    dp mesh: the loss trajectory matches the XLA lowering."""
    feed = _feed(rng)
    base = _run_train(frozenset(), feed)
    pal = _run_train(ALL_OPS, feed)
    np.testing.assert_allclose(pal, base, rtol=1e-5, atol=1e-5)
    assert base[0] > base[-1]      # it actually trained


def test_use_pallas_in_compile_cache_token(rng):
    """Toggling use_pallas must re-lower (a stale executable would keep
    the old lowering); returning to a seen setting re-uses its entry."""
    feed = _feed(rng)
    with scope_guard(Scope()):
        main, startup, loss = _build_train()
        exe = pt.Executor()
        exe.run(startup)
        for ops in (frozenset(), frozenset({"adam"}), frozenset()):
            bs = BuildStrategy()
            bs.mesh_axes = {"dp": min(8, len(jax.devices()))}
            bs.use_pallas = ops
            exe.run(CompiledProgram(main, bs), feed=feed,
                    fetch_list=[loss])
        assert exe.cache_misses == 2
        assert exe.cache_hits == 1


# ---------------------------------------------------------------------------
# autotune: cache round-trip, tuned override, XLA-fallback routing
# ---------------------------------------------------------------------------

def test_autotune_cache_roundtrip(tmp_path):
    path = str(tmp_path / "tune.json")
    cache = at.AutotuneCache(path)
    key = pd.cache_key("adam", (4096,), "float32", {"dp": 8}, "cpu")
    entry = {"impl": "pallas", "config": {"block_rows": 64},
             "pallas_s": 0.001, "xla_s": 0.002}
    cache.put(key, entry)
    cache.save()
    fresh = at.AutotuneCache(path)
    assert fresh.lookup(key) == entry
    assert len(fresh) == 1
    assert fresh.lookup("missing|key") is None
    # corrupt file tolerated (treated empty, trace time never bricks)
    with open(path, "w") as f:
        f.write("{torn")
    assert at.AutotuneCache(path).lookup(key) is None


def test_autotune_cache_sees_resweep_of_same_file(tmp_path):
    """A live process holding an AutotuneCache must see a re-run of
    tools/autotune.py rewriting the same file (stat-based reload), and
    the executor compile token must change with the contents."""
    path = str(tmp_path / "tune.json")
    held = at.AutotuneCache(path)
    assert held.lookup("k") is None          # loads the missing file
    writer = at.AutotuneCache(path)
    writer.put("k", {"impl": "xla"})
    writer.save()
    assert held.lookup("k") == {"impl": "xla"}
    # unsaved local puts survive (no reload while dirty)
    held.put("local", {"impl": "pallas"})
    assert held.lookup("local") is not None

    bs = BuildStrategy()
    bs.mesh_axes = {"dp": 1}
    bs.use_pallas = frozenset({"adam"})
    bs.pallas_tune_cache = path
    comp = CompiledProgram(pt.Program(), bs)
    tok1 = comp._cache_token()
    writer.put("k2", {"impl": "xla"})
    writer.save()
    assert comp._cache_token() != tok1


def test_autotune_all_failed_interpret_sweep_never_says_xla(tmp_path):
    """Dry/interpret sweeps must not poison the cache with an
    unmeasured "xla" verdict: when every candidate fails to tile, the
    entry stays impl:"pallas" with no config (kernel defaults, whose
    own size guards still fall back dynamically)."""
    cache = at.AutotuneCache(str(tmp_path / "tune.json"))
    # 512 elements -> 4 lane rows < 8: every adam candidate raises
    summary = at.autotune_op("adam", (512,), probes=1, interpret=True,
                             cache=cache)
    assert all(r["status"] == "failed"
               for r in summary["results"].values())
    assert summary["entry"]["impl"] == "pallas"
    assert summary["entry"]["config"] is None


def test_choose_applies_tuned_config_and_xla_fallback(tmp_path):
    cache = at.AutotuneCache(str(tmp_path / "tune.json"))
    cfg = pd.PallasConfig({"adam", "layer_norm"}, tuning=cache,
                          mesh_axes={"dp": 8}, backend="cpu")
    cache.put(pd.cache_key("adam", (4096,), "float32", {"dp": 8}, "cpu"),
              {"impl": "pallas", "config": {"block_rows": 64}})
    cache.put(pd.cache_key("layer_norm", (32, 128), "float32", {"dp": 8},
                           "cpu"),
              {"impl": "xla"})
    assert pd.choose(cfg, "adam", (4096,), "float32") == \
        ("pallas", {"block_rows": 64})
    # the sweep said XLA wins here -> the wiring takes its XLA branch
    assert pd.choose(cfg, "layer_norm", (32, 128), "float32") == \
        ("xla", None)
    # unseen key / no cache -> pallas at defaults
    assert pd.choose(cfg, "adam", (8192,), "float32") == ("pallas", None)
    assert pd.choose(pd.PallasConfig({"adam"}), "adam", (4096,),
                     "float32") == ("pallas", None)


def test_xla_fallback_verdict_through_program(rng, tmp_path):
    """An impl:"xla" cache entry for the exact program shape routes the
    op back to XLA under use_pallas — and the run still matches."""
    cache = at.AutotuneCache(str(tmp_path / "tune.json"))
    n_dev = min(8, len(jax.devices()))
    # the train program's adam params are keyed on their FLATTENED size
    # (what the kernel tiles): route every size the program owns to xla
    for size in (64 * 128, 128, 128 * 128):
        cache.put(pd.cache_key("adam", (size,), "float32",
                               {"dp": n_dev}, "cpu"),
                  {"impl": "xla"})
    cache.save()
    feed = _feed(rng)
    base = _run_train(frozenset(), feed)
    routed = _run_train(frozenset({"adam"}), feed,
                        tune_cache=str(tmp_path / "tune.json"))
    np.testing.assert_allclose(routed, base, rtol=1e-6, atol=1e-6)


def test_autotune_op_dry_sweep_persists_winner(tmp_path):
    cache = at.AutotuneCache(str(tmp_path / "tune.json"))
    summary = at.autotune_op("layer_norm", (32, 128), probes=1,
                             interpret=True, cache=cache)
    entry = summary["entry"]
    assert entry["impl"] == "pallas"      # interpret sweeps never say xla
    assert entry["config"] in at.DRY_CANDIDATES["layer_norm"]
    assert os.path.exists(cache.path)
    fresh = at.AutotuneCache(cache.path)
    assert fresh.lookup(summary["key"])["config"] == entry["config"]
    assert all(r["status"] == "ok" and
               isinstance(r["measured_s"], float)
               for r in summary["results"].values())
    # the winner's per-candidate rows are banked for future model fits
    assert entry["results"] and all(
        isinstance(s, float) for s in entry["results"].values())


def test_tools_autotune_cli_dry_run(tmp_path, capsys):
    """tools/autotune.py --dry-run end-to-end in-process: the tier-1
    smoke that keeps the sweep harness itself from rotting."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "_autotune_cli", os.path.join(root, "tools", "autotune.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    cache = str(tmp_path / "dry.json")
    rc = mod.main(["--dry-run", "--ops", "adam,layer_norm",
                   "--cache", cache])
    assert rc == 0
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["metric"] == "pallas_autotune" and report["ok"]
    assert report["dry_run"] and report["entries"] == 2
    data = json.load(open(cache))
    # versioned envelope (tools/tunecheck.py's format contract)
    assert data["format_version"] == at.FORMAT_VERSION
    assert len(data["entries"]) == 2
    for entry in data["entries"].values():
        assert entry["impl"] == "pallas" and entry["interpret"]
    # bad op name is a usage error, not a crash
    with pytest.raises(SystemExit):
        mod.main(["--ops", "nope"])
    capsys.readouterr()


# ---------------------------------------------------------------------------
# fused_mlm_head_loss model-head wiring (PR 10 satellite: the registry op
# that bert/gpt heads now emit — ROADMAP item 2 remainder)
# ---------------------------------------------------------------------------

def _head_program(t=32, d=16, v=512):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("hx", [d], dtype="float32")
        lbl = layers.data("hl", [1], dtype="int64")
        h = layers.fc(x, size=d, act="tanh",
                      param_attr=pt.ParamAttr(name="head_fc_w"),
                      bias_attr=pt.ParamAttr(name="head_fc_b"))
        emb = layers.create_parameter([v, d], "float32", name="head_emb")
        bias = layers.create_parameter([v], "float32", name="head_bias")
        ce = layers.fused_mlm_head_loss(h, emb, lbl, bias=bias)
        loss = layers.mean(ce)
        optimizer.SGD(0.05).minimize(loss)
    return main, startup, loss


def test_fused_head_op_registry_wiring_parity(rng):
    """The fused_mlm_head_loss registry op trains identically with the
    Pallas lowering on (interpret) and off — and toggling use_pallas
    re-lowers (cache-token regression)."""
    t, d, v = 32, 16, 512
    xv = rng.rand(t, d).astype(np.float32)
    lv = rng.randint(0, v, (t, 1)).astype(np.int64)

    def run(use_pallas, steps=4):
        with scope_guard(Scope()):
            main, startup, loss = _head_program(t, d, v)
            exe = pt.Executor()
            exe.run(startup)
            bs = BuildStrategy()
            bs.mesh_axes = {"dp": 8}
            if use_pallas:
                bs.use_pallas = frozenset({"fused_mlm_head_loss"})
            os.environ["PADDLE_TPU_PALLAS_INTERPRET"] = "1"
            try:
                comp = CompiledProgram(main, bs)
                out = [float(exe.run(comp, feed={"hx": xv, "hl": lv},
                                     fetch_list=[loss])[0][0])
                       for _ in range(steps)]
            finally:
                os.environ.pop("PADDLE_TPU_PALLAS_INTERPRET", None)
            w = pt.global_scope().get_numpy("head_emb").copy()
        return out, w

    ref, w_ref = run(False)
    got, w_got = run(True)
    assert ref[-1] < ref[0]
    np.testing.assert_allclose(got, ref, rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(w_got, w_ref, rtol=1e-3, atol=1e-5)


def test_fused_head_op_never_materializes_logits_in_program_grad(rng):
    """Through the REGISTRY op (what the model heads emit), the Pallas
    route keeps the (T, V) logits out of the fwd+bwd jaxpr; the XLA
    fallback (positive control) materializes them."""
    from paddle_tpu.ops.registry import get_op
    # v = 4x the default block_v, so the kernel's (bt, bv) tile can
    # never be mistaken for the full (t, v) logits by the shape walk
    t, d, v = 32, 16, 2048
    h = jnp.asarray(rng.rand(t, d).astype(np.float32))
    w = jnp.asarray(rng.rand(v, d).astype(np.float32) * 0.1)
    b = jnp.zeros((v,), jnp.float32)
    lbl = jnp.asarray(rng.randint(0, v, (t, 1)).astype(np.int32))
    kern = get_op("fused_mlm_head_loss").fn

    class _Ctx(object):
        def rng(self):
            return jax.random.PRNGKey(0)

    def make_grad():
        # a FRESH function object per trace: jax caches traced jaxprs
        # by function identity, which would let the in-scope trace
        # leak into the control
        def loss_of(h, w, b):
            out = kern(_Ctx(), {"Hidden": [h], "Weight": [w],
                                "Bias": [b], "Label": [lbl]}, {})
            return jnp.sum(out["Loss"])
        return jax.grad(loss_of, argnums=(0, 1, 2))

    cfg = pd.PallasConfig({"fused_mlm_head_loss"}, interpret=True)
    shapes = set()
    with pd.scope(cfg):
        _collect_shapes(jax.make_jaxpr(make_grad())(h, w, b).jaxpr,
                        shapes)
    assert (t, v) not in shapes
    # migration seam: a pre-PR-10 config that enabled the blockwise CE
    # by its OLD op name still routes the (now fused) model heads
    # through Pallas
    legacy = pd.PallasConfig({"softmax_with_cross_entropy"},
                             interpret=True)
    shapes_legacy = set()
    with pd.scope(legacy):
        _collect_shapes(jax.make_jaxpr(make_grad())(h, w, b).jaxpr,
                        shapes_legacy)
    assert (t, v) not in shapes_legacy
    control = set()
    _collect_shapes(jax.make_jaxpr(make_grad())(h, w, b).jaxpr, control)
    assert (t, v) in control


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

def _pallas_call_sites():
    """(file, line, name or None) of every pl.pallas_call( in ops/pallas."""
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
                name = next((kw.value.value for kw in node.keywords
                             if kw.arg == "name"
                             and isinstance(kw.value, ast.Constant)), None)
                sites.append((os.path.basename(path), node.lineno, name))
    return sites


_SITES = _pallas_call_sites()


@pytest.mark.parametrize("site", _SITES,
                         ids=["%s:%d" % s[:2] for s in _SITES])
def test_every_pallas_call_has_a_stable_name(site):
    """The device trace tells the kernels apart by `name=` (it becomes the
    instruction's name and a component of its op_name), not by whatever
    JAX scope they were traced in."""
    fname, _line, name = site
    assert name and name.isidentifier(), site
    stem = fname[:-3]
    assert name.startswith({"flash_attention": "flash_",
                            "selective_scan": "ssm_scan_"}.get(stem, stem))


def test_pallas_call_names_are_distinct():
    names = [s[2] for s in _SITES]
    assert len(names) == 13
    assert len(set(names)) == len(names), names


def test_a_kernel_name_reaches_the_traced_step():
    x = jnp.ones((128, 128), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x: fused_layer_norm(
        x, jnp.ones((128,)), jnp.zeros((128,)), interpret=True))(x)
    assert "name=layer_norm_fwd" in str(jaxpr)
