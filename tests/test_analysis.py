"""Program IR verifier batteries (framework/analysis.py).

The adversarial corpus: >= 3 deliberately-broken programs PER PASS,
each pinning the exact diagnostic (pass name, op index, severity);
plus the wiring contract — strict raises with ALL violations listed,
warn logs + exports metrics, "off" is inert on the compile path — and
the strict-mode sweep over the model zoo programs.
"""
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers, optimizer
from paddle_tpu.distributed.pipeline_program import pp_stage_guard
from paddle_tpu.framework import analysis, resilience
from paddle_tpu.framework.analysis import (
    PASS_DEF_USE, PASS_SHAPE, PASS_SHARDING, PASS_PIPELINE, PASS_DCE,
    ProgramVerificationError)
from paddle_tpu.framework.compiler import BuildStrategy, CompiledProgram
from paddle_tpu.framework.scope import Scope, scope_guard

pytestmark = [pytest.mark.analysis]


def _diags(result, pass_name, severity=None):
    return [d for d in result
            if d.pass_name == pass_name
            and (severity is None or d.severity == severity)]


def _find(result, pass_name, severity, op_idx):
    hits = [d for d in _diags(result, pass_name, severity)
            if d.op_idx == op_idx]
    assert hits, "no %s/%s diagnostic at op %r in:\n%s" % (
        pass_name, severity, op_idx, result.summary())
    return hits[0]


# ---------------------------------------------------------------------------
# pass 1: def_use — dangling reads, def-before-use, section ordering
# ---------------------------------------------------------------------------

def test_def_use_dangling_undeclared_read():
    main = pt.Program()
    blk = main.global_block()
    blk.create_var(name="o", shape=[4], dtype="float32")
    blk.append_op("scale", inputs={"X": ["nope"]},
                  outputs={"Out": ["o"]}, attrs={"scale": 2.0})
    r = analysis.verify_program(main, feeds={})
    d = _find(r, PASS_DEF_USE, "error", 0)
    assert "nope" in d.vars and "dangling" in d.message


def test_def_use_read_never_produced_declared_var():
    main = pt.Program()
    blk = main.global_block()
    blk.create_var(name="ghost", shape=[4], dtype="float32")
    blk.create_var(name="o", shape=[4], dtype="float32")
    blk.append_op("scale", inputs={"X": ["ghost"]},
                  outputs={"Out": ["o"]}, attrs={"scale": 2.0})
    # feeds known and do not include `ghost` -> a certain trace failure
    r = analysis.verify_program(main, feeds={})
    d = _find(r, PASS_DEF_USE, "error", 0)
    assert "ghost" in d.vars
    # feed set unknown -> it MIGHT be fed: degraded to a warning
    r2 = analysis.verify_program(main)
    _find(r2, PASS_DEF_USE, "warning", 0)


def test_def_use_def_before_use():
    main = pt.Program()
    blk = main.global_block()
    for n in ("a", "b", "t"):
        blk.create_var(name=n, shape=[4], dtype="float32")
    blk.append_op("scale", inputs={"X": ["t"]},      # op 0 reads t
                  outputs={"Out": ["a"]}, attrs={"scale": 1.0})
    blk.append_op("scale", inputs={"X": ["a"]},      # op 1 produces t
                  outputs={"Out": ["t"]}, attrs={"scale": 1.0})
    r = analysis.verify_program(main, feeds={})
    d = _find(r, PASS_DEF_USE, "error", 0)
    assert "before its producer" in d.message and "t" in d.vars


def test_def_use_backward_after_optimize_ordering():
    main = pt.Program()
    blk = main.global_block()
    for n in ("x", "y", "z"):
        blk.create_var(name=n, shape=[4], dtype="float32")
    blk.append_op("scale", inputs={"X": ["x"]}, outputs={"Out": ["y"]},
                  attrs={"scale": 1.0, "op_role": "optimize"})
    blk.append_op("scale", inputs={"X": ["y"]}, outputs={"Out": ["z"]},
                  attrs={"scale": 1.0, "op_role": "backward"})
    r = analysis.verify_program(main, feeds={"x": (4,)})
    # info, not error: gradients()-after-minimize and two-optimizer
    # adversarial steps interleave sections ON PURPOSE (test_dcgan,
    # test_ops_extra) — the report locates it without refusing it
    d = _find(r, PASS_DEF_USE, "info", 1)
    assert "forward < backward < optimize" in d.message


# ---------------------------------------------------------------------------
# pass 2: shape_dtype — wrong-width matmul, reshape mismatch, dtype mix
# ---------------------------------------------------------------------------

def _two_var_program(shape_x, shape_y, dtype_x="float32",
                     dtype_y="float32"):
    main = pt.Program()
    blk = main.global_block()
    blk.create_var(name="x", shape=shape_x, dtype=dtype_x, is_data=True)
    blk.create_var(name="y", shape=shape_y, dtype=dtype_y, is_data=True)
    blk.create_var(name="o", shape=None, dtype=None)
    return main, blk


def test_shape_matmul_contraction_mismatch():
    main, blk = _two_var_program([4, 8], [7, 3])
    blk.append_op("matmul", inputs={"X": ["x"], "Y": ["y"]},
                  outputs={"Out": ["o"]})
    r = analysis.verify_program(main, feeds={"x": (4, 8), "y": (7, 3)})
    d = _find(r, PASS_SHAPE, "error", 0)
    assert "contraction width mismatch" in d.message


def test_shape_reshape_element_mismatch():
    main = pt.Program()
    blk = main.global_block()
    blk.create_var(name="x", shape=[4, 16], dtype="float32", is_data=True)
    blk.create_var(name="o", shape=None, dtype=None)
    blk.append_op("reshape2", inputs={"X": ["x"]},
                  outputs={"Out": ["o"]}, attrs={"shape": [4, 15]})
    r = analysis.verify_program(main, feeds={"x": (4, 16)})
    d = _find(r, PASS_SHAPE, "error", 0)
    assert "element count mismatch" in d.message


def test_shape_mixed_float_dtype_add():
    main, blk = _two_var_program([4, 8], [4, 8], "float32", "float16")
    blk.append_op("elementwise_add", inputs={"X": ["x"], "Y": ["y"]},
                  outputs={"Out": ["o"]})
    r = analysis.verify_program(main, feeds={"x": (4, 8), "y": (4, 8)})
    # warning, not error: AMP mixes bf16/f32 on purpose (weak
    # promotion); strict mode must keep compiling those programs
    d = _find(r, PASS_SHAPE, "warning", 0)
    assert "mixes float dtypes" in d.message


def test_shape_ce_label_misalignment_and_broadcast():
    # wrong-width head: label rows disagree with the logits rows
    main, blk = _two_var_program([16, 4], [8, 1], dtype_y="int64")
    blk.append_op("softmax_with_cross_entropy",
                  inputs={"Logits": ["x"], "Label": ["y"]},
                  outputs={"Softmax": ["s"], "Loss": ["o"]})
    blk.create_var(name="s", shape=None, dtype=None)
    r = analysis.verify_program(main, feeds={"x": (16, 4), "y": (8, 1)})
    assert _find(r, PASS_SHAPE, "error", 0)
    # non-broadcastable elementwise
    main2, blk2 = _two_var_program([4, 8], [4, 7])
    blk2.append_op("elementwise_mul", inputs={"X": ["x"], "Y": ["y"]},
                   outputs={"Out": ["o"]})
    r2 = analysis.verify_program(main2, feeds={"x": (4, 8),
                                               "y": (4, 7)})
    d = _find(r2, PASS_SHAPE, "error", 0)
    assert "not broadcastable" in d.message


def test_shape_unknown_op_never_false_positives():
    """An op without a shape rule infers top; downstream checks that
    would need its output shape are skipped."""
    main = pt.Program()
    blk = main.global_block()
    blk.create_var(name="x", shape=[4, 8], dtype="float32", is_data=True)
    for n in ("h", "o"):
        blk.create_var(name=n, shape=None, dtype=None)
    blk.append_op("definitely_not_an_op", inputs={"X": ["x"]},
                  outputs={"Out": ["h"]})
    blk.append_op("matmul", inputs={"X": ["h"], "Y": ["x"]},
                  outputs={"Out": ["o"]})
    r = analysis.verify_program(main, feeds={"x": (4, 8)},
                                passes=[PASS_SHAPE])
    assert not r.errors() and not r.warnings(), r.summary()


# ---------------------------------------------------------------------------
# pass 3: sharding feasibility
# ---------------------------------------------------------------------------

def _mesh_bs(**kw):
    bs = BuildStrategy(**kw)
    return bs


def test_sharding_quantize_needs_pure_dp():
    main = pt.Program()
    bs = _mesh_bs(quantize_collectives=True)
    bs.mesh_axes = {"dp": 2, "mp": 4}
    r = analysis.verify_program(main, build_strategy=bs)
    d = _diags(r, PASS_SHARDING, "error")
    assert d and "pure data-parallel" in d[0].message


def test_sharding_feed_batch_not_dp_divisible():
    main = pt.Program()
    blk = main.global_block()
    blk.create_var(name="x", shape=[-1, 8], dtype="float32",
                   is_data=True)
    bs = _mesh_bs()
    bs.mesh_axes = {"dp": 2}
    r = analysis.verify_program(main, feeds={"x": (7, 8)},
                                build_strategy=bs)
    d = _diags(r, PASS_SHARDING, "warning")
    assert d and "does not divide" in d[0].message and \
        d[0].vars == ("x",)


def test_sharding_mp_axis_divisibility_and_unknown_axis():
    main = pt.Program()
    blk = main.global_block()
    v = blk.create_var(name="w", shape=[5, 8], dtype="float32")
    v.sharding = ("mp", None)
    bs = _mesh_bs()
    bs.mesh_axes = {"dp": 2, "mp": 2}
    r = analysis.verify_program(main, build_strategy=bs)
    warn = _diags(r, PASS_SHARDING, "warning")
    assert warn and "stays replicated" in warn[0].message
    # axis absent from the mesh -> info, mirroring _var_sharding's drop
    v.sharding = ("tp9", None)
    r2 = analysis.verify_program(main, build_strategy=bs)
    info = _diags(r2, PASS_SHARDING, "info")
    assert info and "does not have" in info[0].message


# ---------------------------------------------------------------------------
# pass 4: pipeline feasibility (pre-extract diagnostics list)
# ---------------------------------------------------------------------------

def _pp_bs(n_stage=2, schedule="1f1b", m=1):
    bs = BuildStrategy(pp_stages=n_stage, pp_micro_batches=m,
                       pp_schedule=schedule)
    bs.mesh_axes = {"pp": n_stage, "dp": 1}
    return bs


def _stamped_program(n_stage=2, heterogeneous=False, stages=None):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("pp_x", [8, 16], "float32",
                        append_batch_size=False)
        h = x
        for i in range(n_stage):
            with pp_stage_guard(stages[i] if stages else i):
                h = layers.fc(h, size=16,
                              act="relu" if heterogeneous and i else
                              "tanh")
        y = layers.data("pp_y", [8, 16], "float32",
                        append_batch_size=False)
        loss = layers.reduce_mean(layers.square(h - y))
        optimizer.SGD(0.1).minimize(loss)
    return main, loss


def test_pipeline_unminimized_program():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("pp_x", [8, 16], "float32",
                        append_batch_size=False)
        h = x
        for i in range(2):
            with pp_stage_guard(i):
                h = layers.fc(h, size=16, act="tanh")
    r = analysis.verify_program(main, build_strategy=_pp_bs())
    d = _diags(r, PASS_PIPELINE, "error")
    assert d and "minimize" in d[0].message


def test_pipeline_non_contiguous_stamps():
    main, _ = _stamped_program(stages=[0, 2])
    r = analysis.verify_program(main, build_strategy=_pp_bs())
    d = _diags(r, PASS_PIPELINE, "error")
    assert d and "contiguous" in d[0].message


def test_pipeline_heterogeneous_stages():
    main, _ = _stamped_program(heterogeneous=True)
    r = analysis.verify_program(main, build_strategy=_pp_bs())
    d = _diags(r, PASS_PIPELINE, "error")
    assert d and any("structurally identical" in x.message for x in d)


def test_pipeline_stage_count_vs_strategy_and_mesh():
    main, _ = _stamped_program(n_stage=2)
    bs = _pp_bs(n_stage=4)
    r = analysis.verify_program(main, build_strategy=bs)
    msgs = [d.message for d in _diags(r, PASS_PIPELINE, "error")]
    assert any("stamped with 2" in m for m in msgs), msgs
    # mesh pp axis disagreeing with pp_stages
    bs2 = _pp_bs(n_stage=2)
    bs2.mesh_axes = {"pp": 4, "dp": 1}
    r2 = analysis.verify_program(main, build_strategy=bs2)
    msgs2 = [d.message for d in _diags(r2, PASS_PIPELINE, "error")]
    assert any("does not match" in m for m in msgs2), msgs2


def test_pipeline_bad_schedule_and_micro_divisibility():
    main, _ = _stamped_program()
    bs = _pp_bs(schedule="zigzag", m=3)
    r = analysis.verify_program(main, feeds={"pp_x": (8, 16),
                                             "pp_y": (8, 16)},
                                build_strategy=bs)
    msgs = [d.message for d in _diags(r, PASS_PIPELINE, "error")]
    assert any("pp_schedule" in m for m in msgs), msgs
    assert any("pp_micro_batches" in m for m in msgs), msgs


def test_pipeline_reports_all_violations_in_one_shot():
    """The tentpole contract: N independent pp violations surface as N
    diagnostics, not first-error-wins."""
    main, _ = _stamped_program(heterogeneous=True)
    bs = _pp_bs(schedule="zigzag", m=3)
    r = analysis.verify_program(main, feeds={"pp_x": (8, 16),
                                             "pp_y": (8, 16)},
                                build_strategy=bs)
    errs = _diags(r, PASS_PIPELINE, "error")
    assert len(errs) >= 3, r.summary()


# ---------------------------------------------------------------------------
# pass 5: dce — dead ops against fetch/update/collective roots
# ---------------------------------------------------------------------------

def _dead_op_program():
    main = pt.Program()
    blk = main.global_block()
    blk.create_var(name="x", shape=[4], dtype="float32", is_data=True)
    for n in ("live", "dead1", "dead2"):
        blk.create_var(name=n, shape=[4], dtype="float32")
    blk.append_op("scale", inputs={"X": ["x"]}, outputs={"Out": ["live"]},
                  attrs={"scale": 2.0})                       # op 0
    blk.append_op("scale", inputs={"X": ["x"]},
                  outputs={"Out": ["dead1"]}, attrs={"scale": 3.0})  # op 1
    blk.append_op("scale", inputs={"X": ["dead1"]},
                  outputs={"Out": ["dead2"]}, attrs={"scale": 4.0})  # op 2
    return main


def test_dce_flags_dead_chain():
    r = analysis.verify_program(_dead_op_program(), feeds={"x": (4,)},
                                fetch_list=["live"])
    assert _find(r, PASS_DCE, "info", 1)
    assert _find(r, PASS_DCE, "info", 2)
    assert len(_diags(r, PASS_DCE)) == 2


def test_dce_needs_fetch_roots():
    # without fetch roots any leaf could be the fetch: no report
    r = analysis.verify_program(_dead_op_program(), feeds={"x": (4,)})
    assert not _diags(r, PASS_DCE)


def test_dce_persistable_and_collective_roots_stay_live():
    main = pt.Program()
    blk = main.global_block()
    blk.create_var(name="x", shape=[4], dtype="float32", is_data=True)
    blk.create_var(name="w", shape=[4], dtype="float32",
                   persistable=True)
    blk.create_var(name="g", shape=[4], dtype="float32")
    blk.create_var(name="out", shape=[4], dtype="float32")
    # op 0: collective — live root even though `g` is never read
    blk.append_op("c_allreduce_sum", inputs={"X": ["x"]},
                  outputs={"Out": ["g"]})
    # op 1: persistable update — live root
    blk.append_op("scale", inputs={"X": ["x"]}, outputs={"Out": ["w"]},
                  attrs={"scale": 0.9})
    # op 2: genuinely dead
    blk.append_op("scale", inputs={"X": ["x"]}, outputs={"Out": ["out"]},
                  attrs={"scale": 1.0})
    r = analysis.verify_program(main, feeds={"x": (4,)}, fetch_list=[])
    dead = _diags(r, PASS_DCE)
    assert [d.op_idx for d in dead] == [2], r.summary()


# ---------------------------------------------------------------------------
# wiring: strict / warn / off on the compile path
# ---------------------------------------------------------------------------

def _train_program():
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [8], dtype="float32")
        y = layers.data("y", [1], dtype="int64")
        h = layers.fc(x, size=16, act="relu")
        logits = layers.fc(h, size=4)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, y))
        optimizer.SGD(0.1).minimize(loss)
    return main, startup, loss, logits


def _feed(batch=16):
    rng = np.random.RandomState(0)
    return {"x": rng.rand(batch, 8).astype(np.float32),
            "y": rng.randint(0, 4, (batch, 1)).astype(np.int64)}


def test_strict_mode_raises_with_all_violations():
    main = pt.Program()
    blk = main.global_block()
    blk.create_var(name="x", shape=[4, 8], dtype="float32", is_data=True)
    blk.create_var(name="y", shape=[3, 9], dtype="float32", is_data=True)
    for n in ("a", "b"):
        blk.create_var(name=n, shape=None, dtype=None)
    # two INDEPENDENT shape errors — both must be in the exception
    blk.append_op("matmul", inputs={"X": ["x"], "Y": ["y"]},
                  outputs={"Out": ["a"]})
    blk.append_op("reshape2", inputs={"X": ["x"]},
                  outputs={"Out": ["b"]}, attrs={"shape": [5, 5]})
    result = analysis.verify_program(main, feeds={"x": (4, 8),
                                                  "y": (3, 9)})
    assert len(result.errors()) == 2
    with pytest.raises(ProgramVerificationError) as ei:
        raise ProgramVerificationError(result)
    msg = str(ei.value)
    assert "contraction width" in msg and "element count" in msg


def test_compile_seam_strict_catches_malformed_program():
    """The executor's compile seam (not a direct verify call) fails a
    malformed program with located diagnostics under strict mode."""
    main = pt.Program()
    blk = main.global_block()
    blk.create_var(name="x", shape=[-1, 8], dtype="float32",
                   is_data=True)
    blk.create_var(name="o", shape=None, dtype=None)
    blk.append_op("matmul", inputs={"X": ["x"], "Y": ["missing_w"]},
                  outputs={"Out": ["o"]})
    exe = pt.Executor()
    assert os.environ.get("PADDLE_TPU_VERIFY") == "strict"
    with pytest.raises(ProgramVerificationError, match="missing_w"):
        exe.run(main, feed={"x": np.zeros((4, 8), np.float32)},
                fetch_list=["o"])


def test_off_mode_is_inert_on_the_compile_path(monkeypatch):
    """verify_program='off' must never even CALL the verifier."""
    main, startup, loss, _ = _train_program()

    def _boom(*a, **kw):
        raise AssertionError("verifier ran in off mode")

    with scope_guard(Scope()):
        exe = pt.Executor()
        exe.run(startup)
        monkeypatch.setattr(analysis, "verify_program", _boom)
        monkeypatch.setenv("PADDLE_TPU_VERIFY", "off")
        out = exe.run(main, feed=_feed(), fetch_list=[loss])
        assert np.isfinite(np.asarray(out[0])).all()
        # CompiledProgram route honors the strategy knob the same way
        bs = BuildStrategy(verify_program="off")
        comp = CompiledProgram(main, bs).with_data_parallel(
            loss_name=loss.name)
        out2 = exe.run(comp, feed=_feed(), fetch_list=[loss])
        assert np.isfinite(np.asarray(out2[0])).all()


def test_warn_mode_logs_and_counts_but_does_not_raise(monkeypatch):
    resilience.clear_events()
    main = pt.Program()
    blk = main.global_block()
    blk.create_var(name="x", shape=[4, 16], dtype="float32",
                   is_data=True)
    blk.create_var(name="o", shape=None, dtype=None)
    blk.append_op("reshape2", inputs={"X": ["x"]},
                  outputs={"Out": ["o"]}, attrs={"shape": [4, 15]})
    from paddle_tpu.framework.compiler import verify_for_compile
    bs = BuildStrategy(verify_program="warn")
    result = verify_for_compile(main, bs, feeds={"x": (4, 16)},
                                fetch_names=["o"])
    assert result is not None and result.errors()
    totals = resilience.analysis_totals()
    assert totals.get((PASS_SHAPE, "error"), 0) >= 1
    evs = resilience.events("program_analysis")
    assert evs and evs[-1]["errors"] >= 1
    # ... and the counter rides the metrics exposition
    m = resilience.metrics()
    names = {(c["name"], tuple(sorted(c["labels"].items())))
             for c in m["counters"]}
    assert any("analysis_diagnostics_total" in n for n, _ in names)


def test_verify_memo_one_walk_per_program_version(monkeypatch):
    main, startup, loss, _ = _train_program()
    calls = []
    real = analysis.verify_program

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(analysis, "verify_program", counting)
    with scope_guard(Scope()):
        exe = pt.Executor()
        exe.run(startup)
        for _ in range(4):
            exe.run(main, feed=_feed(), fetch_list=[loss])
    assert len(calls) == 1, "verifier must be memoized per version"


def test_allowlist_suppresses_a_pass():
    main = _dead_op_program()
    r = analysis.verify_program(main, feeds={"x": (4,)},
                                fetch_list=["live"])
    assert _diags(r, PASS_DCE)
    analysis.allowlist(main, PASS_DCE, reason="corpus: intentional")
    r2 = analysis.verify_program(main, feeds={"x": (4,)},
                                 fetch_list=["live"])
    assert not _diags(r2, PASS_DCE)


def test_verify_memo_is_per_strategy_not_just_per_program():
    """REGRESSION: two strategies sharing one Program must not share a
    memoized verdict — a clean verify under bs1 must not mask a
    quantize-on-mp error under bs2."""
    from paddle_tpu.framework.compiler import verify_for_compile
    main = pt.Program()
    bs1 = BuildStrategy(verify_program="strict")
    bs1.mesh_axes = {"dp": 2, "mp": 4}
    r1 = verify_for_compile(main, bs1)
    assert r1 is not None and not r1.errors()
    bs2 = BuildStrategy(verify_program="strict",
                        quantize_collectives=True)
    bs2.mesh_axes = {"dp": 2, "mp": 4}
    with pytest.raises(ProgramVerificationError,
                       match="pure data-parallel"):
        verify_for_compile(main, bs2)


def test_verify_cache_evicts_stale_versions():
    """REGRESSION: a mutate-run loop must not accumulate one verdict
    per historical program version."""
    from paddle_tpu.framework.compiler import verify_for_compile
    main = pt.Program()
    blk = main.global_block()
    blk.create_var(name="x", shape=[4], dtype="float32", is_data=True)
    bs = BuildStrategy(verify_program="strict")
    for i in range(5):
        blk.create_var(name="o%d" % i, shape=[4], dtype="float32")
        blk.append_op("scale", inputs={"X": ["x"]},
                      outputs={"Out": ["o%d" % i]}, attrs={"scale": 1.0})
        verify_for_compile(main, bs, feeds={"x": (4,)},
                           fetch_names=["o%d" % i])
    versions = {k[0] for k in main._verify_cache}
    assert versions == {main._version}, versions


def test_allowlist_survives_clone_and_prune():
    """REGRESSION: clone(for_test=True) / _prune keep the vetted
    exemptions — an eval program must not re-flag (or strict-fail) a
    diagnostic the train program already allowlisted."""
    main = _dead_op_program()
    analysis.allowlist(main, PASS_DCE, reason="test: vetted dead ops")
    for derived in (main.clone(), main.clone(for_test=True),
                    main._prune(["x"], ["live"])):
        r = analysis.verify_program(derived, feeds={"x": (4,)},
                                    fetch_list=["live"])
        assert not _diags(r, PASS_DCE), r.summary()


def test_pp_run_seam_checks_micro_divisibility():
    """REGRESSION: the REAL pp execution route (exe.run on a pp
    CompiledProgram) verifies with the actual feed shapes, so a batch
    not divisible by pp_micro_batches is a located diagnostic, not a
    mid-lowering error."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("pp_x", [6, 16], "float32",
                        append_batch_size=False)
        h = x
        for i in range(2):
            with pp_stage_guard(i):
                h = layers.fc(h, size=16, act="tanh")
        y = layers.data("pp_y", [6, 16], "float32",
                        append_batch_size=False)
        loss = layers.reduce_mean(layers.square(h - y))
        optimizer.SGD(0.1).minimize(loss)
    bs = BuildStrategy(pp_stages=2, pp_micro_batches=4,
                       verify_program="strict")
    bs.mesh_axes = {"pp": 2, "dp": 1}
    with scope_guard(Scope()):
        exe = pt.Executor()
        exe.run(startup)
        comp = CompiledProgram(main, bs)
        feed = {"pp_x": np.zeros((6, 16), np.float32),
                "pp_y": np.zeros((6, 16), np.float32)}
        with pytest.raises(ProgramVerificationError,
                           match="pp_micro_batches"):
            exe.run(comp, feed=feed, fetch_list=[loss])


def test_shape_squared_l2_norm_is_rank0():
    """The rule mirrors the kernel's reshape(()) — rank 0, not (1,)."""
    from paddle_tpu.ops.registry import get_shape_rule
    from paddle_tpu.ops.shape_rules import TensorMeta

    class _Op(object):
        type = "squared_l2_norm"
    out = get_shape_rule("squared_l2_norm")(
        _Op(), {"X": [TensorMeta((4, 8), "float32")]}, {})
    assert out["Out"][0].shape == ()


def test_allowlist_applied_after_first_compile_takes_effect():
    """REGRESSION: the compile seam memoizes verdicts per program
    version — an allowlist applied AFTER a strict failure must
    invalidate the memo, not wait for an unrelated version bump."""
    from paddle_tpu.framework.compiler import verify_for_compile
    main = pt.Program()
    blk = main.global_block()
    blk.create_var(name="x", shape=[4, 16], dtype="float32",
                   is_data=True)
    blk.create_var(name="o", shape=None, dtype=None)
    blk.append_op("reshape2", inputs={"X": ["x"]},
                  outputs={"Out": ["o"]}, attrs={"shape": [4, 15]})
    bs = BuildStrategy(verify_program="strict")
    with pytest.raises(ProgramVerificationError):
        verify_for_compile(main, bs, feeds={"x": (4, 16)},
                           fetch_names=["o"])
    analysis.allowlist(main, PASS_SHAPE,
                       reason="test: vetted reshape")
    r = verify_for_compile(main, bs, feeds={"x": (4, 16)},
                           fetch_names=["o"])
    assert r is not None and not r.errors()


# ---------------------------------------------------------------------------
# strict sweep over the model zoo programs
# ---------------------------------------------------------------------------

def _zoo_program(name):
    from paddle_tpu.models import bert, gpt, simple
    if name == "bert":
        cfg = bert.BertConfig(vocab_size=128, hidden_size=32, num_layers=2,
                              num_heads=2, ff_size=64, max_position=64)
        return bert.bert_pretrain_program(cfg, batch_size=4, seq_len=16,
                                          max_preds_per_seq=4)
    if name == "bert_base":     # 12 layers: the walk scales with the ops
        return bert.bert_pretrain_program(bert.bert_base(), batch_size=8,
                                          seq_len=128)
    if name == "gpt":
        cfg = gpt.GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                            num_heads=2, max_position=64)
        return gpt.gpt_pretrain_program(cfg, batch_size=4, seq_len=16)
    return simple.mlp_classifier_program(input_dim=16, hidden=(8,),
                                         classes=4)


@pytest.mark.parametrize("name", ["bert", "bert_base", "gpt", "mlp"])
def test_models_verify_clean_in_strict_mode(name):
    """Representative model-zoo programs verify with ZERO errors —
    the no-false-positive acceptance bar (the rest of the zoo rides
    the compile seam across the whole strict-mode suite)."""
    prog, _startup, feeds, fetch = _zoo_program(name)
    feed_names = [getattr(f, "name", f) for f in (
        feeds.values() if isinstance(feeds, dict) else feeds)]
    fetch_list = list(fetch.values()) if isinstance(fetch, dict) \
        else list(fetch)
    r = analysis.verify_program(prog, feeds=feed_names,
                                fetch_list=fetch_list)
    assert not r.errors(), "%s: %s" % (name, r.summary())


# ---------------------------------------------------------------------------
# progcheck CLI
# ---------------------------------------------------------------------------

def _tools():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    if path not in sys.path:
        sys.path.insert(0, path)


def test_progcheck_green_on_exported_model(tmp_path):
    _tools()
    import progcheck
    from paddle_tpu import io
    main, startup, _loss, logits = _train_program()
    with scope_guard(Scope()):
        exe = pt.Executor()
        exe.run(startup)
        io.save_inference_model(str(tmp_path), ["x"], [logits], exe,
                                main_program=main)
    assert progcheck.main([str(tmp_path)]) == 0
    # corrupt the exported IR: point an op input at a renamed var
    model = tmp_path / "__model__.json"
    meta = json.loads(model.read_text())
    prog = meta["program"]
    patched = False
    for op in prog["blocks"][0]["ops"]:
        for slot, names in op["inputs"].items():
            if "x" in names:
                op["inputs"][slot] = ["x_renamed_by_corruption"
                                      if n == "x" else n for n in names]
                patched = True
                break
        if patched:
            break
    assert patched
    model.write_text(json.dumps(meta))
    assert progcheck.main([str(tmp_path)]) == 2    # exit = max severity
    # unreadable envelope is as fatal
    bad = tmp_path / "garbage.json"
    bad.write_text("{not json")
    assert progcheck.main([str(bad)]) == 2


def test_progcheck_json_output(tmp_path, capsys):
    _tools()
    import progcheck
    main = _dead_op_program()
    p = tmp_path / "prog.json"
    p.write_text(main.to_json())
    rc = progcheck.main([str(p), "--fetch", "live", "--json"])
    assert rc == 0     # dead ops are info-severity: clean exit
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["metric"] == "progcheck"
    assert out["programs"][0]["counts"]["info"] == 2


# ---------------------------------------------------------------------------
# serving-artifact verification at predictor load
# ---------------------------------------------------------------------------

def test_serving_predictor_refuses_corrupt_artifact(tmp_path):
    from paddle_tpu import io
    from paddle_tpu.serving import ServingPredictor
    main, startup, _loss, logits = _train_program()
    with scope_guard(Scope()):
        exe = pt.Executor()
        exe.run(startup)
        io.save_inference_model(str(tmp_path), ["x"], [logits], exe,
                                main_program=main, format="stablehlo",
                                batch_sizes=(2,))
        pred = ServingPredictor(str(tmp_path))       # clean: loads
        assert pred.get_input_names() == ["x"]
        # corrupt the shipped IR
        model = tmp_path / "__model__.json"
        meta = json.loads(model.read_text())
        ops = meta["program"]["blocks"][0]["ops"]
        ops[0]["inputs"] = {k: ["gone_var"] for k in ops[0]["inputs"]}
        model.write_text(json.dumps(meta))
        with pytest.raises(ValueError, match="program verification"):
            ServingPredictor(str(tmp_path))
