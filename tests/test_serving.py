"""StableHLO serving artifact: export -> load -> predict parity
(reference capability: C++ PaddlePredictor, paddle_api.h:148)."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers


def _build_and_train(features=6, hidden=8, classes=3):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [features], dtype="float32")
        h = layers.fc(x, hidden, act="relu")
        y = layers.softmax(layers.fc(h, classes))
    exe = pt.Executor()
    exe.run(startup)
    return main, exe, y


def test_stablehlo_export_roundtrip_matches_predictor(tmp_path):
    """export -> load_serving_artifact -> run must match BOTH the live
    Executor and the in-process Predictor bit-for-bit-ish (VERDICT r4
    next #6 'done' criterion)."""
    main, exe, y = _build_and_train()
    xv = np.random.RandomState(0).rand(5, 6).astype(np.float32)
    ref, = exe.run(main, feed={"x": xv}, fetch_list=[y])

    pt.save_inference_model(str(tmp_path), ["x"], [y], exe,
                            main_program=main, format="stablehlo",
                            batch_sizes=(1, 8))
    # artifact files exist: serialized export + MLIR text per bucket
    sdir = os.path.join(str(tmp_path), "serving")
    meta = json.load(open(os.path.join(sdir, "meta.json")))
    assert meta["dynamic_batch"] is True
    for b in (1, 8):
        assert os.path.exists(os.path.join(sdir, "export_b%d.bin" % b))
        mlir = open(os.path.join(sdir, "module_b%d.mlir" % b)).read()
        assert "stablehlo" in mlir or "func.func" in mlir

    from paddle_tpu.serving import load_serving_artifact
    pred = load_serving_artifact(str(tmp_path))
    assert pred.get_input_names() == ["x"]
    out, = pred.run({"x": xv})          # batch 5 -> bucket 8, sliced back
    assert out.shape == (5, 3)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)

    # parity with the in-process Predictor path on the same artifact dir
    from paddle_tpu.inference import Config, create_predictor
    inproc = create_predictor(Config(str(tmp_path)))
    out2, = inproc.run({"x": xv})
    np.testing.assert_allclose(out, out2, rtol=1e-5, atol=1e-6)

    # batch larger than every exported bucket: named error
    with pytest.raises(ValueError, match="largest exported bucket"):
        pred.run({"x": np.zeros((9, 6), np.float32)})


def test_stablehlo_export_weights_are_frozen(tmp_path):
    """The artifact must bake the weights at export time: training the
    live model afterwards must NOT change the artifact's predictions."""
    from paddle_tpu import optimizer
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [4], dtype="float32")
        y = layers.fc(x, 2)
        test_prog = main.clone(for_test=True)
        lbl = layers.data("lbl", [2], dtype="float32")
        loss = layers.reduce_mean(layers.square_error_cost(y, lbl))
        optimizer.SGD(0.5).minimize(loss)
    exe = pt.Executor()
    exe.run(startup)
    xv = np.random.RandomState(1).rand(2, 4).astype(np.float32)

    pt.save_inference_model(str(tmp_path), ["x"], [y], exe,
                            main_program=test_prog, format="stablehlo",
                            batch_sizes=(2,))
    from paddle_tpu.serving import load_serving_artifact
    pred = load_serving_artifact(str(tmp_path))
    before, = pred.run({"x": xv})
    ref, = exe.run(test_prog, feed={"x": xv}, fetch_list=[y])
    np.testing.assert_allclose(before, ref, rtol=1e-5, atol=1e-6)

    for _ in range(3):
        exe.run(main, feed={"x": xv,
                            "lbl": np.ones((2, 2), np.float32)},
                fetch_list=[loss])
    after_live, = exe.run(test_prog, feed={"x": xv}, fetch_list=[y])
    assert not np.allclose(after_live, ref)     # live model moved
    again, = pred.run({"x": xv})
    np.testing.assert_allclose(again, before)   # artifact frozen


def test_stablehlo_export_batch_factor_feeds(tmp_path):
    """Feeds whose leading dim is a MULTIPLE of the batch (BERT's flat
    mask_pos = batch * max_preds) export and reload correctly when an
    example_feed teaches the factors."""
    from paddle_tpu.models import bert
    from paddle_tpu.framework.scope import Scope, scope_guard

    cfg = bert.BertConfig(vocab_size=128, hidden_size=32, num_layers=1,
                          num_heads=2, ff_size=64, max_position=32)
    batch, seq, preds = 4, 16, 4
    main, startup, feeds, fetch = bert.bert_pretrain_program(
        cfg, batch, seq, preds, optimizer_fn=None, is_test=True)
    with scope_guard(Scope()):
        exe = pt.Executor()
        exe.run(startup)
        feed = bert.synthetic_batch(cfg, batch, seq, preds)
        ref, = exe.run(main, feed=feed, fetch_list=[fetch["loss"]])
        pt.save_inference_model(str(tmp_path), list(feed.keys()),
                                [fetch["loss"]], exe, main_program=main,
                                format="stablehlo", batch_sizes=(batch,),
                                example_feed=feed)
    from paddle_tpu.serving import load_serving_artifact
    pred = load_serving_artifact(str(tmp_path))
    meta = pred._meta
    factors = dict(zip(meta["feed_var_names"], meta["feed_batch_factor"]))
    assert factors["mask_pos"] == preds         # batch*preds leading dim
    assert factors["src_ids"] == 1
    out, = pred.run({k: np.asarray(v) for k, v in feed.items()})
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# quantized serving artifacts (ISSUE 16 satellite, ROADMAP 3c): the
# EQuARX-grounded q8 block codec from the checkpoint/state-ship path
# reused for the serving export — weights ride BESIDE the .bin as
# block-quantized int8 and are dequantized once at load
# ---------------------------------------------------------------------------

def test_q8_export_shrinks_and_roundtrips(tmp_path):
    """weight_compress='q8': the .bin holds no baked weights (the
    artifact shrinks ~4x on weight-dominated exports), the predictor
    dequantizes at load, and predictions match the full-precision
    export within the codec's block-quantization tolerance. The model
    is weight-dominated (73 KiB of weights): at the other tests' 83
    weights the four arguments cost the .bin more than the constants
    they replace (2,984 -> 3,032 bytes), and nothing shrinks."""
    main, exe, y = _build_and_train(64, 256, 8)
    xv = np.random.RandomState(0).rand(5, 64).astype(np.float32)

    fp = str(tmp_path / "fp32")
    q8 = str(tmp_path / "q8")
    pt.save_inference_model(fp, ["x"], [y], exe, main_program=main,
                            format="stablehlo", batch_sizes=(8,))
    pt.save_inference_model(q8, ["x"], [y], exe, main_program=main,
                            format="stablehlo", batch_sizes=(8,),
                            weight_compress="q8")

    from paddle_tpu.serving import (SERVING_FORMAT_VERSION,
                                    WEIGHTS_Q8_FILE,
                                    load_serving_artifact)
    meta = json.load(open(os.path.join(q8, "serving", "meta.json")))
    assert meta["format_version"] == SERVING_FORMAT_VERSION == 3
    assert meta["weight_compress"] == "q8"
    assert sorted(meta["weight_names"])
    assert os.path.exists(os.path.join(q8, "serving", WEIGHTS_Q8_FILE))
    # the bins carry the computation only; the weights moved into the
    # int8 npz — the EXPORT pair proves the ship-bytes shrink
    bin_fp = os.path.getsize(os.path.join(fp, "serving",
                                          "export_b8.bin"))
    bin_q8 = os.path.getsize(os.path.join(q8, "serving",
                                          "export_b8.bin"))
    assert bin_q8 < bin_fp

    ref_pred = load_serving_artifact(fp)
    q8_pred = load_serving_artifact(q8)
    assert ref_pred.weight_compress is None
    assert q8_pred.weight_compress == "q8"
    ref, = ref_pred.run({"x": xv})
    out, = q8_pred.run({"x": xv})
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2)


def test_q8_artifact_wire_bytes_shrink(tmp_path):
    """The state-ship accounting a q8 replica reports: the artifact's
    (raw, wire) byte pair — what _load_predictor feeds the stateship
    counters — must SHRINK vs the full-precision export of the same
    model, not just be assumed to.  Uses a weight-dominated model:
    the codec only block-quantizes arrays past its block size, and
    the fixed MLIR/meta overhead must not mask the weight savings."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("x", [64], dtype="float32")
        h = layers.fc(x, 256, act="relu")
        y = layers.softmax(layers.fc(h, 8))
    exe = pt.Executor()
    exe.run(startup)
    fp = str(tmp_path / "fp32")
    q8 = str(tmp_path / "q8")
    pt.save_inference_model(fp, ["x"], [y], exe, main_program=main,
                            format="stablehlo", batch_sizes=(8,))
    pt.save_inference_model(q8, ["x"], [y], exe, main_program=main,
                            format="stablehlo", batch_sizes=(8,),
                            weight_compress="q8")
    from paddle_tpu.serving_fleet import _artifact_wire_bytes
    raw_fp, wire_fp = _artifact_wire_bytes(fp)
    raw_q8, wire_q8 = _artifact_wire_bytes(q8)
    assert raw_q8 < raw_fp
    assert wire_q8 < wire_fp


def test_q8_format_fences(tmp_path):
    """The lossy export is fenced both ways: an unknown compression
    scheme is refused at export AND at load (a v3 artifact from a
    newer codec must never be served as garbage), while a PLAIN
    export stays format_version 2 — old loaders keep working."""
    main, exe, y = _build_and_train()
    plain = str(tmp_path / "plain")
    pt.save_inference_model(plain, ["x"], [y], exe, main_program=main,
                            format="stablehlo", batch_sizes=(8,))
    meta = json.load(open(os.path.join(plain, "serving", "meta.json")))
    assert meta["format_version"] == 2
    assert "weight_compress" not in meta

    with pytest.raises(ValueError, match="weight_compress"):
        pt.save_inference_model(str(tmp_path / "bad"), ["x"], [y],
                                exe, main_program=main,
                                format="stablehlo", batch_sizes=(8,),
                                weight_compress="zstd")

    q8 = str(tmp_path / "q8")
    pt.save_inference_model(q8, ["x"], [y], exe, main_program=main,
                            format="stablehlo", batch_sizes=(8,),
                            weight_compress="q8")
    mpath = os.path.join(q8, "serving", "meta.json")
    meta = json.load(open(mpath))
    meta["weight_compress"] = "zstd9"
    with open(mpath, "w") as f:
        json.dump(meta, f)
    from paddle_tpu.serving import load_serving_artifact
    with pytest.raises(ValueError, match="weight_compress"):
        load_serving_artifact(q8)


def test_q8_artifact_still_verified_at_load(tmp_path, monkeypatch):
    """progcheck at load survives the codec: a q8 artifact shipping a
    CORRUPT program IR refuses to load exactly like a full-precision
    one — compression must not open a verification bypass."""
    main, exe, y = _build_and_train()
    q8 = str(tmp_path / "q8")
    pt.save_inference_model(q8, ["x"], [y], exe, main_program=main,
                            format="stablehlo", batch_sizes=(8,),
                            weight_compress="q8")
    model_path = os.path.join(q8, "__model__.json")
    assert os.path.exists(model_path)
    meta = json.load(open(model_path))
    # first op loses its type: the verifier's strict walk must refuse
    meta["program"]["blocks"][0]["ops"][0].pop("type", None)
    with open(model_path, "w") as f:
        json.dump(meta, f)
    from paddle_tpu.serving import load_serving_artifact
    with pytest.raises(ValueError):
        load_serving_artifact(q8)
