"""The harness end to end at a tiny size on the CPU, through `run_cell`
with platform "cpu" (the test-only way in: `run.py` always asks for "tpu"
and has no fallback), and `correct` coming out false when the timed path is
broken underneath."""
import json
import math
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import cells, harness

REPO = cells.ROOT
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(tiny, name, trace=0, seed=2 ** 31 + 5, broken=None, lines=None):
    say = print if lines is None else lines.append
    return harness.run_cell(name, seed, 0.5, trace, platform="cpu",
                            root=tiny, say=say, broken=broken)


@pytest.mark.parametrize("name", ["tiny-bert.s8-b8", "tiny-gpt.t16-b4",
                                  "tiny-bert.s8-b8-dp4"])
def test_a_timed_run_reports_the_cells_end_to_end_metrics(tiny, name):
    lines = []
    out = run(tiny, name, lines=lines)
    assert set(out) == RESULT_KEYS
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] >= 3
    cell = cells.Cell(name, tiny)
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in cell.end_to_end:
        got = out["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and math.isfinite(got["value"])
    assert out["metrics"]["tokens_per_s_per_chip"]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert out["device"]["platform"] == "cpu"
    assert out["device"]["count"] == cell.chips
    json.dumps(out)
    text = "\n".join(lines)
    # every number compared is printed beside its limit
    for check in ("loss_gap", "grad_diff", "grad_norm_gap", "delta_norm_gap",
                  "window_losses_finite", "recompiles_in_window 0"):
        assert "check " + check in text
    assert "setup split:" in text and "device platform=cpu" in text


def test_a_traced_run_reports_per_layer_metrics_through_their_readers(tiny):
    out = run(tiny, "tiny-bert.s8-b8", trace=1)
    assert out["correct"] is True
    got = out["metrics"]
    # off the TPU the trace holds no device plane: those readers find
    # nothing and their metrics are left out, never faked
    assert set(got) == {"exec_dispatch_ms", "recompiles_in_window",
                        "state_gib"}
    assert got["recompiles_in_window"]["value"] == 0
    assert got["exec_dispatch_ms"]["value"] > 0
    assert got["state_gib"]["unit"] == "GiB" and got["state_gib"]["value"] > 0
    assert "busy_s" not in out["device"]


def test_the_same_seed_gives_the_same_inputs_and_another_seed_others(tiny):
    import numpy as np
    cell = cells.Cell("tiny-gpt.t16-b4", tiny)
    a, b = harness.make_pool(cell, 2 ** 31 + 9), harness.make_pool(
        cell, 2 ** 31 + 9)
    c = harness.make_pool(cell, 10)
    assert all(np.array_equal(x["token_ids"], y["token_ids"])
               for x, y in zip(a, b))
    assert not np.array_equal(a[0]["token_ids"], c[0]["token_ids"])
    assert not np.array_equal(a[0]["token_ids"], a[1]["token_ids"])
    rows = a[0]["token_ids"][:, :, 0]
    assert len({tuple(r) for r in rows}) == len(rows)   # rows all differ


def _state_unchanged(runner):
    """A step that returns its state unchanged: the loss comes back, the
    scope keeps what it held."""
    real = runner.step

    def step(batch):
        import jax.numpy as jnp
        held = {n: jnp.copy(runner.scope.find_var(n))
                for n in list(runner.scope.keys())
                if runner.scope.find_var(n) is not None}
        loss = real(batch)
        for n, v in held.items():
            runner.scope.set_var(n, v)
        return loss
    runner.step = step


def _half_batch(runner):
    """A step that leaves out half of the batch: the second half of the
    rows is fed as a copy of the first."""
    real = runner.step

    def step(batch):
        import numpy as np
        n = batch["token_ids"].shape[0]
        fed = {k: np.concatenate([v[:n // 2], v[:n // 2]])
               for k, v in batch.items()}
        return real(fed)
    runner.step = step


@pytest.mark.parametrize("broken,cell,failing", [
    (_state_unchanged, "tiny-bert.s8-b8", "delta_norm_gap"),
    (_state_unchanged, "tiny-gpt.t16-b4", "delta_norm_gap"),
    (_half_batch, "tiny-gpt.t16-b4", "grad_diff"),
])
def test_a_broken_timed_path_is_not_correct(tiny, broken, cell, failing):
    lines = []
    out = run(tiny, cell, broken=broken, lines=lines)
    assert out["correct"] is False
    failed = [ln for ln in lines if ln.startswith("check ")
              and "FAILED" in ln]
    assert any(failing in ln for ln in failed), lines


def test_a_non_finite_loss_in_the_window_is_not_correct(tiny):
    def poison(runner):
        real, calls = runner.step, []

        def step(batch):
            calls.append(1)
            loss = real(batch)
            return float("nan") if len(calls) == 8 else loss
        runner.step = step
    out = run(tiny, "tiny-bert.s8-b8", broken=poison)
    assert out["correct"] is False and out["failed"] == 1


def test_run_py_refuses_without_a_tpu_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "bert-base.s128-b256", "--seed", "1", "--seconds",
         "1", "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=REPO, timeout=300)
    assert p.returncode == 4
    assert "refused" in p.stderr and "tpu" in p.stderr
    assert '"correct"' not in p.stdout


def test_run_py_fails_where_only_the_benchmark_is(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths` there is no system to measure: non-zero, no result."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "bert-base.s128-b256", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=str(tmp_path), timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def test_too_few_chips_is_refused():
    with pytest.raises(harness.Refused):
        harness.attach("cpu", 4096)
    with pytest.raises(harness.Refused):
        harness.attach("tpu", 1)
