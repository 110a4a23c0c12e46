"""Which path a `kda_attention` call takes: the path rule (128-wide heads
on the TPU take the `kda_fwd` / `kda_bwd` kernels; K = 32, or any shape off
the TPU, the XLA form), with `kda.plan` saying which; and the tool's
`--kernels` mode walks through. The kernels' own oracles are in
`test_kda_kernels.py`."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.framework import obs
from paddle_tpu.ops import linear_attn_ops as la
from paddle_tpu.ops.pallas import delta_rule

from test_kda_kernels import SCALE, wide_inputs
from test_kda_op import inputs


@pytest.fixture
def recorded_plans():
    obs.enable()
    obs.clear()
    yield lambda: [s["labels"] for s in obs.spans(name="kda.plan")]
    obs.disable()
    obs.clear()


def test_the_path_is_decided_from_the_shapes_and_the_platform(
        monkeypatch, recorded_plans):
    # 128-wide heads: the kernels tile them; K = 32 or V = 48 do not
    assert delta_rule.plan((2, 8192, 16, 128), 128, 2)["heads_a_step"] == 8
    assert delta_rule.plan((2, 8192, 16, 32), 128, 2) is None
    assert delta_rule.plan((2, 8192, 16, 128), 48, 2) is None
    # off the TPU (this process): the XLA form whatever the shape
    monkeypatch.delenv("PADDLE_TPU_PALLAS_INTERPRET", raising=False)
    assert la.kernel_plan((2, 8192, 16, 128), 128, 2) is None
    args = wide_inputs(64, "typical", 2)
    out = la.kda_attention(*args)
    assert bool(jnp.all(out == la._kda(*args, SCALE)))     # bit for bit
    assert recorded_plans()[-1]["kernels"].startswith("xla: ")
    # as on the TPU (what the compile-only tests set): the kernels at
    # 128-wide heads, the XLA form at K = 32
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "0")
    found = la.kernel_plan((2, 8192, 16, 128), 128, 2)
    assert found["kernels"].startswith("pallas: kda_fwd, kda_bwd")
    assert la.kernel_plan((2, 8192, 16, 32), 32, 2) is None
    jax.eval_shape(la.kda_attention, *args)
    plan = recorded_plans()[-1]
    assert plan["kernels"].startswith("pallas: kda_fwd, kda_bwd")
    assert plan["chunk"] == 64 and plan["sub_block"] == 16
    assert plan["heads_a_step"] == 2 and plan["padded"] == 0
    assert 0 < plan["vmem_fwd"] < plan["vmem_bwd"] < 16 * 2 ** 20
    assert plan["heads"] == 2 and plan["levels"] == 6
    jax.eval_shape(la.kda_attention, *inputs(64, "typical"))
    assert recorded_plans()[-1]["kernels"].startswith("xla: ")


def test_the_microbenchmarks_kernels_mode_walks_through():
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "mb_kda_intra.py")
    done = subprocess.run(
        [sys.executable, tool, "--kernels", "--walk-through", "--seq", "64",
         "--batch", "1", "--heads", "2", "--calls", "1", "--runs", "1"],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]
    assert "kda_fwd, kda_bwd: 2 heads a step" in done.stdout
    assert "gap" in done.stdout
