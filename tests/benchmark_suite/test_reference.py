"""Each family's plain reference against the program at a tiny size on
seeded weights, and the control: the reference computed in float8 in the
program's place has to fail a limit that the program (bfloat16) passes.

Readings at these sizes on the CPU (six seeds for the program, three for
the control; printed by the test with -s): `grad_diff`, the worst leaf's
norm of (first gradient minus the reference's), reads 0.008-0.013 for the
program and 0.067-0.133 for the control, so the tiny cells' limit is 0.03.
The norms' own gaps do not tell the two apart (program <= 0.007, control >=
0.013 here, and they overlap at BERT-base's size on the chip): their limits
only have to catch a gross fault. The parameter-change gap reads ~0.1 for the
program (bfloat16 weights with no float32 master copy round an Adam step of
1e-4) and has to catch a step that leaves the state unchanged (gap 1.0).
"""
import numpy as np
import pytest

import tiny_root
from benchmark import cells, harness, read_limits, reference

CELLS = ["tiny-bert.s8-b8", "tiny-gpt.t16-b4"]
SEEDS = [11, 2 ** 31 + 12, 13, 14, 15, 16]
CONTROL_SEEDS = [11, 2 ** 31 + 12, 13]


@pytest.fixture(scope="module")
def readings(tiny):
    return {name: read_limits.read(name, SEEDS, CONTROL_SEEDS,
                                   platform="cpu", root=tiny, bfloat16=True)
            for name in CELLS}


@pytest.mark.parametrize("name", CELLS)
def test_program_agrees_with_the_reference_on_every_seed(readings, name):
    limits = tiny_root.LIMITS
    for seed, gaps in readings[name]["program"].items():
        for key in harness.GAPS:
            assert gaps[key] <= limits[key], (seed, key, gaps[key])


@pytest.mark.parametrize("name", CELLS)
def test_the_float8_control_fails_on_every_seed(readings, name):
    limits = tiny_root.LIMITS
    sound = max(g["grad_diff"] for g in readings[name]["program"].values())
    for seed, gaps in readings[name]["control_float8"].items():
        # the control's smallest is over three times the sound runs'
        # largest, and the limit lies between them with room on both sides
        assert gaps["grad_diff"] > 3 * sound, (seed, gaps, sound)
        assert gaps["grad_diff"] > 2 * limits["grad_diff"]
        assert limits["grad_diff"] > 2 * sound


@pytest.mark.parametrize("name", CELLS)
def test_bfloat16_reference_reads_what_the_program_reads(readings, name):
    """The program computes in bfloat16; the reference rounded to bfloat16
    should land in the same decade as the program, far below float8."""
    bf16 = max(g["grad_diff"] for g in readings[name]["bfloat16"].values())
    fp8 = min(g["grad_diff"]
              for g in readings[name]["control_float8"].values())
    assert bf16 < tiny_root.LIMITS["grad_diff"] < fp8


@pytest.mark.parametrize("name", CELLS)
def test_float32_program_matches_the_reference_closely(tiny, name):
    """With the program itself in float32 the two are the same mathematics:
    the gaps fall to float32 rounding."""
    cell = cells.Cell(name, tiny)
    cell.config = dict(cell.config, precision="float32")
    devices, _ = harness.attach("cpu", cell.chips)
    runner = harness.Runner(cell, devices)
    try:
        pool = harness.make_pool(cell, 5)
        runner.reset(5)
        got = runner.check_steps(5, pool)
        ref = harness.reference_numbers(
            cell, runner, 5, pool,
            compare_with={"program": got["first_gradient"]})
    finally:
        runner.close()
    rows = harness.compare(got, ref, {"loss_gap": 1e-5, "grad_diff": 1e-3,
                                      "grad_norm_gap": 1e-3,
                                      "delta_norm_gap": 1e-2})
    assert all(r[3] for r in rows), rows


def test_reference_precisions():
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((4, 16)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    exact = np.asarray(a) @ np.asarray(b)
    err = {p: float(np.max(np.abs(np.asarray(reference.matmul_at(p)(a, b))
                                  - exact))) for p in reference.PRECISIONS}
    assert err["float32"] < 1e-5 < err["bfloat16"] < err["float8"]
    with pytest.raises(ValueError):
        reference.matmul_at("int4")


def test_float8_matmul_gradient_uses_rounded_operands():
    import jax
    import jax.numpy as jnp
    a = jnp.asarray([[1.03, -2.0]], jnp.float32)
    b = jnp.asarray([[0.5], [0.26]], jnp.float32)
    mm = reference.matmul_at("float8")
    da, db = jax.grad(lambda a, b: mm(a, b).sum(), argnums=(0, 1))(a, b)
    assert da.shape == a.shape and db.shape == b.shape
    # d/da = b^T rounded to e4m3 on the tensor's scale 448/0.5: 0.26*896 =
    # 232.96 -> 240 (e4m3 steps by 16 there) -> 240/896
    assert np.allclose(np.asarray(da), [[0.5, 240.0 / 896.0]], atol=1e-6)


def test_adam_reference_is_the_stated_form():
    import jax.numpy as jnp
    opt = {"learning_rate": 0.1, "beta1": 0.9, "beta2": 0.999,
           "epsilon": 1e-8}
    p, g = {"w": jnp.asarray([1.0])}, {"w": jnp.asarray([0.5])}
    z = {"w": jnp.zeros(1)}
    p1, m1, m2 = reference.adam_update(p, g, z, z, 1, opt)
    lr_t = 0.1 * np.sqrt(1 - 0.999) / (1 - 0.9)
    want = 1.0 - lr_t * 0.05 / (np.sqrt(0.001 * 0.25) + 1e-8)
    assert float(p1["w"][0]) == pytest.approx(want, rel=1e-6)
    assert float(m1["w"][0]) == pytest.approx(0.05)
