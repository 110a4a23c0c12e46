"""The `ssd_fwd` / `ssd_bwd` Pallas kernels (ops/pallas/ssd.py) in interpret
mode on the CPU, so tier-1 runs the kernel bodies: against the token-by-token
recurrence and against the XLA form `_ssd` (the kernels' oracle) in y and in
all five gradients (x, dt, A, B, C), over dtype (float32; bfloat16 with dt
float32), T (whole chunks; 200, padded with dt = 0 as `mamba2_scan` pads),
one group and two, and a decay so long that a chunk's cumulative log-decay
passes -100; and the door: where `plan` refuses a shape, and off the TPU,
`mamba2_scan` is `_ssd` to the bit and `ssd.plan` names the XLA form; and
`tools/mb_ssd.py` walks through."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.framework import obs
from paddle_tpu.ops import ssm_ops
from paddle_tpu.ops.pallas import ssd

Q, P, N, HEADS = ssd.CHUNK, 64, 128, 4


def operands(t, groups, decay, dtype, seed=0):
    """`_ssd`'s operands for T tokens, padded to whole chunks as
    `mamba2_scan` pads (dt = 0), and a cotangent of y."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    chunks = -(-t // Q)
    live = (jnp.arange(chunks * Q) < t).reshape(1, chunks, Q, 1)

    def cut(m):
        m = m.reshape((1, chunks, Q) + m.shape[1:])
        return jnp.where(live.reshape(live.shape[:3] + (1,) * (m.ndim - 3)),
                         m, 0.0)

    dt = jax.nn.softplus(jax.random.normal(k[1], (chunks * Q, HEADS))
                         + (4.0 if decay == "long" else -2.0))
    a = -jnp.exp(0.5 * jax.random.normal(k[2], (HEADS,)))
    if decay == "long":
        a = jnp.full((HEADS,), -jnp.e)
    x = jax.random.normal(k[0], (chunks * Q, HEADS, P))
    b, c = (0.3 * jax.random.normal(key, (chunks * Q, groups, N))
            for key in k[3:5])
    cot = cut(jax.random.normal(k[5], (chunks * Q, HEADS, P)))
    return (cut(x).astype(dtype), cut(dt), a, cut(b).astype(dtype),
            cut(c).astype(dtype)), cot


def recurrence(x, dt, a, b, c):
    """S_t = e^{a dt_t} S_{t-1} + dt_t B_t x_t^T, y_t = S_t^T C_t, a token
    at a time, on `_ssd`'s operands."""
    bsz, chunks, q, h, p = x.shape
    flat = lambda m: m.reshape((bsz, chunks * q) + m.shape[3:]).astype(
        jnp.float32)
    x, dt, b, c = (flat(m) for m in (x, dt, b, c))
    b, c = (jnp.repeat(m, h // m.shape[2], axis=2) for m in (b, c))

    def step(s, now):
        x_t, dt_t, b_t, c_t = now
        s = jnp.exp(a * dt_t)[..., None, None] * s \
            + (dt_t[..., None] * b_t)[..., :, None] * x_t[..., None, :]
        return s, jnp.sum(s * c_t[..., :, None], axis=-2)

    _s, ys = jax.lax.scan(step, jnp.zeros((bsz, h, b.shape[-1], p)), tuple(
        m.swapaxes(0, 1) for m in (x, dt, b, c)))
    return ys.swapaxes(0, 1).reshape(bsz, chunks, q, h, p)


def value_and_grads(fn, args, cot):
    out, pull = jax.vjp(fn, *args)
    return (out,) + tuple(pull(cot))


def gap(got, want):
    got, want = (jnp.asarray(m, jnp.float32) for m in (got, want))
    assert bool(jnp.all(jnp.isfinite(got)))
    return float(jnp.linalg.norm(got - want)) \
        / (float(jnp.linalg.norm(want)) + 1e-30)


@pytest.mark.parametrize("dtype,t,groups,decay", [
    ("float32", 256, 1, "typical"), ("float32", 200, 2, "typical"),
    ("float32", 256, 2, "long"), ("bfloat16", 256, 2, "typical"),
    ("bfloat16", 200, 1, "typical")])
def test_the_kernels_are_the_recurrence_and_the_xla_form(dtype, t, groups,
                                                         decay):
    """y and every gradient. float32: both to float32 rounding. bfloat16:
    the kernels round where `_mm(..., mxu)` rounds and nowhere else, so they
    stay as near the float32 recurrence as the XLA form does."""
    args, cot = operands(t, groups, decay, jnp.dtype(dtype), seed=t + groups)
    if decay == "long":
        assert float((args[1] * args[2]).sum(axis=2).min()) < -100.0
    assert ssd.plan((1, t, HEADS, P), groups, N, Q, 4)["heads_a_step"] \
        == HEADS // groups
    got = value_and_grads(lambda *m: ssd.ssd(*m, True), args, cot)
    oracle = value_and_grads(ssm_ops._ssd, args, cot)
    exact = tuple(m.astype(jnp.float32) for m in args)
    plain = value_and_grads(recurrence, exact, cot)
    assert got[0].dtype == jnp.float32
    assert [m.dtype for m in got[1:]] == [m.dtype for m in args]
    for name, mine, xla, ref in zip(("y", "dx", "d dt", "d a", "dB", "dC"),
                                    got, oracle, plain):
        assert mine.shape == ref.shape, name
        if dtype == "float32":
            # (d a under the long decay is what its terms' cancelling
            # leaves: either form lies 1e-2 from the recurrence there)
            far = 1.5 * gap(xla, ref)
            assert gap(mine, ref) <= max(1e-4, far), \
                name + " against the recurrence"
            assert gap(mine, xla) <= max(2e-5, far), \
                name + " against the XLA form"
        else:
            assert gap(mine, ref) <= max(1.5 * gap(xla, ref), 2e-3), name
            assert gap(mine, xla) <= 1e-2, name


def _scan_args(h=8, p=64, g=2, n=128, t=150):
    k = jax.random.split(jax.random.PRNGKey(1), 7)
    return (jax.random.normal(k[0], (1, t, h, p)),
            jax.random.normal(k[1], (1, t, h)),
            0.5 * jax.random.normal(k[2], (h,)),
            0.5 * jax.random.normal(k[3], (h,)),
            jax.random.normal(k[4], (1, t, g, n)),
            jax.random.normal(k[5], (1, t, g, n)),
            jax.random.normal(k[6], (h,)))


@pytest.mark.parametrize("why,chunk,shape,on_tpu", [
    ("off the TPU", 128, {}, False),
    ("a chunk of 64", 64, {}, True),
    ("a state of 64", 128, {"n": 64}, True),
    ("a group's heads fill no lane tile", 128, {"h": 2, "p": 64, "g": 2},
     True)])
def test_the_door_takes_the_xla_form_where_the_kernels_do_not_tile(
        monkeypatch, why, chunk, shape, on_tpu):
    """The choice is made from shapes and the platform alone; a call that
    the kernels refuse is `_ssd` to the bit, and `ssd.plan` says so."""
    args = _scan_args(**shape)
    x, b = args[0], args[4]
    sizes = (tuple(x.shape), b.shape[2], b.shape[3])
    # what the TPU would answer at this shape: nothing but the shape is read
    assert (ssd.plan(*sizes, chunk, 4) is None) == on_tpu
    assert ssd.plan((1, 150, 8, 64), 2, 128, 128, 4)["heads_a_step"] == 4
    if on_tpu:      # the platform says TPU: the shape alone must refuse
        monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "0")
    assert ssm_ops.kernel_plan(*sizes, chunk, 4) is None
    called = []
    monkeypatch.setattr(ssd, "ssd", lambda *a: called.append(a))
    obs.clear()
    obs.enable()
    try:
        got = ssm_ops.mamba2_scan(*args, chunk=chunk)
        plans = obs.spans(name="ssd.plan")
    finally:
        obs.disable()
        obs.clear()
    assert not called
    labels = plans[0]["labels"]
    assert labels["kernels"].startswith("xla: ")
    assert not {"heads_a_step", "vmem_fwd", "vmem_bwd"} & set(labels)

    # `_ssd` on the operands the op hands it
    bsz, t, h, p = x.shape
    chunks = -(-t // chunk)
    cut = lambda m: jnp.pad(
        m, ((0, 0), (0, chunks * chunk - t)) + ((0, 0),) * (m.ndim - 2)
    ).reshape((bsz, chunks, chunk) + m.shape[2:])
    step = jax.nn.softplus(args[1] + args[2])
    y = ssm_ops._ssd(cut(x), cut(step), -jnp.exp(args[3]), cut(b),
                     cut(args[5]))
    want = y.reshape(bsz, -1, h, p)[:, :t] + args[6][None, None, :, None] * x
    assert bool(jnp.all(got == want)), why


def test_the_microbenchmark_walks_through():
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "mb_ssd.py")
    done = subprocess.run(
        [sys.executable, tool, "--walk-through", "--seq", "128", "--batch",
         "1", "--heads", "2", "--groups", "1", "--calls", "1", "--runs", "1"],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]
    assert "ssd_fwd, ssd_bwd: 2 heads a step, forward + " in done.stdout
    assert "from float32" in done.stdout and "dC" in done.stdout
