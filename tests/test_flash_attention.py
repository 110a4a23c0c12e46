"""Pallas flash attention vs reference XLA attention (interpret mode on
CPU — same kernel code path as TPU)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.flash_attention import (flash_attention,
                                                   _xla_attention)


def _rand(shape, seed):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_reference(causal):
    b, h, t, d = 2, 2, 64, 32
    q, k, v = _rand((b, h, t, d), 0), _rand((b, h, t, d), 1), \
        _rand((b, h, t, d), 2)
    scale = d ** -0.5
    out = flash_attention(q, k, v, scale=scale, causal=causal,
                          block_q=16, block_k=16, interpret=True)
    ref = _xla_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         None, scale, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_with_key_mask():
    b, h, t, d = 2, 2, 32, 16
    q, k, v = _rand((b, h, t, d), 0), _rand((b, h, t, d), 1), \
        _rand((b, h, t, d), 2)
    mask = np.zeros((b, 1, 1, t), np.float32)
    mask[:, :, :, t // 2:] = -1e9  # mask out second half of keys
    out = flash_attention(q, k, v, mask=mask, scale=0.25, block_q=8,
                          block_k=8, interpret=True)
    ref = _xla_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                         jnp.asarray(mask), 0.25, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_gradients():
    b, h, t, d = 1, 2, 32, 16
    q, k, v = _rand((b, h, t, d), 0), _rand((b, h, t, d), 1), \
        _rand((b, h, t, d), 2)
    scale = d ** -0.5

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, scale=scale, causal=True,
                                       block_q=8, block_k=8,
                                       interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, None, scale, True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-4)


def test_sdpa_op_uses_flash_on_request():
    """The fused attention op routes impl='flash' through the kernel."""
    import paddle_tpu as pt
    from paddle_tpu import layers
    from paddle_tpu.layers.attention import fused_attention
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        q = layers.data("q", [2, 32, 16], dtype="float32",
                        append_batch_size=False)
        q2 = layers.data("q2", [2, 2, 32, 16], dtype="float32",
                         append_batch_size=False)
    # direct kernel check through the op registry
    from paddle_tpu.ops.registry import get_op

    class Ctx:
        def rng(self):
            return jax.random.PRNGKey(0)

    qv = _rand((2, 2, 32, 16), 0)
    kv = _rand((2, 2, 32, 16), 1)
    vv = _rand((2, 2, 32, 16), 2)
    outs = get_op("scaled_dot_product_attention").fn(
        Ctx(), {"Q": [jnp.asarray(qv)], "K": [jnp.asarray(kv)],
                "V": [jnp.asarray(vv)]}, {"scale": 0.25, "impl": "auto"})
    ref = _xla_attention(jnp.asarray(qv), jnp.asarray(kv), jnp.asarray(vv),
                         None, 0.25, False)
    np.testing.assert_allclose(np.asarray(outs["Out"]), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_pallas_backward_rectangular(causal):
    """Pallas dQ/dK/dV kernels (mask=None path) vs XLA vjp, Tq != Tk."""
    b, h, tq, tk, d = 1, 2, 32, 64, 16
    q, k, v = _rand((b, h, tq, d), 3), _rand((b, h, tk, d), 4), \
        _rand((b, h, tk, d), 5)
    scale = d ** -0.5

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, scale=scale, causal=causal,
                                       block_q=8, block_k=16,
                                       interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(_xla_attention(q, k, v, None, scale, causal) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_key_mask_backward(causal):
    """Pallas backward with a (B,1,1,Tk) padding mask (the BERT case):
    dq/dk/dv from the mask-aware kernels + dmask from the DCE-able XLA
    expression all match the reference vjp."""
    b, h, t, d = 2, 2, 32, 16
    q, k, v = _rand((b, h, t, d), 10), _rand((b, h, t, d), 11), \
        _rand((b, h, t, d), 12)
    mask = np.zeros((b, 1, 1, t), np.float32)
    mask[:, :, :, 3 * t // 4:] = -1e4

    def loss_flash(q, k, v, m):
        return jnp.sum(flash_attention(q, k, v, mask=m, scale=0.25,
                                       causal=causal, block_q=8,
                                       block_k=8, interpret=True) ** 2)

    def loss_ref(q, k, v, m):
        return jnp.sum(_xla_attention(q, k, v, m, 0.25, causal) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2, 3))(q, k, v, mask)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(q, k, v, mask)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-4)


def test_flash_grad_finite_difference():
    """Independent oracle: central finite differences on the flash loss
    itself (not a JAX re-expression) — catches a wrong hand-written vjp."""
    b, h, t, d = 1, 1, 16, 8
    q, k, v = _rand((b, h, t, d), 13), _rand((b, h, t, d), 14), \
        _rand((b, h, t, d), 15)
    mask = np.zeros((b, 1, 1, t), np.float32)
    mask[:, :, :, t // 2:] = -1e4

    def loss(q):
        return jnp.sum(flash_attention(
            q, k, v, mask=mask, scale=0.35, block_q=8, block_k=8,
            interpret=True) ** 2)

    g = np.asarray(jax.grad(loss)(q))
    rng = np.random.RandomState(42)
    for _ in range(5):
        i = tuple(rng.randint(s) for s in q.shape)
        eps = 1e-3
        qp, qm = q.copy(), q.copy()
        qp[i] += eps
        qm[i] -= eps
        fd = (float(loss(qp)) - float(loss(qm))) / (2 * eps)
        # f32 central differences carry ~1% noise; a wrong vjp is off by
        # far more than 5%
        np.testing.assert_allclose(g[i], fd, rtol=5e-2, atol=5e-4)


def test_flash_qk_mask_backward_with_mask_cotangent():
    """(B,1,Tq,Tk) mask: Pallas dq/dk/dv + the separate dmask expression
    together match the reference vjp exactly."""
    b, h, t, d = 1, 2, 16, 16
    q, k, v = _rand((b, h, t, d), 6), _rand((b, h, t, d), 7), \
        _rand((b, h, t, d), 8)
    mask = _rand((b, 1, t, t), 9) * 0.1

    def loss_flash(q, k, v, m):
        return jnp.sum(flash_attention(q, k, v, mask=m, scale=0.25,
                                       block_q=8, block_k=8,
                                       interpret=True) ** 2)

    def loss_ref(q, k, v, m):
        return jnp.sum(_xla_attention(q, k, v, m, 0.25, False) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2, 3))(q, k, v, mask)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(q, k, v, mask)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-3, atol=2e-4)
