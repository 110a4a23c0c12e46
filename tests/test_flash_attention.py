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


# ---- PR 25: tiles from the shape, MXU operands in the input dtype ----

from paddle_tpu.ops.pallas import flash_attention as fa  # noqa: E402

_RULE_SHAPES = [(t, t, d, None) for t in (128, 256, 384, 512, 1024, 4096, 8192)
                for d in (64, 128)] \
    + [(512, 1024, 64, None), (1024, 4096, 128, None)] \
    + [(t, t, d, dv) for d, dv in ((192, 128), (64, 128), (128, 64))
       for t in (256, 1024, 8192, 24576)]     # Dv != D


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("tq,tk,d,dv", _RULE_SHAPES)
def test_tile_rule_table(tq, tk, d, dv, dtype, causal):
    """The tile rule, for the benchmark cells' shapes among others, with
    the value width beside the q/k width where they differ: blocks divide
    T, are >= 128 (the device path's floor), and the reckoned VMEM (dQ's
    row at the q/k width's lanes, dV's accumulator at the value width's)
    stays under what the call asks Mosaic for (its default where it asks
    for nothing)."""
    itemsize = jnp.dtype(dtype).itemsize
    for kernel in fa.KERNELS + fa.FUSED_KERNELS[1:]:
        bq, bk = fa.pick_blocks(tq, tk, d, dtype, kernel, causal, dv=dv)
        assert tq % bq == 0 and tk % bk == 0
        assert bq >= 128 and bk >= 128
        assert bq % 128 == 0 or bq == tq
        # the fused backward also holds dQ's whole row: Tq is part of it
        row = {"tq": tq} if kernel == "bwd" else {}
        for mask_mode in ("none", "k", "qk"):
            need = fa.vmem_bytes(kernel, bq, bk, d, itemsize, mask_mode,
                                 dv, **row)
            if kernel == "bwd" and need > fa._VMEM_CEILING:
                # `backward_rule` keeps such a call on the split pair
                q_shape = (1, 1, tq, d)
                assert fa.backward_rule(
                    q_shape, (1, 1, tk, d), (1, 1, tk, dv or d), dtype,
                    causal, None) == "split: vmem"
                continue
            params = fa._compiler_params(kernel, bq, bk, d, dtype, mask_mode,
                                         dv, **row)
            limit = (params and params.vmem_limit_bytes) \
                or fa._VMEM_DEFAULT
            assert need <= limit <= fa._VMEM_CEILING
            if kernel == "bwd":     # and it runs its inner axes in order
                assert tuple(params.dimension_semantics) \
                    == ("parallel", "arbitrary", "arbitrary")


def test_tile_rule_follows_the_shape(monkeypatch):
    """One algorithm, parameters from the shape (the v5e sweep's winners,
    PERF.md PR 25): the forward takes the widest tile up to 1024x1024; the
    causal backward kernels a quarter of the sequence a side, between 512
    and 1024, the fused backward as dK/dV; float32 stops at 512. Explicit
    blocks keep winning on their side of every kernel's tile."""
    def rule(t, causal=True, dtype="bfloat16", d=64):
        assert fa.pick_blocks(t, t, d, dtype, "bwd", causal) \
            == fa.pick_blocks(t, t, d, dtype, "bwd_dkv", causal)
        return tuple(fa.pick_blocks(t, t, d, dtype, kern, causal)
                     for kern in fa.KERNELS)
    assert rule(4096) == ((1024, 1024),) * 3        # gpt2.t4096-b4
    assert rule(1024) == ((1024, 1024), (512, 512), (512, 512))  # t1024-b16
    assert rule(2048) == ((1024, 1024), (512, 512), (512, 512))
    assert rule(512) == ((512, 512),) * 3
    assert rule(512, causal=False) == ((512, 512),) * 3   # bert-base.s512
    assert rule(1024, causal=False) == ((1024, 1024),) * 3
    assert rule(4096, d=128) == rule(4096)
    assert rule(4096, dtype="float32") == ((512, 512),) * 3
    assert fa.pick_blocks(512, 4096, 64, "bfloat16", "bwd_dq", True) \
        == (512, 512)
    seen = []
    monkeypatch.setattr(fa, "_flash", lambda *a: seen.append(a[6]))
    q = jnp.zeros((1, 1, 1024, 64), jnp.bfloat16)
    flash_attention(q, q, q, causal=True, interpret=True)
    flash_attention(q, q, q, causal=True, block_q=64, block_k=32,
                    interpret=True)
    flash_attention(q, q, q, causal=True, block_k=128, interpret=True)
    rule, explicit, one_side = seen
    # a plain causal call: the forward and the fused backward
    assert rule == tuple(fa.pick_blocks(1024, 1024, 64, q.dtype, kern, True)
                         for kern in fa.FUSED_KERNELS)
    assert explicit == ((64, 32),) * 2
    assert one_side == tuple((bq, 128) for bq, _ in rule)
    del seen[:]
    k = jnp.zeros((1, 1, 1024, 128), jnp.bfloat16)     # Dv != D: fused too
    flash_attention(q, q, k, causal=True, interpret=True)
    flash_attention(q, q, k, causal=True, block_q=64, block_k=32,
                    interpret=True)
    assert seen == [tuple(fa.pick_blocks(1024, 1024, 64, q.dtype, kern,
                                         True, None, 128)
                          for kern in fa.FUSED_KERNELS), ((64, 32),) * 2]
    del seen[:]
    q2 = jnp.zeros((1, 2, 1024, 64), jnp.bfloat16)     # and grouped: fused
    flash_attention(q2, q, k, causal=True, interpret=True)
    flash_attention(q2, q, k, causal=True, window=512, interpret=True)
    assert seen == [tuple(fa.pick_blocks(1024, 1024, 64, q.dtype, kern,
                                         True, None, 128, group=2)
                          for kern in fa.FUSED_KERNELS),
                    ((512, 512),) * 3]      # a window: the split pair


def test_the_fused_backwards_vmem_counts_each_width_at_its_own_lanes():
    """`vmem_bytes("bwd", ...)` at the Kimi cells' call, by its parts: the
    blocks twice (q and dO, k and dK, v and dV, dQ's whole row, the two
    statistics rows, the mask tile), the float32 accumulators (dK at 256
    lanes, dV at 128, dQ's row at 256) and eight score tiles; 192 pads to
    256 lanes, the values stay at 128."""
    mib = 2.0 ** 20
    bq = bk = 1024
    tq = 8192
    blocks = ((bq * 256 + bq * 128 + 2 * bk * 256 + 2 * bk * 128
               + tq * 256) * 2 + 2 * 8 * bq * 4 + bq * bk * 2)
    scratch = (bk * 256 + bk * 128 + tq * 256) * 4
    want = 2 * blocks + scratch + 8 * bq * bk * 4
    got = fa.vmem_bytes("bwd", bq, bk, 192, 2, "qk", dv=128, tq=tq)
    assert got == want and round(got / mib, 1) == 58.1
    assert got < fa._VMEM_CEILING
    # no `dv` means the q/k width; a narrower value width needs less
    assert fa.vmem_bytes("bwd", bq, bk, 192, 2, "qk", tq=tq) \
        == fa.vmem_bytes("bwd", bq, bk, 192, 2, "qk", 192, tq) \
        == fa.vmem_bytes("bwd", bq, bk, 192, 2, "qk", 256, tq) > got
    # Tq enters through dQ's row alone: 256 lanes x (2 x 2 + 4) bytes
    assert fa.vmem_bytes("bwd", bq, bk, 192, 2, "qk", 128, 2 * tq) - got \
        == tq * 256 * 8
    # and the rule weighs that row: the same call at four times the length
    shape = (2, 16, 4 * tq)
    assert fa.backward_rule(shape + (192,), shape + (192,), shape + (128,),
                            "bfloat16", True, None) == "split: vmem"
    assert fa.backward_rule(shape + (128,), shape + (128,), shape + (128,),
                            "bfloat16", True, None) == "fused"


def _loss_grads(fn, args, w):
    out = fn(*args)
    grads = jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * w),
                     argnums=tuple(range(len(args))))(*args)
    return (out,) + tuple(grads)


def _mask_case(mode, b, tq, tk, seed):
    if mode == "key_padding":
        mask = np.zeros((b, 1, 1, tk), np.float32)
        mask[..., 3 * tk // 4:] = -1e4
        return mask, False
    if mode == "per_query_bias":
        return _rand((b, 1, tq, tk), seed) * 0.5, False
    return None, True      # "causal", "causal_rect"


@pytest.mark.parametrize("mode,tq,tk", [
    ("causal", 512, 512), ("key_padding", 512, 512),
    ("per_query_bias", 256, 512), ("causal_rect", 256, 512)])
def test_rule_tiles_match_small_tiles_and_reference(mode, tq, tk):
    """Forward and all three gradients with the rule's large tiles equal
    the 16x16-tile results and `_xla_attention`; the rectangular causal
    case crosses tile edges differently at large tiles (bottom-right
    alignment)."""
    b, h, d = 1, 2, 16
    q, k, v = _rand((b, h, tq, d), 20), _rand((b, h, tk, d), 21), \
        _rand((b, h, tk, d), 22)
    w = _rand((b, h, tq, d), 23)
    mask, causal = _mask_case(mode, b, tq, tk, 24)
    for kernel in fa.KERNELS:
        assert min(fa.pick_blocks(tq, tk, d, "float32", kernel,
                                  causal)) >= 128

    def flash(**blocks):
        return lambda q, k, v: flash_attention(
            q, k, v, mask=mask, scale=d ** -0.5, causal=causal,
            interpret=True, **blocks)

    def ref(q, k, v):
        return _xla_attention(q, k, v, None if mask is None
                              else jnp.asarray(mask), d ** -0.5, causal)

    large = _loss_grads(flash(), (q, k, v), w)
    small = _loss_grads(flash(block_q=16, block_k=16), (q, k, v), w)
    oracle = _loss_grads(ref, (q, k, v), w)
    for a, s_, o in zip(large, small, oracle):
        np.testing.assert_allclose(np.asarray(a), np.asarray(s_),
                                   rtol=2e-3, atol=2e-4)
        np.testing.assert_allclose(np.asarray(a), np.asarray(o),
                                   rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("mode", ["causal", "key_padding", "per_query_bias"])
def test_bf16_operands_against_f32_oracle(mode):
    """bf16 inputs feed the MXU as they are, p and ds cast down for the
    second matmuls: outputs and gradients stay within `pallas_selfcheck`'s
    bf16 tolerance (1e-2 of the oracle's range) of the f32 oracle."""
    b, h, t, d = 2, 2, 256, 64
    mask, causal = _mask_case(mode, b, t, t, 34)
    q, k, v, w = (jnp.asarray(_rand((b, h, t, d), 30 + i), jnp.bfloat16)
                  for i in range(4))
    w = w.astype(jnp.float32)
    mask_j = None if mask is None else jnp.asarray(mask, jnp.bfloat16)

    def flash(q, k, v):
        return flash_attention(q, k, v, mask=mask_j, scale=d ** -0.5,
                               causal=causal, interpret=True)

    def oracle(q, k, v):
        return _xla_attention(q, k, v, None if mask_j is None
                              else mask_j.astype(jnp.float32),
                              d ** -0.5, causal)

    got = _loss_grads(flash, (q, k, v), w)
    f32 = tuple(x.astype(jnp.float32) for x in (q, k, v))
    want = _loss_grads(oracle, f32, w)
    for a, o in zip(got, want):
        assert a.dtype == jnp.bfloat16
        a, o = np.asarray(a, np.float32), np.asarray(o)
        assert np.abs(a - o).max() / max(np.abs(o).max(), 1.0) < 1e-2


def _kernel_dots(dtype, kv_heads, dv=8, window=None):
    """(lhs dtype, rhs dtype, result dtype, precision) of every
    dot_general that a call's kernels trace for inputs of `dtype`: with
    two query heads to `kv_heads` key/value heads, q and k 8 wide and v
    `dv` wide."""
    q = jnp.zeros((1, 2, 32, 8), dtype)
    k = jnp.zeros((1, kv_heads, 32, 8), dtype)
    v = jnp.zeros((1, kv_heads, 32, dv), dtype)
    closed = jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                        window=window, interpret=True).astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, k, v)
    dots = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                dots.append(tuple(str(x.aval.dtype) for x in eqn.invars)
                            + (str(eqn.outvars[0].aval.dtype),
                               eqn.params["precision"]))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(closed.jaxpr)
    return dots


@pytest.mark.parametrize("backward,kv_heads,dv,window,count", [
    ("fused", 2, 8, None, 2 + 5),   # forward; s, dp once, then dV, dK, dQ
    ("fused_dv_16", 2, 16, None, 2 + 5),    # unequal widths: the same five
    ("fused_dv_24", 2, 24, None, 2 + 5),
    ("fused_group_2", 1, 8, None, 2 + 5),   # and a group of query heads
    ("fused_group_2_dv_16", 1, 16, None, 2 + 5),
    ("split", 1, 8, 16, 2 + 4 + 3),     # a window: forward, dK/dV, dQ
    ("split_dv_16", 1, 16, 16, 2 + 4 + 3)])     # (s and dp in both)
@pytest.mark.parametrize("dtype", ["float32", "float16", "bfloat16"])
def test_mxu_operand_dtype_and_precision(dtype, backward, kv_heads, dv,
                                         window, count):
    """float32/float16 inputs keep float32 operands at HIGHEST — the same
    operations, in the same order, as before this rule existed, so the same
    bits at equal tiles; bfloat16 inputs reach every dot as bfloat16 with
    a float32 result. The fused backward runs the mathematics' five
    matmuls, the split kernels seven, whatever the value width."""
    dots = _kernel_dots(dtype, kv_heads, dv, window)
    assert len(dots) == count
    highest = jax.lax.Precision.HIGHEST
    for lhs, rhs, out, precision in dots:
        assert out == "float32"
        if dtype == "bfloat16":
            assert (lhs, rhs) == ("bfloat16", "bfloat16")
            assert precision is None or highest not in tuple(precision)
        else:
            assert (lhs, rhs) == ("float32", "float32")
            assert tuple(precision) == (highest, highest)


def test_f32_unchanged_to_the_bit_at_equal_tiles():
    """Equal tiles, float32: the rule's path (no blocks given) and the
    explicit-block path run the same program. (That program's operations
    are the parent's, see `test_mxu_operand_dtype_and_precision`; on the
    chip its bits were the parent's too: PERF.md, PR 25.)"""
    b, h, t, d = 1, 2, 256, 16
    q, k, v = _rand((b, h, t, d), 40), _rand((b, h, t, d), 41), \
        _rand((b, h, t, d), 42)
    w = _rand((b, h, t, d), 43)
    blocks = {kern: fa.pick_blocks(t, t, d, "float32", kern, True)
              for kern in fa.KERNELS}
    assert len(set(blocks.values())) == 1     # one tile: comparable
    bq, bk = blocks["fwd"]

    def run(**kw):
        return jax.jit(lambda q, k, v: _loss_grads(
            lambda q, k, v: flash_attention(q, k, v, scale=0.25,
                                            causal=True, interpret=True,
                                            **kw), (q, k, v), w))(q, k, v)
    for a, b_ in zip(run(), run(block_q=bq, block_k=bk)):
        assert (np.asarray(a) == np.asarray(b_)).all()


def test_the_microbenchmark_of_the_flash_tiles_walks_through():
    """Off the TPU `tools/mb_flash_tiles.py` exits 1 without the flag; with
    it, at tiny shapes in interpret mode: one JSON line a shape, every
    kernel timed at every tile, beside the rule's tile and backward."""
    import json
    import os
    import subprocess
    import sys
    tool = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "mb_flash_tiles.py")

    def run(*argv):
        return subprocess.run(
            [sys.executable, tool] + list(argv), capture_output=True,
            text=True, timeout=600, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    done = run()
    assert done.returncode == 1 and "not a TPU" in done.stderr
    done = run("--walk-through")
    assert done.returncode == 0, done.stderr[-2000:]
    rows = [json.loads(line) for line in done.stdout.splitlines()]
    assert [row["shape"] for row in rows] == [
        "1x2x256x32", "1x2x256x48|32", "1x4:2x256x32-bd4"]
    kernels = list(fa.KERNELS + fa.FUSED_KERNELS[1:])
    for row in rows:
        assert row["platform"] == "cpu" and row["device_times"] is False
        grouped = ":" in row["shape"]   # the rule's tile divides T = 128
        assert list(row["ms"]) == ["128x128"] + ["256x256"] * (not grouped)
        for tile, timed in row["ms"].items():
            assert list(timed) == kernels, (tile, timed)
            assert all(isinstance(ms, float) for ms in timed.values()), timed
        assert set(row["best"].values()) <= set(row["ms"])
        assert row["rule"] == dict.fromkeys(
            kernels, "128x128" if grouped else "256x256")
        assert row["backward"] == "fused"
    only = run("--walk-through", "--only", "4:2")
    assert [json.loads(line)["shape"] for line in only.stdout.splitlines()] \
        == ["1x4:2x256x32-bd4"]
