"""The flash kernels' grouped heads, sliding window and unequal q/k and
value widths (interpret mode against the XLA path), the errors outside the
supported space, and `flash.plan`. The fused backward kernel against the two
it stands for is in `test_flash_fused_backward.py`, the guard that the
cells' attention calls lower as recorded in `test_flash_lowering_pins.py`."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework import obs
from paddle_tpu.ops import attention_ops
from paddle_tpu.ops.pallas import flash_attention as fa

from _flash_cases import _inputs, _worst


def _value_and_grads(fn, q, k, v, w):
    return jax.value_and_grad(
        lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w),
        (0, 1, 2))(q, k, v)


# a window smaller than, equal to and larger than the 16-wide tile, one
# that is no multiple of it, and none
@pytest.mark.parametrize("heads,dv,window", list(itertools.product(
    [(4, 2), (2, 2), (4, 1)], [16, 32], [None, 8, 16, 24, 40])))
def test_kernels_equal_xla_forward_and_all_three_gradients(heads, dv,
                                                           window):
    hq, hkv = heads
    q, k, v, w = _inputs(2, hq, hkv, 64, 64, 16, dv)
    got = _value_and_grads(
        lambda q, k, v: fa.flash_attention(
            q, k, v, scale=0.25, causal=True, window=window, block_q=16,
            block_k=16, interpret=True), q, k, v, w)
    want = _value_and_grads(
        lambda q, k, v: fa._xla_attention(q, k, v, None, 0.25, True, window),
        q, k, v, w)
    assert abs(float(got[0] - want[0])) <= 1e-5 * abs(float(want[0]))
    assert _worst(got[1], want[1]) < 1e-5


@pytest.mark.parametrize("block_q,block_k,tq", [(8, 32, 64), (32, 8, 64),
                                                (16, 16, 32)])
def test_unequal_tiles_and_fewer_queries_than_keys(block_q, block_k, tq):
    q, k, v, w = _inputs(1, 4, 2, tq, 64, 16, 32, seed=3)
    got = _value_and_grads(
        lambda q, k, v: fa.flash_attention(
            q, k, v, scale=0.25, causal=True, window=24, block_q=block_q,
            block_k=block_k, interpret=True), q, k, v, w)
    want = _value_and_grads(
        lambda q, k, v: fa._xla_attention(q, k, v, None, 0.25, True, 24),
        q, k, v, w)
    assert _worst(got[1], want[1]) < 1e-5


def test_key_mask_with_grouped_heads_and_a_window():
    q, k, v, w = _inputs(2, 4, 2, 64, 64, 16, 32, seed=5)
    mask = jnp.where(jnp.arange(64) % 7 == 0, -1e9, 0.0).reshape(1, 1, 1, 64)
    mask = jnp.broadcast_to(mask, (2, 1, 1, 64)).astype(jnp.float32)
    got = _value_and_grads(
        lambda q, k, v: fa.flash_attention(
            q, k, v, mask=mask, scale=0.25, causal=True, window=24,
            block_q=16, block_k=16, interpret=True), q, k, v, w)
    want = _value_and_grads(
        lambda q, k, v: fa._xla_attention(q, k, v, mask, 0.25, True, 24),
        q, k, v, w)
    assert _worst(got[1], want[1]) < 1e-5


def test_the_cpu_op_path_has_the_same_semantics():
    q, k, v, _w = _inputs(2, 4, 2, 32, 32, 16, 32, seed=7)
    got = attention_ops._sdpa_xla(q, k, v, None, 0.25, True, 8)
    want = fa._xla_attention(q, k, v, None, 0.25, True, 8)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # an explicit oracle: query t sees keys t-8 < s <= t of kv head h // 2
    s = np.einsum("bhqd,bhkd->bhqk", np.asarray(q),
                  np.repeat(np.asarray(k), 2, axis=1)) * 0.25
    rel = np.arange(32)[:, None] - np.arange(32)[None, :]
    s = np.where((rel >= 0) & (rel < 8), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    oracle = np.einsum("bhqk,bhkd->bhqd", p,
                       np.repeat(np.asarray(v), 2, axis=1))
    np.testing.assert_allclose(got, oracle, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("kwargs,match", [
    (dict(hq=3, hkv=2), "whole multiple"),
    (dict(window=0), "positive"),
    (dict(window=-4), "positive"),
    (dict(window=8, causal=False), "causal"),
])
def test_calls_outside_the_supported_space_raise_a_clear_error(kwargs,
                                                               match):
    hq, hkv = kwargs.get("hq", 4), kwargs.get("hkv", 2)
    q, k, v, _w = _inputs(1, hq, hkv, 32, 32, 16, 16)
    with pytest.raises(ValueError, match=match):
        fa.flash_attention(q, k, v, causal=kwargs.get("causal", True),
                           window=kwargs.get("window"), interpret=True)
    with pytest.raises(ValueError, match=match):
        attention_ops._sdpa(None, {"Q": [q], "K": [k], "V": [v]}, {
            "causal": kwargs.get("causal", True),
            "window": kwargs.get("window")})


def test_pick_blocks_follows_the_window_and_the_value_width():
    bf16 = jnp.bfloat16
    for kernel in fa.KERNELS:
        assert fa.pick_blocks(8192, 8192, 64, bf16, kernel, True, 512,
                              128) == (512, 512)
        assert fa.pick_blocks(8192, 8192, 64, bf16, kernel, True, 100,
                              128) == (256, 256)
        assert fa.pick_blocks(8192, 8192, 64, bf16, kernel, True, None,
                              128) == (1024, 1024)
    # the value width counts: dv = None is dv = d
    assert fa.vmem_bytes("fwd", 512, 512, 64, 2) \
        == fa.vmem_bytes("fwd", 512, 512, 64, 2, dv=64)
    assert fa.vmem_bytes("bwd_dkv", 512, 512, 64, 2, dv=256) \
        > fa.vmem_bytes("bwd_dkv", 512, 512, 64, 2)


def test_the_plan_counts_tiles_skipped_by_causality_and_by_the_window():
    shapes = ((2, 20, 8192, 64), (2, 10, 8192, 64), (2, 10, 8192, 128))
    blocks = [fa.pick_blocks(8192, 8192, 64, jnp.bfloat16, k, True, 512, 128)
              for k in fa.KERNELS]
    windowed = fa.plan(*shapes, True, 512, blocks)
    assert windowed["group"] == 2 and windowed["d_v"] == 128
    for kernel in fa.KERNELS:
        row = windowed[kernel]
        # 16 x 16 tiles of 512: the diagonal and the one before it run
        assert row["tiles_visited"] == 31
        assert row["tiles_skipped_causal"] == 120
        assert row["tiles_skipped_window"] == 105 > 0
        assert row["grid_inner"] == 2
    blocks = [fa.pick_blocks(8192, 8192, 64, jnp.bfloat16, k, True, None,
                             128) for k in fa.KERNELS]
    full = fa.plan(*shapes, True, None, blocks)
    for kernel in fa.KERNELS:
        assert full[kernel]["tiles_skipped_window"] == 0
        assert full[kernel]["tiles_visited"] == 36
        assert full[kernel]["grid_inner"] == 8


def test_a_lowering_records_one_flash_plan_while_obs_is_on():
    q, k, v, _w = _inputs(1, 4, 2, 64, 64, 16, 32)
    obs.clear()
    obs.enable()
    try:
        jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=True, window=16, block_q=16, block_k=16,
            interpret=True))(q, k, v)
        plans = obs.spans(name="flash.plan")
    finally:
        obs.disable()
        obs.clear()
    assert len(plans) == 1
    labels = plans[0]["labels"]
    assert labels["window"] == 16 and labels["group"] == 2
    assert labels["fwd"]["tiles_skipped_window"] > 0
    assert labels["backward"] == "split: window" and "bwd" not in labels


@pytest.mark.parametrize("heads,block_diffusion", [
    ((4, 2), None), ((14, 2), None), ((8, 1), (4, 32))])
def test_a_grouped_call_records_the_fused_backward_and_its_group(
        heads, block_diffusion):
    """What the five cells with shared key/value heads read back from
    `flash.plan`: "fused" beside the group, one "bwd" row and no pair."""
    hq, hkv = heads
    q, k, v, _w = _inputs(1, hq, hkv, 64, 64, 16, 16)
    obs.clear()
    obs.enable()
    try:
        jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
            q, k, v, causal=block_diffusion is None, block_q=16, block_k=16,
            interpret=True, block_diffusion=block_diffusion))))(q, k, v)
        plans = obs.spans(name="flash.plan")
    finally:
        obs.disable()
        obs.clear()
    assert len(plans) == 1
    labels = plans[0]["labels"]
    assert labels["backward"] == "fused" and labels["group"] == hq // hkv
    assert "bwd_dkv" not in labels and "bwd_dq" not in labels
    assert labels["bwd"]["grid_inner"] == 4     # q-blocks innermost


def test_the_plan_names_the_fused_backward_and_its_tile():
    shape = (4, 12, 4096, 64)
    path = fa.attention_path(shape, shape, shape, jnp.bfloat16, True, None,
                             False)
    got = fa.plan(shape, shape, shape, True, None, path.blocks,
                  path.backward)
    assert got["backward"] == "fused"
    assert "bwd_dkv" not in got and "bwd_dq" not in got
    bq, bk = fa.pick_blocks(4096, 4096, 64, jnp.bfloat16, "bwd", True)
    nq, nk = 4096 // bq, 4096 // bk
    assert got["bwd"]["block_q"] == bq and got["bwd"]["block_k"] == bk
    assert got["bwd"]["grid_inner"] == nq       # q-blocks innermost
    assert got["bwd"]["tiles_visited"] + got["bwd"]["tiles_skipped_causal"] \
        == nq * nk
