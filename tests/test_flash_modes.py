"""The flash kernels' grouped heads, sliding window and unequal q/k and
value widths (interpret mode against the XLA path), the errors outside the
supported space, `flash.plan`, and the guard that the plain causal call GPT
makes lowers exactly as it did before those modes existed."""
import hashlib
import itertools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework import obs
from paddle_tpu.ops import attention_ops
from paddle_tpu.ops.pallas import flash_attention as fa

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _inputs(b, hq, hkv, tq, tk, d, dv, dtype=jnp.float32, seed=0):
    key = jax.random.PRNGKey(seed)
    shapes = [(b, hq, tq, d), (b, hkv, tk, d), (b, hkv, tk, dv),
              (b, hq, tq, dv)]
    q, k, v, w = (jax.random.normal(jax.random.fold_in(key, i), s,
                                    jnp.float32)
                  for i, s in enumerate(shapes))
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), w


def _value_and_grads(fn, q, k, v, w):
    return jax.value_and_grad(
        lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w),
        (0, 1, 2))(q, k, v)


def _worst(got, want):
    return max(float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-9))
               for a, b in zip(got, want))


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


# ---------------------------------------------------------------------------
# gpt2 unchanged: the jaxpr of its two attention calls (forward and both
# backward kernels: kernel bodies, grids, block shapes, the VMEM request)
# with every BlockSpec's index map, as the parent commit (PR 25) traced it.
# After a deliberate change to the plain causal path, print the new
# digests with `python tests/test_flash_modes.py` and say in PERF.md why.
# ---------------------------------------------------------------------------

GPT2_CALLS = {
    (4, 12, 4096, 64): {
        "sha256": "d99fac5d6726ca8c92a2d9b098ae92b83ab3ef9a104c8a91087d413f9"
                  "a081ea0", "chars": 45022,
        "blocks": [(1024, 1024), (1024, 1024), (1024, 1024)]},
    (16, 12, 1024, 64): {
        "sha256": "edde0d72e057e7a566c15126400493f74ea09b4512e0499454fe763e0"
                  "03f887e", "chars": 43965,
        "blocks": [(1024, 1024), (512, 512), (512, 512)]},
}


def lowered_text(b, h, t, d):
    q = jax.ShapeDtypeStruct((b, h, t, d), jnp.bfloat16)

    def call(q, k, v):
        out, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
            q, k, v, scale=d ** -0.5, causal=True, interpret=False), q, k, v)
        return out, vjp(out)

    closed = jax.make_jaxpr(call)(q, q, q)
    parts = [str(closed)]
    for eqn in closed.jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            parts.extend(str(bm.index_map_jaxpr)
                         for bm in eqn.params["grid_mapping"].block_mappings)
    # source positions move with every edit of the file; nothing else does
    return re.sub(r"/[^\s:\"']*\.py:\d+", "", "\n".join(parts))


@pytest.mark.parametrize("shape", sorted(GPT2_CALLS))
def test_gpt2_attention_calls_lower_as_the_parent_commit_did(shape):
    want = GPT2_CALLS[shape]
    _b, _h, t, d = shape
    assert [fa.pick_blocks(t, t, d, jnp.bfloat16, k, True)
            for k in fa.KERNELS] == want["blocks"]
    text = lowered_text(*shape)
    assert text.count("pallas_call[") == 3
    assert (len(text), hashlib.sha256(text.encode()).hexdigest()) \
        == (want["chars"], want["sha256"])


if __name__ == "__main__":
    print(json.dumps({str(s): hashlib.sha256(
        lowered_text(*s).encode()).hexdigest() for s in GPT2_CALLS},
        indent=1))
