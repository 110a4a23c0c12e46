"""The flash kernels' grouped heads, sliding window and unequal q/k and
value widths (interpret mode against the XLA path), the errors outside the
supported space, `flash.plan`, the fused backward kernel against the two
it stands for, and the guard that the cells' attention calls lower as
recorded."""
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
    assert labels["backward"] == "split: group" and "bwd" not in labels


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


# ---------------------------------------------------------------------------
# the fused backward (`flash_bwd`) against the split kernels at equal tiles
# ---------------------------------------------------------------------------

def _mask(mode, b, tq, tk, dtype):
    if mode == "key":
        m = np.zeros((b, 1, 1, tk), np.float32)
        m[..., 3 * tk // 4:] = -1e4
        return jnp.asarray(m, dtype)
    if mode == "qk":
        return (0.5 * jax.random.normal(jax.random.PRNGKey(9),
                                        (b, 1, tq, tk))).astype(dtype)
    return None


def _flash_grads(blocks, q, k, v, w, mask, causal):
    return jax.grad(lambda q, k, v: jnp.sum(fa._flash(
        q, k, v, mask, 0.25, causal, blocks, True, None).astype(jnp.float32)
        * w), (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("mask_mode", ["none", "key", "qk"])
@pytest.mark.parametrize("tq,tk,tile,d,dv", [
    (64, 64, (16, 16), 16, 16), (32, 64, (8, 16), 16, 16),
    (32, 64, (16, 8), 16, 16),
    # Dv != D: a small pair, and latent attention's decompressed heads
    (64, 64, (16, 16), 24, 16), (32, 64, (8, 16), 24, 16),
    (64, 64, (16, 16), 192, 128), (32, 64, (8, 16), 192, 128)])
def test_fused_backward_equals_the_split_kernels_to_the_bit(
        dtype, causal, mask_mode, tq, tk, tile, d, dv):
    """Equal tiles: both run `_bwd_p_ds` and then the same dots, dK/dV
    summed over ascending q-blocks and dQ over ascending k-blocks in both,
    so every gradient is bit-equal, whatever the two widths; and within
    float32 rounding (bfloat16: its 1e-2) of the float32 XLA oracle."""
    q, k, v, w = _inputs(2, 2, 2, tq, tk, d, dv, jnp.dtype(dtype), seed=11)
    mask = _mask(mask_mode, 2, tq, tk, jnp.dtype(dtype))
    fused = _flash_grads((tile, tile), q, k, v, w, mask, causal)
    split = _flash_grads((tile,) * 3, q, k, v, w, mask, causal)
    for a, b_ in zip(fused, split):
        assert a.dtype == b_.dtype == jnp.dtype(dtype)
        assert (np.asarray(a, np.float32) == np.asarray(b_, np.float32)).all()
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    oracle = jax.grad(lambda q, k, v: jnp.sum(fa._xla_attention(
        q, k, v, None if mask is None else mask.astype(jnp.float32), 0.25,
        causal) * w), (0, 1, 2))(*f32)
    assert _worst([g.astype(jnp.float32) for g in fused], oracle) \
        < (1e-5 if dtype == "float32" else 1e-2)


def test_fused_backward_with_unequal_backward_tiles_matches_the_split():
    """The split kernels at their own (different) tiles sum in another
    order than the fused one at its tile: equal to float32 rounding, 1e-6
    of the gradient's range, not to the bit."""
    q, k, v, w = _inputs(1, 2, 2, 64, 64, 16, 16, seed=13)
    fused = _flash_grads(((16, 16), (32, 16)), q, k, v, w, None, True)
    split = _flash_grads(((16, 16), (16, 32), (8, 8)), q, k, v, w, None,
                         True)
    assert _worst(fused, split) < 1e-6


@pytest.mark.parametrize("hq,hkv,d,dv,window,fused", [
    (2, 2, 16, 16, None, True),
    (2, 2, 24, 16, None, True),         # Dv != D, either way round
    (2, 2, 16, 32, None, True),
    (4, 2, 16, 16, None, False),        # grouped heads
    (4, 2, 24, 16, None, False),
    (2, 2, 24, 16, 32, False)])         # a window
def test_which_backward_a_call_differentiates_through(hq, hkv, d, dv,
                                                      window, fused):
    q, k, v, _w = _inputs(1, hq, hkv, 64, 64, d, dv)
    text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(
        fa.flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                           window=window, interpret=True)),
        (0, 1, 2)))(q, k, v))
    assert text.count("pallas_call[") == (2 if fused else 3)
    assert ("name=flash_bwd\n" in text) == fused
    assert ("flash_bwd_dkv" in text) == (not fused)
    assert ("flash_bwd_dq" in text) == (not fused)


# ---------------------------------------------------------------------------
# The jaxpr of the cells' attention calls (forward and backward: kernel
# bodies, grids, block shapes, the VMEM request) with every BlockSpec's
# index map, held by digest. gpt2's two calls: the forward ("fwd_*") is
# as the parent commit (PR 25) traced it; the whole call was re-recorded
# in PR 30, when one `flash_bwd` took the place of `flash_bwd_dkv` +
# `flash_bwd_dq` (3 pallas_calls -> 2; PERF.md, PR 30). Phi's calls keep
# the two kernels ("split: group"), so their digests are the parent's.
# The Kimi cells' call (D 192 | Dv 128) was recorded in PR 43, when the
# fused kernel took unequal widths; its forward is as PR 41 traced it.
# After a deliberate change to one of these paths, print the new digests
# with `python tests/test_flash_modes.py` and say in PERF.md why.
# ---------------------------------------------------------------------------

GPT2_CALLS = {
    (4, 12, 4096, 64): {
        "sha256": "0967609dfd4cfa0338b07a01a25861d4c2a658d9d4f9ac386cb48f8b1"
                  "8bece7d", "chars": 35126,
        "fwd_sha256": "741646151b98e5e43927993a659403340f335e16ad650d68ccfa9"
                      "5d83ea17f78", "fwd_chars": 12447,
        "blocks": [(1024, 1024), (1024, 1024)]},
    (16, 12, 1024, 64): {
        "sha256": "5406ef045bcd9d0c3d0652ad135f616e046bbab6d6701fb486e46572e"
                  "7e96bc2", "chars": 34512,
        "fwd_sha256": "59072170dde3fcf8c49b9b255be1bcdb347a23e12e9fd93ce5342"
                      "9818ddcdfc9", "fwd_chars": 12467,
        "blocks": [(1024, 1024), (512, 512)]},
}

PHI_SHAPES = ((2, 20, 8192, 64), (2, 10, 8192, 64), (2, 10, 8192, 128))
PHI_CALLS = {   # by window: the window layer; the full and cross layers
    512: {"sha256": "dfa607c7a19b7d3ad2a5b529bd113481f53d1bb4aea493df8de988ad6"
                    "38a2eba", "chars": 58129,
          "blocks": [(512, 512)] * 3},
    None: {"sha256": "9904d1e1e6d77351f99711e247503cf1004bd7e7b1583c54396119b92"
                     "501a0e3", "chars": 51164,
           "blocks": [(1024, 1024)] * 3},
}


KIMI_SHAPES = ((2, 16, 8192, 192), (2, 16, 8192, 192), (2, 16, 8192, 128))
KIMI_CALL = {
    "sha256": "2c2688081f6e954ae74038e036c2d6b5f609b0f29e8d663fbf5537518e34f"
              "11c", "chars": 36746,
    "fwd_sha256": "888f361976603edf0ca93588f4d7de459e2921a5760378067b9cff079"
                  "a9fb275", "fwd_chars": 12932,
    "blocks": [(1024, 1024), (1024, 1024)]}


def lowered_text(q_shape, k_shape=None, v_shape=None, window=None,
                 backward=True):
    q, k, v = (jax.ShapeDtypeStruct(s or q_shape, jnp.bfloat16)
               for s in (q_shape, k_shape, v_shape))

    def forward(q, k, v):
        return fa.flash_attention(q, k, v, scale=q_shape[-1] ** -0.5,
                                  causal=True, window=window,
                                  interpret=False)

    def call(q, k, v):
        out, vjp = jax.vjp(forward, q, k, v)
        return out, vjp(out)

    closed = jax.make_jaxpr(call if backward else forward)(q, k, v)
    parts = [str(closed)]
    for eqn in closed.jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            parts.extend(str(bm.index_map_jaxpr)
                         for bm in eqn.params["grid_mapping"].block_mappings)
    # source positions move with every edit of the file; nothing else does
    return re.sub(r"/[^\s:\"']*\.py:\d+", "", "\n".join(parts))


def digest(text):
    return len(text), hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("shape", sorted(GPT2_CALLS))
def test_gpt2_attention_calls_lower_as_recorded(shape):
    want = GPT2_CALLS[shape]
    _b, _h, t, d = shape
    got = fa.attention_path(shape, shape, shape, jnp.bfloat16, True, None,
                            False)
    assert got.backward == "fused" and list(got.blocks) == want["blocks"]
    assert [fa.pick_blocks(t, t, d, jnp.bfloat16, k, True)
            for k in fa.FUSED_KERNELS] == want["blocks"]
    text = lowered_text(shape)
    assert text.count("pallas_call[") == 2
    for name, there in (("flash_fwd", True), ("flash_bwd", True),
                        ("flash_bwd_dkv", False), ("flash_bwd_dq", False)):
        assert ("name=%s\n" % name in text) == there, name
    assert digest(text) == (want["chars"], want["sha256"])
    # the forward kernel is the parent commit's
    assert digest(lowered_text(shape, backward=False)) \
        == (want["fwd_chars"], want["fwd_sha256"])


def test_the_latent_attention_call_lowers_to_the_fused_backward():
    """The two Kimi cells' call, D 192 | Dv 128: one `flash_bwd` where the
    parent commit (PR 41) ran the split pair for its widths alone (3
    pallas_calls, 45,714 characters there); the forward is the parent's."""
    want = KIMI_CALL
    got = fa.attention_path(*KIMI_SHAPES, jnp.bfloat16, True, None, False)
    assert got.backward == "fused" and list(got.blocks) == want["blocks"]
    text = lowered_text(*KIMI_SHAPES)
    assert text.count("pallas_call[") == 2
    for name, there in (("flash_fwd", True), ("flash_bwd", True),
                        ("flash_bwd_dkv", False), ("flash_bwd_dq", False)):
        assert ("name=%s\n" % name in text) == there, name
    assert digest(text) == (want["chars"], want["sha256"])
    assert digest(lowered_text(*KIMI_SHAPES, backward=False)) \
        == (want["fwd_chars"], want["fwd_sha256"])


@pytest.mark.parametrize("window", sorted(PHI_CALLS, key=str))
def test_phi_attention_calls_lower_as_the_parent_commit_did(window):
    want = PHI_CALLS[window]
    got = fa.attention_path(*PHI_SHAPES, jnp.bfloat16, True, window, False)
    assert got.backward == "split: group"
    assert list(got.blocks) == want["blocks"]
    text = lowered_text(*PHI_SHAPES, window=window)
    assert text.count("pallas_call[") == 3
    assert "name=flash_bwd\n" not in text
    assert digest(text) == (want["chars"], want["sha256"])


if __name__ == "__main__":
    out = {str(s): digest(lowered_text(s)) for s in GPT2_CALLS}
    out.update({"phi window %s" % w: digest(lowered_text(*PHI_SHAPES,
                                                         window=w))
                for w in PHI_CALLS})
    out["kimi"] = digest(lowered_text(*KIMI_SHAPES))
    print(json.dumps(out, indent=1))
