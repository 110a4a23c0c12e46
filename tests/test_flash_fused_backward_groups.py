"""The fused flash backward (`flash_bwd`) with more than one query head a
key/value head: interpret mode against `_xla_attention`'s gradients at the
five cells' groups under every visibility rule the kernel takes, against
the split pair to the bit, what `vmem_bytes` weighs for a group, and which
backward the five cells' calls get."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa

from _flash_cases import (GROUPED_CELL_CALLS, _flash_grads, _inputs, _mask,
                          _worst, call_shapes)

#: SDAR 32:4, SmallThinker 28:4, LFM2 32:8, Nemotron 32:2, Phi 20:10
GROUPS = [2, 4, 7, 8, 16]

#: name -> (Tq, Tk, D, Dv, causal, mask, block_diffusion)
CASES = {
    "causal": (64, 64, 16, 16, True, None, None),
    "no_mask": (64, 64, 16, 16, False, None, None),
    "key_mask": (64, 64, 16, 16, False, "key", None),
    "causal_key_mask": (64, 64, 16, 16, True, "key", None),
    "block_diffusion": (64, 64, 16, 16, False, None, (4, 32)),
    "dv_wider": (64, 64, 16, 32, True, None, None),
    "dv_narrower": (64, 64, 24, 16, True, None, None),
    "tq_under_tk": (32, 64, 16, 16, True, None, None),
}


def _grads(fn, q, k, v, w):
    return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)
                                            * w), (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("group", GROUPS)
@pytest.mark.parametrize("case", list(CASES))
def test_grouped_fused_backward_equals_xla(case, group):
    """Two key/value heads a batch row (and two rows at the small groups:
    the interpreter's time goes with the grid), so that every index map's
    head arithmetic (`bb * group + head`, `bb // hkv`) is off 0."""
    tq, tk, d, dv, causal, mask_mode, bd = CASES[case]
    b = 2 if group <= 4 else 1
    q, k, v, w = _inputs(b, 2 * group, 2, tq, tk, d, dv, seed=group)
    mask = _mask(mask_mode, b, tq, tk, jnp.float32)
    path = fa.attention_path(q.shape, k.shape, v.shape, q.dtype, causal,
                             None, True, block_q=16, block_k=16,
                             block_diffusion=bd)
    assert path == ("flash", ((16, 16),) * 2, None, "fused")
    got = _grads(lambda q, k, v: fa.flash_attention(
        q, k, v, mask=mask, scale=0.25, causal=causal, block_q=16,
        block_k=16, interpret=True, block_diffusion=bd), q, k, v, w)
    want = _grads(lambda q, k, v: fa._xla_attention(
        q, k, v, mask, 0.25, causal, None, bd), q, k, v, w)
    assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
    assert _worst(got, want) < 1e-5


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("group,tile,mask_mode,causal", [
    (2, (16, 16), "none", True), (7, (8, 16), "key", False),
    (8, (16, 8), "qk", True), (16, (16, 16), "none", False)])
def test_grouped_fused_backward_equals_the_split_kernels_to_the_bit(
        group, tile, mask_mode, causal, dtype):
    """Equal tiles: dK/dV summed over the group's heads and then ascending
    q-blocks, dQ over ascending k-blocks, in both forms."""
    q, k, v, w = _inputs(1, 2 * group, 2, 32, 64, 16, 16, jnp.dtype(dtype),
                         seed=17)
    mask = _mask(mask_mode, 1, 32, 64, jnp.dtype(dtype))
    fused = _flash_grads((tile, tile), q, k, v, w, mask, causal)
    split = _flash_grads((tile,) * 3, q, k, v, w, mask, causal)
    for a, b_ in zip(fused, split):
        assert a.dtype == b_.dtype == jnp.dtype(dtype)
        assert (np.asarray(a, np.float32) == np.asarray(b_, np.float32)).all()


def test_block_diffusion_group_equals_the_split_kernels_to_the_bit():
    q, k, v, w = _inputs(1, 8, 1, 128, 128, 16, 16, seed=19)

    def grads(blocks):
        return jax.jit(jax.grad(lambda q, k, v: jnp.sum(fa._flash(
            q, k, v, None, 0.25, False, blocks, True, None, (4, 64)) * w),
            (0, 1, 2)))(q, k, v)
    for a, b_ in zip(grads(((32, 32),) * 2), grads(((32, 32),) * 3)):
        assert (np.asarray(a) == np.asarray(b_)).all()


def test_a_groups_vmem_counts_the_key_value_rows_and_the_tile_follows():
    """`vmem_bytes("bwd", ...)` at SDAR's and SmallThinker's call, by its
    parts: the blocks twice (q and dO, k and dK, v and dV, dQ's whole row,
    the two statistics rows, the mask tile), dQ's float32 row, dK's and
    dV's float32 rows as long as the kv head's, and eight score tiles."""
    mib = 2.0 ** 20
    bq = bk = 1024
    t = 16384
    blocks = (6 * bq * 128 + t * 128) * 2 + 2 * 8 * bq * 4 + bq * bk * 2
    want = 2 * blocks + (2 * t * 128 + t * 128) * 4 + 8 * bq * bk * 4
    got = fa.vmem_bytes("bwd", bq, bk, 128, 2, "qk", tq=t, tk=t)
    assert got == want and round(got / mib, 1) == 71.1
    assert got == fa.vmem_bytes("bwd", bq, bk, 128, 2, "qk",
                                **fa._bwd_rows(t, t, 8)) < fa._VMEM_CEILING
    # one query head a kv head holds a k-block's accumulators, as it did
    assert fa._bwd_rows(t, t, 1) == {"tq": t, "tk": 0}
    assert got - fa.vmem_bytes("bwd", bq, bk, 128, 2, "qk", tq=t) \
        == 2 * (t - bk) * 128 * 4
    # the rows of a key length under a block change nothing
    assert fa.vmem_bytes("bwd", bq, bk, 128, 2, "qk", tq=t, tk=bk) \
        == fa.vmem_bytes("bwd", bq, bk, 128, 2, "qk", tq=t)
    # unequal widths: dK's row at the q/k width's lanes, dV's at the values'
    assert fa.vmem_bytes("bwd", bq, bk, 64, 2, "qk", 256, tq=t, tk=t) - got \
        == (2 * 2 * 2 * bk + 2 * 2 * bq + 4 * t) * 128
    # the call asks Mosaic for that much, and runs three inner axes in order
    params = fa._compiler_params("bwd", bq, bk, 128, jnp.bfloat16, "none",
                                 None, t, t)
    assert params.vmem_limit_bytes == fa.vmem_bytes(
        "bwd", bq, bk, 128, 2, "none", tq=t, tk=t)
    assert tuple(params.dimension_semantics) \
        == ("parallel", "arbitrary", "arbitrary", "arbitrary")
    # twice the length: the tile is halved on the query side and fits;
    # four times: no tile fits and `backward_rule` keeps the pair
    for length, tile, rule in ((2 * t, (512, 1024), "fused"),
                               (4 * t, (128, 128), "split: vmem")):
        assert fa.pick_blocks(length, length, 128, "bfloat16", "bwd", True,
                              group=8) == tile
        assert (fa.vmem_bytes("bwd", *tile, 128, 2, "qk", None,
                              **fa._bwd_rows(length, length, 8))
                <= fa._VMEM_CEILING) == (rule == "fused")
        assert fa.backward_rule((1, 8, length, 128), (1, 1, length, 128),
                                (1, 1, length, 128), "bfloat16", True,
                                None) == rule
    # one query head a kv head: the tile is the one it was (the rule alone
    # weighs dQ's row there)
    assert fa.pick_blocks(4 * t, 4 * t, 128, "bfloat16", "bwd", True) \
        == (1024, 1024)


@pytest.mark.parametrize("call", list(GROUPED_CELL_CALLS))
def test_which_backward_the_cells_grouped_calls_get(call):
    _b, hq, hkv, t, d, dv, window, rule = GROUPED_CELL_CALLS[call]
    shapes = call_shapes(GROUPED_CELL_CALLS[call])
    got = fa.backward_rule(*shapes, "bfloat16", rule is None, window, rule)
    assert got == ("fused" if window is None else "split: window")
    if window is not None:
        return
    tile = fa.pick_blocks(t, t, d, "bfloat16", "bwd", rule is None, dv=dv,
                          block_diffusion=rule, group=hq // hkv)
    assert tile == (1024, 1024)
    need = fa.vmem_bytes("bwd", *tile, d, 2, "qk", dv,
                         **fa._bwd_rows(t, t, hq // hkv))
    assert fa._VMEM_DEFAULT < need < fa._VMEM_CEILING
    # float32 operands stop at 512 and fit too
    assert fa.backward_rule(*shapes, "float32", rule is None, None, rule) \
        == "fused"


def test_backward_rule_reads_shapes_alone(monkeypatch):
    """No argument, environment variable or name moves the answer: the
    signature is the parent's, and the variables that once chose kernels
    change nothing."""
    import inspect
    assert list(inspect.signature(fa.backward_rule).parameters) == [
        "q_shape", "k_shape", "v_shape", "dtype", "causal", "window",
        "block_diffusion"]
    shapes = ((1, 32, 16384, 128), (1, 4, 16384, 128), (1, 4, 16384, 128))
    before = fa.attention_path(*shapes, "bfloat16", True, None, False)
    for name in ("PADDLE_TPU_FLASH_BACKWARD", "PADDLE_TPU_ATTN_IMPL",
                 "PADDLE_TPU_FLASH_BLOCK_Q", "PADDLE_TPU_FLASH_BLOCK_K"):
        monkeypatch.setenv(name, "split")
    assert fa.attention_path(*shapes, "bfloat16", True, None, False) \
        == before == ("flash", ((1024, 1024),) * 2, None, "fused")
    source = inspect.getsource(fa)
    assert "os.environ" not in source and "getenv" not in source
    assert '"split: group"' not in source
