"""The block-diffusion visibility rule of the attention op: the flash kernels
(interpret mode) and the XLA body against an oracle that writes the mask out
pair by pair, forward and every gradient; which tiles run, which blocks the
skipped grid steps name, what a call outside the rule's space is told, and
what a call without the rule still plans."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.framework import obs
from paddle_tpu.ops import attention_ops
from paddle_tpu.ops.pallas import flash_attention as fa


def oracle_mask(length, t):
    """Bool (2T, 2T), the three lines of the rule, row by row."""
    row = np.arange(2 * t)
    noisy, blk = row < t, row % t // length
    seen = np.zeros((2 * t, 2 * t), bool)
    for r in range(2 * t):
        for s in range(2 * t):
            if not noisy[r] and not noisy[s]:
                seen[r, s] = blk[s] <= blk[r]
            elif noisy[r] and noisy[s]:
                seen[r, s] = blk[s] == blk[r]
            elif noisy[r]:
                seen[r, s] = blk[s] < blk[r]
    return seen


def oracle(q, k, v, seen, scale):
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, 1), jnp.repeat(v, group, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * scale
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


def _inputs(hq, hkv, rows, d=16, dtype=jnp.float32, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(1, h, rows, d), dtype)
                 for h in (hq, hkv, hkv, hq))


# (L, T, block_q, block_k): tiles under, at and over a block, square and not
TILES = [(4, 32, 8, 8), (4, 32, 16, 8), (4, 32, 32, 32), (32, 64, 8, 16),
         (32, 64, 32, 32), (32, 64, 64, 64)]


@pytest.mark.parametrize("group", [1, 8])
@pytest.mark.parametrize("length,t,bq,bk", TILES)
def test_the_kernels_equal_the_explicit_mask(length, t, bq, bk, group):
    q, k, v, g = _inputs(group, 1, 2 * t)
    seen = jnp.asarray(oracle_mask(length, t))
    path = fa.attention_path(q.shape, k.shape, v.shape, q.dtype, False, None,
                             True, block_q=bq, block_k=bk,
                             block_diffusion=(length, t))
    assert path.path == "flash"
    # the fused backward whatever the group: both kernels at the tile
    assert path.backward == "fused" and path.blocks == ((bq, bk),) * 2
    got, vjp = jax.vjp(lambda *a: fa.flash_attention(
        *a, scale=0.25, block_q=bq, block_k=bk, interpret=True,
        block_diffusion=(length, t)), q, k, v)
    want, want_vjp = jax.vjp(lambda *a: oracle(*a, seen, 0.25), q, k, v)
    for mine, theirs in zip((got,) + vjp(g), (want,) + want_vjp(g)):
        np.testing.assert_allclose(mine, theirs, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("length,t", [(4, 32), (32, 64), (3, 24)])
def test_the_xla_bodies_and_visible_mask_equal_the_explicit_mask(length, t):
    q, k, v, g = _inputs(8, 1, 2 * t)
    seen = oracle_mask(length, t)
    np.testing.assert_array_equal(
        fa.visible_mask(2 * t, 2 * t, block_diffusion=(length, t)), seen)
    want, want_vjp = jax.vjp(lambda *a: oracle(*a, jnp.asarray(seen), 0.25),
                             q, k, v)
    for body in (fa._xla_attention, attention_ops._sdpa_xla):
        got, vjp = jax.vjp(lambda *a: body(
            *a, None, 0.25, False, None, (length, t)), q, k, v)
        for mine, theirs in zip((got,) + vjp(g), (want,) + want_vjp(g)):
            np.testing.assert_allclose(mine, theirs, rtol=2e-5, atol=2e-5)
    # every row sees a key; the pairs are T^2 + T L
    assert seen.any(1).all() and seen.sum() == t * t + t * length


def test_bfloat16_runs_the_same_rule():
    q, k, v, _g = _inputs(8, 1, 128, dtype=jnp.bfloat16)
    seen = jnp.asarray(oracle_mask(4, 64))
    got = fa.flash_attention(q, k, v, scale=0.25, block_q=32, block_k=32,
                             interpret=True, block_diffusion=(4, 64))
    want = oracle(*(a.astype(jnp.float32) for a in (q, k, v)), seen, 0.25)
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=0.03,
                               atol=0.03)


def _tile_any(seen, bq, bk):
    nq, nk = seen.shape[0] // bq, seen.shape[1] // bk
    return seen.reshape(nq, bq, nk, bk).any((1, 3))


@pytest.mark.parametrize("length,t,bq,bk", TILES + [(4, 64, 8, 32),
                                                    (3, 24, 8, 8)])
def test_the_tiles_that_run_are_the_tiles_the_rule_predicts(length, t, bq,
                                                            bk):
    """`_bd_tile_visible` is the kernels' `pl.when` and the plan's count: it
    holds exactly for the tiles some query of which sees some key; and every
    other grid step names the block of a tile of its row that runs, so a
    row's sweep fetches as many blocks as it has running tiles."""
    bd = (length, t)
    runs = _tile_any(oracle_mask(length, t), bq, bk)
    nq, nk = runs.shape
    mine = np.array([[bool(fa._bd_tile_visible(qi, kj, bq, bk, bd))
                      for kj in range(nk)] for qi in range(nq)])
    np.testing.assert_array_equal(mine, runs)
    shape = (1, 8, 2 * t, 16)
    plan = fa.plan(shape, (1, 1, 2 * t, 16), (1, 1, 2 * t, 16), False, None,
                   ((bq, bk),) * 3, "split: vmem", bd)
    fused = fa.plan(shape, (1, 1, 2 * t, 16), (1, 1, 2 * t, 16), False, None,
                    ((bq, bk),) * 2, "fused", bd)
    plan["bwd"] = fused["bwd"]
    assert plan["mask"] == fused["mask"] == "block_diffusion"
    for kernel in fa.KERNELS + fa.FUSED_KERNELS[1:]:
        assert plan[kernel]["tiles_run"] == runs.sum()
        assert plan[kernel]["tiles_grid"] == nq * nk
    # the quadrant nobody sees never runs
    assert not runs[nq // 2:, :nk // 2].any()
    for qi in range(nq):
        named = [int(fa._bd_nearest_k(qi, kj, bq, bk, bd))
                 for kj in range(nk)]
        assert all(runs[qi, kj] for kj in named)
        assert all(named[kj] == kj for kj in range(nk) if runs[qi, kj])
        fetches = 1 + sum(a != b for a, b in zip(named, named[1:]))
        assert fetches == runs[qi].sum()
    for kj in range(nk):
        named = [int(fa._bd_nearest_q(kj, qi, bq, bk, bd))
                 for qi in range(nq)]
        assert all(runs[qi, kj] for qi in named)
        assert all(named[qi] == qi for qi in range(nq) if runs[qi, kj])
        fetches = 1 + sum(a != b for a, b in zip(named, named[1:]))
        assert fetches == runs[:, kj].sum()


def test_at_the_cells_size_a_quarter_of_the_square_and_the_diagonals_run():
    q_shape, kv_shape = (1, 32, 16384, 128), (1, 4, 16384, 128)
    bd = (4, 8192)
    path = fa.attention_path(q_shape, kv_shape, kv_shape, jnp.bfloat16,
                             False, None, False, block_diffusion=bd)
    assert path == ("flash", ((1024, 1024),) * 2, None, "fused")
    plan = fa.plan(q_shape, kv_shape, kv_shape, False, None, path.blocks,
                   path.backward, bd)
    assert plan["group"] == 8 and plan["block_diffusion"] == [4, 8192]
    for kernel in fa.FUSED_KERNELS:
        # 8 x 8 tiles a quadrant: two lower triangles with their diagonals
        # (36 each) and the noisy quadrant's 8 diagonal tiles
        assert (plan[kernel]["tiles_run"], plan[kernel]["tiles_grid"]) \
            == (80, 256)
        assert plan[kernel]["grid_inner"] == 16
    # one query head a key head takes the same backward at the same tiles
    assert fa.attention_path(kv_shape, kv_shape, kv_shape, jnp.bfloat16,
                             False, None, False, block_diffusion=bd) == path
    # the VMEM a tile asks for is a plain call's; the fused kernel's for a
    # group weighs the rows of all 2T = 16,384 queries and keys, not T's
    assert fa.pick_blocks(16384, 16384, 128, jnp.bfloat16, "bwd_dkv",
                          block_diffusion=bd) == (1024, 1024)
    assert fa.pick_blocks(16384, 16384, 128, jnp.bfloat16, "bwd",
                          block_diffusion=bd, group=8) == (1024, 1024)
    assert fa.pick_blocks(32768, 32768, 128, jnp.bfloat16, "bwd",
                          block_diffusion=(4, 16384), group=8) == (512, 1024)


def test_a_tile_that_does_not_divide_the_half_is_refused_with_a_message():
    q, k, v, _g = _inputs(2, 1, 96)
    with pytest.raises(ValueError, match="does not divide the 48 rows"):
        fa.flash_attention(q, k, v, block_q=32, interpret=True,
                           block_diffusion=(4, 48))
    with pytest.raises(ValueError, match="does not divide the 48 rows"):
        fa.attention_path(q.shape, k.shape, v.shape, q.dtype, False, None,
                          True, block_k=32, block_diffusion=(4, 48))
    # the rule's own tile divides T: 48 rows a side here
    path = fa.attention_path(q.shape, k.shape, v.shape, q.dtype, False, None,
                             True, block_diffusion=(4, 48))
    assert set(path.blocks) == {(48, 48)}


@pytest.mark.parametrize("kwargs,message", [
    (dict(causal=True), "no causal, window or additive mask"),
    (dict(causal=True, window=8), "no causal, window or additive mask"),
    (dict(mask=jnp.zeros((1, 1, 1, 64))), "no causal, window or additive"),
    (dict(block_diffusion=(5, 32)), "blocks of L that divide T"),
    (dict(block_diffusion=(4, 16)), "2T = 32 query and key rows"),
    (dict(block_diffusion=(0, 32)), "blocks of L that divide T"),
])
def test_a_call_outside_the_rules_space_is_told_why(kwargs, message):
    q, k, v, _g = _inputs(2, 1, 64)
    kwargs.setdefault("block_diffusion", (4, 32))
    with pytest.raises(ValueError, match=message):
        fa.flash_attention(q, k, v, interpret=True, **kwargs)


#: `plan` of two calls as the tree before the rule gave it (PR 47), to which
#: only the rule's name has been added; since PR 49 the full layer's
#: backward is the fused kernel and the window layer's rule is the window
OLD_PLANS = {
    None: {"group": 2, "d_qk": 64, "d_v": 128, "window": None,
           "causal": True, "backward": "fused", "mask": "causal",
           "fwd": {"block_q": 1024, "block_k": 1024, "grid_inner": 8,
                   "tiles_visited": 36, "tiles_skipped_causal": 28,
                   "tiles_skipped_window": 0}},
    512: {"group": 2, "d_qk": 64, "d_v": 128, "window": 512, "causal": True,
          "backward": "split: window", "mask": "window",
          "fwd": {"block_q": 512, "block_k": 512, "grid_inner": 2,
                  "tiles_visited": 31, "tiles_skipped_causal": 120,
                  "tiles_skipped_window": 105}},
}


@pytest.mark.parametrize("window", [None, 512])
def test_a_call_without_the_rule_plans_what_it_planned(window):
    shapes = ((2, 20, 8192, 64), (2, 10, 8192, 64), (2, 10, 8192, 128))
    path = fa.attention_path(*shapes, jnp.bfloat16, True, window, False)
    got = fa.plan(*shapes, True, window, path.blocks, path.backward)
    want = OLD_PLANS[window]
    assert {k: got[k] for k in want} == want
    assert set(got) == set(want) | (
        {"bwd"} if window is None else {"bwd_dkv", "bwd_dq"})
    assert fa.plan(*shapes, False, None, path.blocks)["mask"] == "none"
    assert fa.pick_blocks(8192, 8192, 64, jnp.bfloat16, "bwd_dkv", True) \
        == fa.pick_blocks(8192, 8192, 64, jnp.bfloat16, "bwd_dkv", True,
                          block_diffusion=None)


def _op_program(block_diffusion, rows=64, impl="auto"):
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        q = layers.data("q", [8, rows, 16], dtype="float32")
        k = layers.data("k", [1, rows, 16], dtype="float32")
        v = layers.data("v", [1, rows, 16], dtype="float32")
        out = layers.fused_attention(q, k, v, scale=0.25, impl=impl,
                                     block_diffusion=block_diffusion)
    return main, startup, out


@pytest.mark.parametrize("impl", ["auto", "flash", "xla"])
def test_the_op_runs_the_rule_on_every_path(impl):
    q, k, v, _g = _inputs(8, 1, 64)
    main, startup, out = _op_program((4, 32), impl=impl)
    exe = pt.Executor()
    exe.run(startup)
    got, = exe.run(main, feed={"q": np.asarray(q), "k": np.asarray(k),
                               "v": np.asarray(v)}, fetch_list=[out])
    want = oracle(q, k, v, jnp.asarray(oracle_mask(4, 32)), 0.25)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_the_op_carries_the_attr_only_where_the_rule_is_given():
    with_rule, _s, _o = _op_program((4, 32))
    without, _s, _o = _op_program(None)
    ops = [[op for op in prog.global_block().ops
            if op.type == "scaled_dot_product_attention"][0]
           for prog in (with_rule, without)]
    assert ops[0].attrs["block_diffusion"] == [4, 32]
    assert "block_diffusion" not in ops[1].attrs
    with pytest.raises(ValueError, match="block_diffusion"):
        attention_ops._sdpa(
            None, {"Q": [jnp.zeros((1, 2, 64, 8))],
                   "K": [jnp.zeros((1, 2, 64, 8))],
                   "V": [jnp.zeros((1, 2, 64, 8))]},
            {"impl": "ring", "block_diffusion": [4, 32]})


def test_the_flash_calls_lower_under_the_scope_and_record_the_plan():
    """A block-diffusion op lowers its kernels under the inner scope
    `block_diffusion_attention` (what a trace reader splits by), and with
    obs on records one `flash.plan` that names the rule and counts tiles."""
    q, k, v, _g = _inputs(8, 1, 512)
    kern = attention_ops._sdpa
    obs.clear()
    obs.enable()
    try:
        text = str(jax.jit(lambda q, k, v: kern(
            None, {"Q": [q], "K": [k], "V": [v]},
            {"impl": "flash", "block_diffusion": [4, 256]})["Out"]).lower(
                q, k, v).as_text(debug_info=True))
        plans = obs.spans(name="flash.plan")
    finally:
        obs.disable()
        obs.clear()
    assert attention_ops.BLOCK_DIFFUSION_SCOPE == "block_diffusion_attention"
    assert "block_diffusion_attention" in text
    assert attention_ops.WINDOW_SCOPE not in text
    assert len(plans) == 1
    labels = plans[0]["labels"]
    assert labels["mask"] == "block_diffusion" and labels["group"] == 8
    assert 0 < labels["fwd"]["tiles_run"] <= labels["fwd"]["tiles_grid"]


@pytest.mark.parametrize("period", [None, 16])
def test_rope_qk_norm_counts_positions_from_0_in_every_period(period):
    rng = np.random.RandomState(3)
    q = rng.randn(2, 32, 4 * 8).astype(np.float32)
    k = rng.randn(2, 32, 1 * 8).astype(np.float32)
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        qv = layers.data("q", [32, 32], dtype="float32")
        kv = layers.data("k", [32, 8], dtype="float32")
        outs = layers.rope_qk_norm(qv, kv, 8, theta=100.0,
                                   q_norm_attr=False, k_norm_attr=False,
                                   position_period=period)
    op = [op for op in main.global_block().ops
          if op.type == "rope_qk_norm"][0]
    assert ("position_period" in op.attrs) == (period is not None)
    exe = pt.Executor()
    exe.run(startup)
    got_q, got_k = exe.run(main, feed={"q": q, "k": k},
                           fetch_list=list(outs))

    def turned(x, heads):
        x = x.reshape(2, 32, heads, 8).transpose(0, 2, 1, 3)
        pos = np.arange(32) % (period or 32)
        angle = pos[:, None] * 100.0 ** (-np.arange(0, 8, 2) / 8.0)[None, :]
        cos, sin = np.cos(angle), np.sin(angle)
        x1, x2 = x[..., :4], x[..., 4:]
        return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              -1)

    np.testing.assert_allclose(got_q, turned(q, 4), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_k, turned(k, 1), rtol=1e-5, atol=1e-5)
    if period:
        # the second copy is turned as the first is
        same = np.concatenate([q[:, :16], q[:, :16]], 1)
        got, _k = exe.run(main, feed={"q": same, "k": k},
                          fetch_list=list(outs))
        np.testing.assert_allclose(got[:, :, 16:], got[:, :, :16],
                                   rtol=1e-6, atol=1e-6)
