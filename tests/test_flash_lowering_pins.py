"""The guard that the cells' attention calls lower as recorded: the jaxpr
of each call, forward and backward, held by digest."""
import hashlib
import json
import re

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa

from _flash_cases import GROUPED_CELL_CALLS, call_shapes

# ---------------------------------------------------------------------------
# The jaxpr of the cells' attention calls (forward and backward: kernel
# bodies, grids, block shapes, the VMEM request) with every BlockSpec's
# index map, held by digest. gpt2's two calls: the forward ("fwd_*") is
# as the parent commit (PR 25) traced it; the whole call was re-recorded
# in PR 30, when one `flash_bwd` took the place of `flash_bwd_dkv` +
# `flash_bwd_dq` (3 pallas_calls -> 2; PERF.md, PR 30). The Kimi cells'
# call (D 192 | Dv 128) was recorded in PR 43, when the fused kernel took
# unequal widths; its forward is as PR 41 traced it. Phi's full and cross
# layers (group 2) and SDAR's call (group 8 under the block-diffusion
# rule) were re-recorded in PR 49, when the fused kernel took grouped
# heads (3 pallas_calls -> 2: 51,164 and 88,189 characters at the parent);
# Phi's window layer keeps the two kernels (now "split: window") and the
# parent's digest, and every group-1 digest above is the parent's too.
# After a deliberate change to one of these paths, print the new digests
# with `python tests/test_flash_lowering_pins.py` and say in PERF.md why.
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
          "blocks": [(512, 512)] * 3, "backward": "split: window"},
    None: {"sha256": "4ccbd0ea649af216c6b8fbf5bff5704fb43bd6b57890a88b53fcd83ef"
                     "6dc889c", "chars": 36029,
           "blocks": [(1024, 1024)] * 2, "backward": "fused"},
}


KIMI_SHAPES = ((2, 16, 8192, 192), (2, 16, 8192, 192), (2, 16, 8192, 128))
KIMI_CALL = {
    "sha256": "2c2688081f6e954ae74038e036c2d6b5f609b0f29e8d663fbf5537518e34f"
              "11c", "chars": 36746,
    "fwd_sha256": "888f361976603edf0ca93588f4d7de459e2921a5760378067b9cff079"
                  "a9fb275", "fwd_chars": 12932,
    "blocks": [(1024, 1024), (1024, 1024)]}


SDAR_SHAPES = ((1, 32, 16384, 128), (1, 4, 16384, 128), (1, 4, 16384, 128))
SDAR_RULE = (4, 8192)
SDAR_CALL = {
    "sha256": "9eaba8592d6791cad24f0f65dbdbed6d0ae29a29c864c0aea8badb07a2ae9"
              "1f4", "chars": 62639,
    "blocks": [(1024, 1024)] * 2}

#: the cells' grouped calls that take no window (`_flash_cases`)
GROUPED_CALLS = {name: call for name, call in GROUPED_CELL_CALLS.items()
                 if call[6] is None}


def lowered(q_shape, k_shape=None, v_shape=None, window=None,
            backward=True, block_diffusion=None):
    """The call's closed jaxpr, forward with its pullback or alone."""
    q, k, v = (jax.ShapeDtypeStruct(s or q_shape, jnp.bfloat16)
               for s in (q_shape, k_shape, v_shape))

    def forward(q, k, v):
        if block_diffusion is not None:
            return fa.flash_attention(q, k, v, scale=q_shape[-1] ** -0.5,
                                      interpret=False,
                                      block_diffusion=block_diffusion)
        return fa.flash_attention(q, k, v, scale=q_shape[-1] ** -0.5,
                                  causal=True, window=window,
                                  interpret=False)

    def call(q, k, v):
        out, vjp = jax.vjp(forward, q, k, v)
        return out, vjp(out)

    return jax.make_jaxpr(call if backward else forward)(q, k, v)


def lowered_text(*shapes, **how):
    closed = lowered(*shapes, **how)
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
def test_phi_attention_calls_lower_as_recorded(window):
    """The window layer as the parent commit (PR 48) lowered it, to the
    byte; the full and cross layers through the fused kernel's group
    grid."""
    want = PHI_CALLS[window]
    got = fa.attention_path(*PHI_SHAPES, jnp.bfloat16, True, window, False)
    assert got.backward == want["backward"]
    assert list(got.blocks) == want["blocks"]
    text = lowered_text(*PHI_SHAPES, window=window)
    assert text.count("pallas_call[") == len(want["blocks"])
    assert ("name=flash_bwd\n" in text) == (window is None)
    assert ("name=flash_bwd_dkv\n" in text) == (window is not None)
    assert digest(text) == (want["chars"], want["sha256"])


def test_the_block_diffusion_call_lowers_as_recorded():
    """The SDAR cell's call (PR 48): 32 query heads on 4 key heads of 128
    over the 16,384 rows of an 8,192-token document's two copies in blocks
    of 4; since PR 49 the fused backward for its group of 8, both kernels
    at 1024 x 1024, the whole square as the grid with the rule in the
    index maps."""
    want = SDAR_CALL
    got = fa.attention_path(*SDAR_SHAPES, jnp.bfloat16, False, None, False,
                            block_diffusion=SDAR_RULE)
    assert got.backward == "fused"
    assert list(got.blocks) == want["blocks"]
    text = lowered_text(*SDAR_SHAPES, block_diffusion=SDAR_RULE)
    assert text.count("pallas_call[") == 2
    assert "name=flash_bwd\n" in text and "flash_bwd_dkv" not in text
    assert digest(text) == (want["chars"], want["sha256"])


@pytest.mark.parametrize("cell", sorted(GROUPED_CALLS))
def test_a_cells_grouped_call_lowers_to_one_flash_bwd_over_its_group(cell):
    """What `flash.plan` will record in the five cells: "fused" with the
    cell's group; and the one backward kernel walks (kv head, head of the
    group, k-blocks, q-blocks), with dQ's row one head's and dK/dV's rows
    the kv head's in its scratch."""
    b, hq, hkv, t, d, dv, _window, rule = GROUPED_CALLS[cell]
    shapes, group = call_shapes(GROUPED_CALLS[cell]), hq // hkv
    assert group == {"sdar": 8, "smal": 7, "lfm2": 4, "nemo": 16,
                     "phi4": 2}[cell[:4]]
    got = fa.attention_path(*shapes, jnp.bfloat16, rule is None, None, False,
                            block_diffusion=rule)
    assert got == ("flash", ((1024, 1024),) * 2, None, "fused")
    plan = fa.plan(*shapes, rule is None, None, got.blocks, got.backward,
                   rule)
    assert (plan["backward"], plan["group"]) == ("fused", group)
    assert "bwd_dkv" not in plan and plan["bwd"]["grid_inner"] == t // 1024
    calls = {str(e.params["name"]): e for e in lowered(
        *shapes, block_diffusion=rule).jaxpr.eqns
        if e.primitive.name == "pallas_call"}
    assert sorted(calls) == ["flash_bwd", "flash_fwd"]
    mapping = calls["flash_bwd"].params["grid_mapping"]
    n = t // 1024
    assert tuple(mapping.grid) == (b * hkv, group, n, n)
    scratch = [tuple(v.aval.shape) for v in
               calls["flash_bwd"].params["jaxpr"].invars[-3:]]
    assert scratch == [(n, 1024, d), (n, 1024, d), (n, 1024, dv)]


if __name__ == "__main__":
    out = {str(s): digest(lowered_text(s)) for s in GPT2_CALLS}
    out.update({"phi window %s" % w: digest(lowered_text(*PHI_SHAPES,
                                                         window=w))
                for w in PHI_CALLS})
    out["kimi"] = digest(lowered_text(*KIMI_SHAPES))
    out["sdar"] = digest(lowered_text(*SDAR_SHAPES,
                                      block_diffusion=SDAR_RULE))
    print(json.dumps(out, indent=1))
