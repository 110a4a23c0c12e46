"""The guard that the cells' attention calls lower as recorded: the jaxpr
of each call, forward and backward, held by digest."""
import hashlib
import json
import re

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa

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


SDAR_SHAPES = ((1, 32, 16384, 128), (1, 4, 16384, 128), (1, 4, 16384, 128))
SDAR_RULE = (4, 8192)
SDAR_CALL = {
    "sha256": "73dba223ea6e085bea539b3086a2eed6b4599fe5e2dcf06090efa31b36436"
              "b66", "chars": 88189,
    "blocks": [(1024, 1024)] * 3}


def lowered_text(q_shape, k_shape=None, v_shape=None, window=None,
                 backward=True, block_diffusion=None):
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


def test_the_block_diffusion_call_lowers_as_recorded():
    """The SDAR cell's call (PR 48): 32 query heads on 4 key heads of 128
    over the 16,384 rows of an 8,192-token document's two copies in blocks
    of 4; the split pair for its group, every kernel at 1024 x 1024, the
    whole square as the grid with the rule in the index maps."""
    want = SDAR_CALL
    got = fa.attention_path(*SDAR_SHAPES, jnp.bfloat16, False, None, False,
                            block_diffusion=SDAR_RULE)
    assert got.backward == "split: group"
    assert list(got.blocks) == want["blocks"]
    text = lowered_text(*SDAR_SHAPES, block_diffusion=SDAR_RULE)
    assert text.count("pallas_call[") == 3
    assert "name=flash_bwd\n" not in text
    assert digest(text) == (want["chars"], want["sha256"])


if __name__ == "__main__":
    out = {str(s): digest(lowered_text(s)) for s in GPT2_CALLS}
    out.update({"phi window %s" % w: digest(lowered_text(*PHI_SHAPES,
                                                         window=w))
                for w in PHI_CALLS})
    out["kimi"] = digest(lowered_text(*KIMI_SHAPES))
    out["sdar"] = digest(lowered_text(*SDAR_SHAPES,
                                      block_diffusion=SDAR_RULE))
    print(json.dumps(out, indent=1))
