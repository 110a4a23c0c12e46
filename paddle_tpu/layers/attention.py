"""Attention layers.

Reference parity: fluid nets.scaled_dot_product_attention + the transformer
in PaddlePaddle/models. TPU-native: single fused attention op (XLA or Pallas
flash kernel), plus multi_head_attention with optional tensor-parallel
sharding of the head dimension and sequence-parallel ring attention.
"""
from ..layer_helper import LayerHelper
from .nn import fc, matmul, softmax, dropout, reshape, transpose
from .tensor import concat
from ..param_attr import ParamAttr


def scaled_dot_product_attention(queries, keys, values, num_heads=1,
                                 dropout_rate=0.0, is_test=False):
    """queries/keys/values: (N, T, D). Multi-head fused attention."""
    helper = LayerHelper("sdpa")
    n, tq, d = queries.shape
    dh = d // num_heads
    q = transpose(reshape(queries, [0, -1 if tq == -1 else tq, num_heads,
                                    dh]), [0, 2, 1, 3])
    k = transpose(reshape(keys, [0, -1 if keys.shape[1] == -1
                                 else keys.shape[1], num_heads, dh]),
                  [0, 2, 1, 3])
    v = transpose(reshape(values, [0, -1 if values.shape[1] == -1
                                   else values.shape[1], num_heads, dh]),
                  [0, 2, 1, 3])
    out = fused_attention(q, k, v)
    out = reshape(transpose(out, [0, 2, 1, 3]), [0, -1 if tq == -1 else tq,
                                                 d])
    if dropout_rate:
        out = dropout(out, dropout_rate, is_test=is_test)
    return out


def fused_attention(q, k, v, mask=None, scale=None, causal=False,
                    impl="auto", sp_axis="sp", name=None, window=None,
                    block_diffusion=None):
    """q: (B, Hq, T, Dh), k: (B, Hkv, T, Dh), v: (B, Hkv, T, Dv) — one
    fused op; Pallas flash path when available. Reference composes this
    from matmul+softmax+matmul ops. Hq may be any whole multiple of Hkv
    (query head h reads kv head h // (Hq // Hkv)), Dv may differ from Dh
    (the output is Dv wide), and `window=W` with `causal=True` lets query
    t see keys s with t - W < s <= t. `block_diffusion=(L, T)` (Python
    ints; 2T rows; instead of `causal`, `window` or `mask`) is the
    block-diffusion training mask over a noisy copy (rows 0..T-1) and a
    clean copy (rows T..2T-1) of a T-token document in blocks of L: a clean
    query sees the clean keys of its own and earlier blocks, a noisy query
    the noisy keys of its own block and the clean keys of strictly earlier
    blocks. The flash kernels compute it from row indices and skip the
    tiles nobody sees.

    impl: "auto" | "xla" | "flash" | "ring" | "ulysses" — the last two
    run sequence-parallel attention over the installed mesh's `sp_axis`:
    ring rotates K/V blocks via ppermute and accepts additive
    key-padding masks (..., 1, T) riding the ring; ulysses re-shards
    heads via all_to_all and accepts any additive mask."""
    helper = LayerHelper("fused_attention", name=name)
    out = helper.create_variable_for_type_inference(
        q.dtype, tuple(q.shape[:-1]) + (v.shape[-1],))
    inputs = {"Q": [q.name], "K": [k.name], "V": [v.name]}
    if mask is not None:
        inputs["Mask"] = [mask.name]
    attrs = {"scale": scale, "causal": causal, "impl": impl,
             "sp_axis": sp_axis, "window": window}
    if block_diffusion is not None:     # an op without the rule has no attr
        attrs["block_diffusion"] = [int(n) for n in block_diffusion]
    helper.append_op("scaled_dot_product_attention", inputs=inputs,
                     outputs={"Out": [out.name]}, attrs=attrs)
    return out


def rope_qk_norm(q, k, head_dim, theta=10000.0, epsilon=1e-5,
                 q_norm_attr=None, k_norm_attr=None, name=None,
                 position_period=None):
    """q (B, T, Hq*D), k (B, T, Hkv*D) -> (q', k') head-major, (B, H, T, D):
    an RMS norm over each head's D numbers with a learned float32 scale of
    D (`*_norm_attr=False`: no norm), then rotary positions over the whole
    head (pairs (i, i + D/2), base `theta`), in one elementwise op in
    front of `fused_attention`. Row t turns by t, or with
    `position_period=P` by t mod P (copies of a document side by side,
    each counted from 0)."""
    from ..initializer import ConstantInitializer
    helper = LayerHelper("rope_qk_norm", name=name)
    inputs = {"Q": [q.name], "K": [k.name]}
    for slot, attr, suffix in (("QScale", q_norm_attr, "_q_norm_s"),
                               ("KScale", k_norm_attr, "_k_norm_s")):
        if attr is False:
            continue
        attr = ParamAttr._to_attr(attr)
        if attr.name is None:
            attr.name = helper.name + suffix
        scale = helper.create_parameter(
            attr, shape=[head_dim], dtype="float32",
            default_initializer=ConstantInitializer(1.0))
        inputs[slot] = [scale.name]
    outs = []
    for x in (q, k):
        width = x.shape[2]
        outs.append(helper.create_variable_for_type_inference(
            x.dtype, (x.shape[0], width // head_dim, x.shape[1], head_dim)))
    attrs = {"head_dim": int(head_dim), "theta": float(theta),
             "epsilon": float(epsilon)}
    if position_period is not None:     # an op without it has no attr
        attrs["position_period"] = int(position_period)
    helper.append_op("rope_qk_norm", inputs=inputs,
                     outputs={"QOut": [outs[0].name],
                              "KOut": [outs[1].name]}, attrs=attrs)
    return outs[0], outs[1]


def partial_rope(q, k_pe, nope_dim, rope_dim, theta=10000.0, name=None):
    """Latent attention's decoupled rotary part: q (B, T, H*(nope + rope))
    with the last `rope_dim` numbers of every head turned, k_pe (B, T,
    rope), the key part all heads share, turned whole; same shapes out, no
    layout change (`partial_rope` op: x cos + partner(x) sin in float32, the
    partners through a signed permutation; positions 0..T-1). Pair i of a
    part is its neighbours (2i, 2i + 1), as the published latent-attention
    checkpoints store them, and turns by t * theta^(-2i/rope)."""
    helper = LayerHelper("partial_rope", name=name)
    outs = [helper.create_variable_for_type_inference(x.dtype, x.shape)
            for x in (q, k_pe)]
    helper.append_op("partial_rope",
                     inputs={"Q": [q.name], "KPe": [k_pe.name]},
                     outputs={"QOut": [outs[0].name],
                              "KPeOut": [outs[1].name]},
                     attrs={"nope_dim": int(nope_dim),
                            "rope_dim": int(rope_dim),
                            "theta": float(theta)})
    return outs[0], outs[1]


def mha_kv_projection(keys, values, d_key, d_value, n_head,
                      param_initializer=None, name="multi_head_att"):
    """Project encoder output once into head-split K/V for cross-attention
    caching (reference: fast_decoder's static_k/static_v). Uses the same
    parameter names as multi_head_attention's k/v projections, so a decoder
    built for training reuses the identical weights at decode time.
    Returns (static_k, static_v), each (N, H, T_src, Dh)."""
    def _attr(suffix):
        return ParamAttr(name=None if name is None else name + suffix,
                         initializer=param_initializer)

    k = fc(keys, d_key * n_head, num_flatten_dims=2,
           param_attr=_attr("_key_fc.w_0"), bias_attr=_attr("_key_fc.b_0"))
    v = fc(values, d_value * n_head, num_flatten_dims=2,
           param_attr=_attr("_value_fc.w_0"), bias_attr=_attr("_value_fc.b_0"))

    def _split_heads(x, dh):
        r = reshape(x, [0, -1 if x.shape[1] == -1 else x.shape[1],
                        n_head, dh])
        return transpose(r, [0, 2, 1, 3])

    return _split_heads(k, d_key), _split_heads(v, d_value)


def multi_head_attention(queries, keys, values, attn_bias, d_key, d_value,
                         d_model, n_head=1, dropout_rate=0.0, cache=None,
                         param_initializer=None, name="multi_head_att",
                         is_test=False, causal=False, attn_impl="auto"):
    """The transformer MHA block used by ERNIE/BERT/Transformer models
    (mirrors PaddlePaddle/models transformer.multi_head_attention).
    attn_impl routes the fused attention op ("auto" | "xla" | "flash" |
    "ring" | "ulysses") — the sequence-parallel paths accept attn_bias
    key-padding masks (BERT's (N,1,1,T) bias rides the ring with K/V)."""
    keys = queries if keys is None else keys
    values = keys if values is None else values

    def _attr(suffix):
        return ParamAttr(name=None if name is None else name + suffix,
                         initializer=param_initializer)

    def _split_heads(x, dh):
        r = reshape(x, [0, -1 if x.shape[1] == -1 else x.shape[1],
                        n_head, dh])
        return transpose(r, [0, 2, 1, 3])

    q = fc(queries, d_key * n_head, num_flatten_dims=2,
           param_attr=_attr("_query_fc.w_0"), bias_attr=_attr("_query_fc.b_0"))
    qh = _split_heads(q, d_key)

    if cache is not None and "static_k" in cache:
        # cross-attention with precomputed encoder K/V (see mha_kv_projection)
        kh, vh = cache["static_k"], cache["static_v"]
    else:
        kh, vh = mha_kv_projection(keys, values, d_key, d_value, n_head,
                                   param_initializer=param_initializer,
                                   name=name)
        if cache is not None:
            # incremental self-attention: append this step's K/V to the cache
            # (reference: PaddlePaddle/models transformer fast_decoder cache)
            if cache.get("k") is not None:
                kh = concat([cache["k"], kh], axis=2)
                vh = concat([cache["v"], vh], axis=2)
            cache["k"], cache["v"] = kh, vh
            if queries.shape[1] == 1:
                causal = False    # single newest query sees the whole cache
    ctx = fused_attention(qh, kh, vh, mask=attn_bias,
                          scale=d_key ** -0.5, causal=causal,
                          impl=attn_impl)
    ctx = transpose(ctx, [0, 2, 1, 3])
    ctx = reshape(ctx, [0, -1 if queries.shape[1] == -1 else queries.shape[1],
                        d_value * n_head])
    if dropout_rate:
        ctx = dropout(ctx, dropout_rate, is_test=is_test,
                      dropout_implementation="upscale_in_train")
    out = fc(ctx, d_model, num_flatten_dims=2,
             param_attr=_attr("_output_fc.w_0"),
             bias_attr=_attr("_output_fc.b_0"))
    return out
