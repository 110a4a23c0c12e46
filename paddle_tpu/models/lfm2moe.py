"""LFM2-8B-A1B (`lfm2_moe`, static graph): gated short convolutions and
grouped-query attention layers over sparse experts.

Every layer is `h = x + Mixer(RMSNorm(x)); y = h + FFN(RMSNorm(h))`, RMS norm
with a learned scale, no bias anywhere. By `layer_kinds`:
  - "conv": `[B, C, u] = split3(x W_in)`; `v = B * u`; a depthwise causal
    convolution of width `conv_width` over v, no bias; `out = (C * c) W_out`.
  - "attention": q, k, v by one (d, (Hq + 2 Hkv) D) matrix; an RMS norm
    over each head of q and of k with a learned scale of D, rotary positions
    over the whole head (`rope_qk_norm`); causal grouped-query attention
    with scale D^-1/2 (the flash kernels); `out = concat W_o`.
The feed-forward of layer i is the dense gated MLP `W2(silu(W1 u) * W3 u)`
of width `ff_size` where its PUBLISHED index is below `num_dense_layers`,
else `layers.moe_ffn`: sigmoid router over all `num_experts`, an expert bias
on the choice, top `top_k`, renormalised weights, experts of width
`moe_ff_size`; `experts_held` says which of them this program holds (an
expert-parallel rank's share: the result is the part they give).

TPU-first choices as models/phi4flash.py: bf16 activations, the fused tied
head (`fused_mlm_head_loss`), each layer a `recompute_segment`; an expert
layer's load count leaves its segment as a second result, and the step keeps
it and moves the expert bias by it there (`layers.moe_balance`:
`expert_bias_update_rate`, the loss-free balance step), so the replay in the
backward writes nothing.
"""
import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.initializer import TruncatedNormalInitializer
from paddle_tpu.layers.attention import fused_attention
from paddle_tpu.models.gpt import masked_mean_weights
from paddle_tpu.param_attr import ParamAttr

KINDS = ("conv", "attention")


class Lfm2MoeConfig(object):
    def __init__(self, vocab_size=65536, hidden_size=2048, num_heads=32,
                 num_kv_heads=8, head_dim=64, ff_size=7168,
                 moe_ff_size=1792, num_experts=32, top_k=4,
                 experts_held=None, num_dense_layers=2, conv_width=3,
                 layer_kinds=None, published_layer_index=None,
                 rope_theta=1000000.0, norm_eps=1e-5, norm_topk_prob=True,
                 routed_scaling_factor=1.0, expert_bias_update_rate=0.0,
                 initializer_range=0.02, dtype="float32", recompute=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.ff_size = ff_size
        self.moe_ff_size = moe_ff_size
        self.num_experts = num_experts
        self.top_k = top_k
        self.experts_held = tuple(experts_held or (0, num_experts))
        self.num_dense_layers = num_dense_layers
        self.conv_width = conv_width
        if layer_kinds is None:     # the published 24-layer pattern
            layer_kinds = ["attention" if i in (2, 6, 10, 14, 18, 21)
                           else "conv" for i in range(24)]
        self.layer_kinds = list(layer_kinds)
        self.published_layer_index = list(
            published_layer_index or range(len(self.layer_kinds)))
        self.rope_theta = rope_theta
        self.norm_eps = norm_eps
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.expert_bias_update_rate = expert_bias_update_rate
        self.initializer_range = initializer_range
        self.dtype = dtype
        self.recompute = recompute
        if set(self.layer_kinds) - set(KINDS):
            raise ValueError("unknown layer kinds %r"
                             % (set(self.layer_kinds) - set(KINDS)))
        if len(self.published_layer_index) != len(self.layer_kinds):
            raise ValueError("published_layer_index needs one entry a layer")
        if num_heads % num_kv_heads:
            raise ValueError("%d query heads do not group over %d key/value "
                             "heads" % (num_heads, num_kv_heads))

    @property
    def num_layers(self):
        return len(self.layer_kinds)

    def is_dense(self, i):
        return self.published_layer_index[i] < self.num_dense_layers


def _w(cfg, name):
    return ParamAttr(name=name, initializer=TruncatedNormalInitializer(
        scale=cfg.initializer_range))


def _norm(x, cfg, name):
    return layers.rms_norm(x, epsilon=cfg.norm_eps,
                           param_attr=ParamAttr(name=name + "_s"))


def _fc(u, width, cfg, name):
    return layers.fc(u, width, num_flatten_dims=2, param_attr=_w(cfg, name),
                     bias_attr=False)


def short_conv(u, cfg, name):
    """The gated short convolution: (C * conv(B * x)) W_out."""
    b, c, x = layers.split(_fc(u, 3 * cfg.hidden_size, cfg,
                               name + "_conv_in.w_0"), 3, dim=2)
    conv = layers.causal_conv1d(layers.elementwise_mul(b, x), cfg.conv_width,
                                param_attr=_w(cfg, name + "_conv.w_0"),
                                bias_attr=False)
    return _fc(layers.elementwise_mul(c, conv), cfg.hidden_size, cfg,
               name + "_conv_out.w_0")


def gqa_attention(u, cfg, name):
    """Causal grouped-query attention with per-head q/k norms and rotary
    positions."""
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = layers.split(
        _fc(u, (hq + 2 * hkv) * dh, cfg, name + "_qkv.w_0"),
        [hq * dh, hkv * dh, hkv * dh], dim=2)
    q, k = layers.rope_qk_norm(
        q, k, dh, theta=cfg.rope_theta, epsilon=cfg.norm_eps,
        q_norm_attr=ParamAttr(name=name + "_q_norm_s"),
        k_norm_attr=ParamAttr(name=name + "_k_norm_s"))
    v = layers.transpose(layers.reshape(v, [0, 0, hkv, dh]), [0, 2, 1, 3])
    o = fused_attention(q, k, v, scale=dh ** -0.5, causal=True)
    o = layers.reshape(layers.transpose(o, [0, 2, 1, 3]), [0, 0, hq * dh])
    return _fc(o, cfg.hidden_size, cfg, name + "_out.w_0")


def dense_ffn(u, cfg, name):
    gate, up = layers.split(_fc(u, 2 * cfg.ff_size, cfg,
                                name + "_mlp_gate_up.w_0"), 2, dim=2)
    return _fc(layers.elementwise_mul(layers.silu(gate), up),
               cfg.hidden_size, cfg, name + "_mlp_down.w_0")


def expert_ffn(u, cfg, name):
    """(out (B,T,d), load): the held experts' part of the expert layer and
    the picks each of the `num_experts` experts received."""
    out, load = layers.moe_ffn(
        layers.reshape(u, [-1, cfg.hidden_size]), cfg.num_experts, cfg.top_k,
        cfg.moe_ff_size, experts_held=cfg.experts_held,
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        router_attr=_w(cfg, name + "_router.w_0"),
        gate_up_attr=_w(cfg, name + "_experts_gate_up"),
        down_attr=_w(cfg, name + "_experts_down"), name=name)
    return layers.reshape(out, [-1, u.shape[1], cfg.hidden_size]), load


def lfm2_layer(x, cfg, i):
    """Layer i: [x'] for a dense layer, [x', load] for an expert layer."""
    name = "lfm_layer_%d" % i
    u = _norm(x, cfg, name + "_op_norm")
    mix = gqa_attention(u, cfg, name) if cfg.layer_kinds[i] == "attention" \
        else short_conv(u, cfg, name)
    h = layers.elementwise_add(x, mix)
    u2 = _norm(h, cfg, name + "_ffn_norm")
    if cfg.is_dense(i):
        return [layers.elementwise_add(h, dense_ffn(u2, cfg, name))]
    out, load = expert_ffn(u2, cfg, name)
    return [layers.elementwise_add(h, out), load]


def lfm2moe_decoder(token_ids, cfg, is_test=False):
    """Embed -> the layers -> final RMS norm; (B, T, d) in cfg.dtype."""
    x = layers.embedding(token_ids, [cfg.vocab_size, cfg.hidden_size],
                         param_attr=_w(cfg, "lfm_word_embedding"),
                         dtype="float32")
    if cfg.dtype == "bfloat16":
        x = layers.cast(x, "bfloat16")
    for i in range(cfg.num_layers):
        def run(h, i=i):
            return lfm2_layer(h, cfg, i)

        if cfg.recompute and not is_test:
            outs = layers.recompute_segment(run, [x])
        else:
            outs = run(x)
        outs = outs if isinstance(outs, (list, tuple)) else [outs]
        x = outs[0]
        if len(outs) > 1:
            layers.moe_balance(
                outs[1], "lfm_layer_%d" % i, cfg.experts_held,
                0.0 if is_test else cfg.expert_bias_update_rate)
    return _norm(x, cfg, "lfm_norm_f")


def lfm2moe_pretrain_program(cfg, batch_size, seq_len, optimizer_fn=None,
                             is_test=False):
    """Next-token LM: feeds token_ids/labels (N,T,1) int64 + loss_mask
    (N,T,1) float32 (1 = predict here). Tied-embedding decode through the
    fused head, in bf16 with f32 accumulation when cfg.dtype is bfloat16."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        tok = layers.data("token_ids", [seq_len, 1], dtype="int64")
        lbl = layers.data("labels", [seq_len, 1], dtype="int64")
        lmask = layers.data("loss_mask", [seq_len, 1], dtype="float32")
        h = lfm2moe_decoder(tok, cfg, is_test=is_test)
        emb = main.global_block().var("lfm_word_embedding")
        loss = layers.fused_mlm_head_loss(
            layers.reshape(h, [-1, cfg.hidden_size]), emb,
            layers.reshape(lbl, [-1, 1]), cast_bf16=cfg.dtype == "bfloat16",
            token_weight=masked_mean_weights(lmask))
        if optimizer_fn is not None:
            optimizer_fn(loss)
    return main, startup, ["token_ids", "labels", "loss_mask"], {"loss": loss}
