"""SmallThinker-21BA3B-Instruct (`smallthinker`, static graph): window and
full attention mixed 3:1 over sparse experts whose router reads the block's
input, ahead of attention.

Every layer, RMS norm with a learned scale, no bias anywhere:
    u  = RMSNorm(h)                      input_layernorm
    h1 = h + attention(u)
    x  = RMSNorm(h1)                     post_attention_layernorm
    h' = h1 + experts(x; routed by h)
- attention: q, k, v by one (d, (Hq + 2 Hkv) D) matrix, no q/k norm, scale
  D^-1/2, causal grouped-query attention (the flash kernels). Where
  `window_layout[i]` is 1 query t sees keys t - window + 1 .. t; where
  `rope_layout[i]` is 1 q and k get rotary positions over the whole head
  (`rope_qk_norm` with both norms off), else NO positions at all. Published:
  layers 1, 2, 3 of every 4 are windowed and rotary, layer 0 of every 4 is
  full and position-free.
- experts (`layers.moe_ffn`): the router reads h, the layer's own input,
  BEFORE its norm (the published modeling code takes `router_input` ahead of
  `input_layernorm`, so that the picks are known while attention runs): 64
  float32 logits, the top `top_k` of them, weights = softmax over the picks'
  logits (`norm_topk_prob` then divides by a sum that is 1); experts
  W_down(relu(W_gate x) * (W_up x)) of width `moe_ff_size`; `experts_held`
  says which of them this program holds (an expert-parallel rank's share:
  the result is the part they give; with `absent_picks="folded"` a pick on
  an absent expert is answered by the held expert congruent to it, so every
  pick is answered: the rows the rank's experts see when all ranks bring
  such a batch). No shared expert, no expert bias, no auxiliary loss.
The head is its own (vocab, d) float32 matrix (untied).

TPU-first choices as models/lfm2moe.py: bf16 activations, the fused head
(`fused_mlm_head_loss`), each layer a `recompute_segment`; an expert layer's
load count leaves its segment as a second result and the step keeps it
there (`layers.moe_balance`, rate 0: there is no bias to move).
"""
import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.initializer import TruncatedNormalInitializer
from paddle_tpu.layers.attention import fused_attention
from paddle_tpu.models.gpt import masked_mean_weights
from paddle_tpu.param_attr import ParamAttr


class SmallThinkerConfig(object):
    def __init__(self, vocab_size=151936, hidden_size=2560, num_heads=28,
                 num_kv_heads=4, head_dim=128, moe_ff_size=768,
                 num_experts=64, top_k=6, experts_held=None, window=4096,
                 window_layout=None, rope_layout=None, rope_theta=1500000.0,
                 norm_eps=1e-6, norm_topk_prob=True, initializer_range=0.02,
                 absent_picks="nothing", dtype="float32", recompute=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.moe_ff_size = moe_ff_size
        self.num_experts = num_experts
        self.top_k = top_k
        self.experts_held = tuple(experts_held or (0, num_experts))
        self.window = window
        if window_layout is None:   # the published 52 layers: 0, 1, 1, 1, ...
            window_layout = [int(i % 4 != 0) for i in range(52)]
        self.window_layout = [int(w) for w in window_layout]
        self.rope_layout = [int(r) for r in (
            self.window_layout if rope_layout is None else rope_layout)]
        self.rope_theta = rope_theta
        self.norm_eps = norm_eps
        self.norm_topk_prob = norm_topk_prob
        self.initializer_range = initializer_range
        self.absent_picks = absent_picks
        self.dtype = dtype
        self.recompute = recompute
        if len(self.rope_layout) != len(self.window_layout):
            raise ValueError("rope_layout needs one entry a layer")
        if num_heads % num_kv_heads:
            raise ValueError("%d query heads do not group over %d key/value "
                             "heads" % (num_heads, num_kv_heads))
        if head_dim % 2:
            raise ValueError("rotary positions pair a head's halves: "
                             "head_dim %d is odd" % head_dim)

    @property
    def num_layers(self):
        return len(self.window_layout)


def _w(cfg, name):
    return ParamAttr(name=name, initializer=TruncatedNormalInitializer(
        scale=cfg.initializer_range))


def _norm(x, cfg, name):
    return layers.rms_norm(x, epsilon=cfg.norm_eps,
                           param_attr=ParamAttr(name=name + "_s"))


def _fc(u, width, cfg, name):
    return layers.fc(u, width, num_flatten_dims=2, param_attr=_w(cfg, name),
                     bias_attr=False)


def _heads(m, count, dh):
    return layers.transpose(layers.reshape(m, [0, 0, count, dh]),
                            [0, 2, 1, 3])


def attention(u, cfg, i, name):
    """Causal grouped-query attention of layer i: windowed or full, rotary
    or position-free, by the two layouts."""
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q, k, v = layers.split(
        _fc(u, (hq + 2 * hkv) * dh, cfg, name + "_qkv.w_0"),
        [hq * dh, hkv * dh, hkv * dh], dim=2)
    if cfg.rope_layout[i]:
        q, k = layers.rope_qk_norm(q, k, dh, theta=cfg.rope_theta,
                                   q_norm_attr=False, k_norm_attr=False)
    else:
        q, k = _heads(q, hq, dh), _heads(k, hkv, dh)
    o = fused_attention(q, k, _heads(v, hkv, dh), scale=dh ** -0.5,
                        causal=True,
                        window=cfg.window if cfg.window_layout[i] else None)
    o = layers.reshape(layers.transpose(o, [0, 2, 1, 3]), [0, 0, hq * dh])
    return _fc(o, cfg.hidden_size, cfg, name + "_out.w_0")


def expert_ffn(x, routed_by, cfg, name):
    """(out (B,T,d), load): the held experts' part of the expert layer for
    the tokens x, routed by the logits `routed_by` gives."""
    d = cfg.hidden_size
    out, load = layers.moe_ffn(
        layers.reshape(x, [-1, d]), cfg.num_experts, cfg.top_k,
        cfg.moe_ff_size, experts_held=cfg.experts_held,
        norm_topk_prob=cfg.norm_topk_prob,
        router_attr=_w(cfg, name + "_router.w_0"),
        gate_up_attr=_w(cfg, name + "_experts_gate_up"),
        down_attr=_w(cfg, name + "_experts_down"), name=name,
        router_input=layers.reshape(routed_by, [-1, d]), scoring="softmax",
        gate="relu", absent=cfg.absent_picks)
    return layers.reshape(out, [-1, x.shape[1], d]), load


def smallthinker_layer(h, cfg, i):
    """Layer i: [h', load]."""
    name = "st_layer_%d" % i
    u = _norm(h, cfg, name + "_attn_norm")
    h1 = layers.elementwise_add(h, attention(u, cfg, i, name))
    out, load = expert_ffn(_norm(h1, cfg, name + "_ffn_norm"), h, cfg, name)
    return [layers.elementwise_add(h1, out), load]


def smallthinker_decoder(token_ids, cfg, is_test=False):
    """Embed -> the layers -> final RMS norm; (B, T, d) in cfg.dtype."""
    x = layers.embedding(token_ids, [cfg.vocab_size, cfg.hidden_size],
                         param_attr=_w(cfg, "st_word_embedding"),
                         dtype="float32")
    if cfg.dtype == "bfloat16":
        x = layers.cast(x, "bfloat16")
    for i in range(cfg.num_layers):
        def run(h, i=i):
            return smallthinker_layer(h, cfg, i)

        if cfg.recompute and not is_test:
            x, load = layers.recompute_segment(run, [x])
        else:
            x, load = run(x)
        layers.moe_balance(load, "st_layer_%d" % i, cfg.experts_held)
    return _norm(x, cfg, "st_norm_f")


def smallthinker_pretrain_program(cfg, batch_size, seq_len, optimizer_fn=None,
                                  is_test=False):
    """Next-token LM: feeds token_ids/labels (N,T,1) int64 + loss_mask
    (N,T,1) float32 (1 = predict here). The head is its own (vocab, d)
    matrix (untied, as published), through the fused head, in bf16 with f32
    accumulation when cfg.dtype is bfloat16."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        tok = layers.data("token_ids", [seq_len, 1], dtype="int64")
        lbl = layers.data("labels", [seq_len, 1], dtype="int64")
        lmask = layers.data("loss_mask", [seq_len, 1], dtype="float32")
        h = smallthinker_decoder(tok, cfg, is_test=is_test)
        head = layers.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], "float32",
            attr=_w(cfg, "st_lm_head"))
        loss = layers.fused_mlm_head_loss(
            layers.reshape(h, [-1, cfg.hidden_size]), head,
            layers.reshape(lbl, [-1, 1]), cast_bf16=cfg.dtype == "bfloat16",
            token_weight=masked_mean_weights(lmask))
        if optimizer_fn is not None:
            optimizer_fn(loss)
    return main, startup, ["token_ids", "labels", "loss_mask"], {"loss": loss}
