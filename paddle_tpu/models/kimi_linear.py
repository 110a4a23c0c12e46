"""Kimi-Linear-48B-A3B (`kimi_linear`, static graph; arXiv:2510.26692 and the
published `modeling_kimi.py`): Kimi Delta Attention and latent attention
mixers, three to one, over sparse experts with a shared expert.

Every layer is `h = x + Mixer(RMSNorm(x)); y = h + FFN(RMSNorm(h))`, RMS
norms with a learned scale, no bias anywhere; a final norm and an untied
head. By `layer_kinds`:
  - "kda" (`layers.kda_attention`; H heads held, K = V = `kda_head_dim`,
    u the normed input):
      q = l2norm_head(silu(conv(u Wq))), k = l2norm_head(silu(conv(u Wk))),
      v = silu(conv(u Wv)); conv the depthwise causal convolution of width
      `conv_width`, one a projection; l2norm_head divides a head's K
      numbers by their norm;
      g = -exp(A_log[h]) * softplus(W_fb (W_fa u) + dt_bias) per token, head
      and key channel, a = exp(g) in (0, 1]; beta = sigmoid(u W_beta), one
      a head;
      S_0 = 0; S' = Diag(a_t) S_{t-1};
      S_t = S' - beta_t k_t (k_t^T S') + beta_t k_t v_t^T;
      o_t = S_t^T (q_t K^-1/2);
      out = W_o (rmsnorm_head(o_t; g_o) * sigmoid(W_gb (W_ga u))).
  - "mla" (`layers.mla_attention`; no positions, `mla_use_nope`: the 64
    "rope" numbers are carried and never turned):
      q = u W_q -> (H, nope + rope); [c | k_pe] = u W_kva -> (rank | rope);
      [k_nope | v] = rmsnorm(c) W_kvb -> (H, nope | v);
      k = [k_nope | k_pe for every head]; causal softmax attention at scale
      (nope + rope)^-1/2, values `v_dim` wide; out = W_o concat.
The feed-forward of layer i is the dense gated MLP `W_d(silu(W_g u) * W_u u)`
of width `ff_size` where its PUBLISHED index (from 1, as
`linear_attn_config` numbers layers) is at most `first_k_dense`, else
`shared(u) + moe_ffn(u)`: shared the same gated MLP at `moe_ff_size` x
`num_shared_experts`, `layers.moe_ffn` a sigmoid router over all
`num_experts`, top `top_k` of scores + bias (one group: the published grouped
top-k is a plain one), the picks' scores over their sum, times
`routed_scaling_factor`.

`experts_held` and `heads_held` say which experts and which heads (of both
mixers) this program holds: a rank's share; the result is the part they
give, the shared expert and everything else whole.

TPU-first choices as models/lfm2moe.py: bf16 activations, the fused head
(`fused_mlm_head_loss`), each layer a `recompute_segment`; an expert layer's
load count leaves its segment as a second result and `layers.moe_balance`
keeps it there. The blocks behind the mixer (the gated MLP, router +
`moe_ffn` + shared expert) and the frame around the layers (embedding,
segments, final norm, head) are `models/moe_decoder.py`'s, shared with
`models/kimi_vl.py`; this file brings the config and the two mixers.
"""
from paddle_tpu import layers
from paddle_tpu.models import moe_decoder

KINDS = ("kda", "mla")


class KimiLinearConfig(object):
    def __init__(self, vocab_size=163840, hidden_size=2304, num_heads=32,
                 kda_head_dim=128, conv_width=4, gate_rank=None,
                 qk_nope_dim=128, qk_rope_dim=64, v_dim=128, kv_rank=512,
                 ff_size=9216, moe_ff_size=1024, num_experts=256, top_k=8,
                 num_shared_experts=1, first_k_dense=1, experts_held=None,
                 heads_held=None, layer_kinds=None,
                 published_layer_index=None, norm_eps=1e-5,
                 norm_topk_prob=True, routed_scaling_factor=2.446,
                 expert_bias_update_rate=0.0, initializer_range=0.02,
                 dtype="float32", recompute=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.kda_head_dim = kda_head_dim
        self.conv_width = conv_width
        self.gate_rank = gate_rank or kda_head_dim
        self.qk_nope_dim = qk_nope_dim
        self.qk_rope_dim = qk_rope_dim
        self.v_dim = v_dim
        self.kv_rank = kv_rank
        self.ff_size = ff_size
        self.moe_ff_size = moe_ff_size
        self.num_experts = num_experts
        self.top_k = top_k
        self.num_shared_experts = num_shared_experts
        self.first_k_dense = first_k_dense
        self.experts_held = tuple(experts_held or (0, num_experts))
        self.heads_held = tuple(heads_held or (0, num_heads))
        if layer_kinds is None:     # the published 27 layers, K K K M
            layer_kinds = ["mla" if i % 4 == 0 or i == 27 else "kda"
                           for i in range(1, 28)]
        self.layer_kinds = list(layer_kinds)
        self.published_layer_index = list(
            published_layer_index or range(1, len(self.layer_kinds) + 1))
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

    @classmethod
    def from_published(cls, config, **more):
        """From the keys of the published `config.json` (`linear_attn_config`
        included). Beside them the dict may hold this program's share:
        `experts_held` (then `num_experts_routed` is the router's width and
        `num_experts` the count held), `heads_held` (of
        `linear_attn_config.num_heads`, which both mixers have), `layer_kinds`
        and `published_layer_index` (default: every layer, its kind by
        `linear_attn_config.full_attn_layers`)."""
        lin = config["linear_attn_config"]
        depth = config["num_hidden_layers"]
        index = list(config.get("published_layer_index")
                     or range(1, depth + 1))
        kinds = config.get("layer_kinds") or [
            "mla" if i in lin["full_attn_layers"] else "kda" for i in index]
        return cls(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"], num_heads=lin["num_heads"],
            kda_head_dim=lin["head_dim"],
            conv_width=lin["short_conv_kernel_size"],
            qk_nope_dim=config["qk_nope_head_dim"],
            qk_rope_dim=config["qk_rope_head_dim"],
            v_dim=config["v_head_dim"], kv_rank=config["kv_lora_rank"],
            ff_size=config["intermediate_size"],
            moe_ff_size=config["moe_intermediate_size"],
            num_experts=config.get("num_experts_routed",
                                   config["num_experts"]),
            top_k=config["num_experts_per_token"],
            num_shared_experts=config["num_shared_experts"],
            first_k_dense=config["first_k_dense_replace"],
            experts_held=config.get("experts_held"),
            heads_held=config.get("heads_held"), layer_kinds=kinds,
            published_layer_index=index, norm_eps=config["rms_norm_eps"],
            norm_topk_prob=config["moe_renormalize"],
            routed_scaling_factor=config["routed_scaling_factor"],
            expert_bias_update_rate=config.get("expert_bias_update_rate",
                                               0.0),
            initializer_range=config.get("initializer_range", 0.02), **more)

    @property
    def num_layers(self):
        return len(self.layer_kinds)

    def is_dense(self, i):
        return self.published_layer_index[i] <= self.first_k_dense


def mixer(u, cfg, i, name):
    if cfg.layer_kinds[i] == "kda":
        return layers.kda_attention(
            u, cfg.num_heads, cfg.kda_head_dim, gate_rank=cfg.gate_rank,
            conv_width=cfg.conv_width, heads_held=cfg.heads_held,
            epsilon=cfg.norm_eps, param_initializer=moe_decoder.init(cfg),
            name=name + "_kda")
    return layers.mla_attention(
        u, cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_dim,
        cfg.kv_rank, heads_held=cfg.heads_held, epsilon=cfg.norm_eps,
        param_initializer=moe_decoder.init(cfg), name=name + "_mla")


def kimi_layer(x, cfg, i):
    """Layer i: [x'] for a dense layer, [x', load] for an expert layer."""
    return moe_decoder.layer(x, cfg, i, "kimi_layer_%d" % i, mixer)


def kimi_linear_decoder(token_ids, cfg, is_test=False):
    """Embed -> the layers -> final RMS norm; (B, T, d) in cfg.dtype."""
    return moe_decoder.decoder(token_ids, cfg, "kimi", mixer,
                               is_test=is_test)


def kimi_linear_pretrain_program(cfg, batch_size, seq_len, optimizer_fn=None,
                                 is_test=False):
    """Next-token LM: feeds token_ids/labels (N,T,1) int64 + loss_mask
    (N,T,1) float32 (1 = predict here). The head is its own (vocab, d)
    matrix (untied, as published), through the fused head, in bf16 with f32
    accumulation when cfg.dtype is bfloat16."""
    return moe_decoder.pretrain_program(cfg, seq_len, "kimi", mixer,
                                        optimizer_fn=optimizer_fn,
                                        is_test=is_test)
