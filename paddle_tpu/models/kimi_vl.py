"""Kimi-VL-A3B-Instruct's language model (`kimi_vl`, static graph; the
decoder of huggingface.co/moonshotai/Kimi-VL-A3B-Instruct, a DeepSeek-V3
shaped one): latent attention WITH its decoupled rotary part in every
layer, over a 64-wide sigmoid router with top-6, two shared experts and
experts 1408 wide. The vision tower and its projector are NOT built: this
is the text decoder alone, fed token ids.

Every layer is `h = x + MLA(RMSNorm(x)); y = h + FFN(RMSNorm(h))`, RMS
norms with a learned scale, no bias anywhere; a final norm and an untied
head. With u the normed input and H heads:
  - MLA (`layers.mla_attention(rope_theta=)`):
      q = u W_q -> (H, nope + rope); [c | k_pe] = u W_kva -> (rank | rope);
      [k_nope | v] = rmsnorm(c) W_kvb -> (H, nope | v);
      rotary positions t = 0..T-1 on the last `rope` numbers of every query
      head and on k_pe, one vector a token that all heads share, turned
      before it is broadcast: pair (2i, 2i + 1) by t * theta^(-2i/rope)
      (no scaling: `rope_scaling` null);
      k = [k_nope | k_pe for every head]; causal softmax attention at scale
      (nope + rope)^-1/2, values `v_dim` wide; out = W_o concat.
  - the feed-forward of layer i is the dense gated MLP
    `W_d(silu(W_g u) * W_u u)` of width `ff_size` where its PUBLISHED index
    (from 0) is under `first_k_dense`, else `shared(u) + moe_ffn(u)`: the
    `num_shared_experts` shared experts one gated MLP of their widths' sum,
    `layers.moe_ffn` a sigmoid router over all `num_experts`, top `top_k`
    of scores + bias (`n_group` = `topk_group` = 1: a plain top-k), the
    picks' scores over their sum, times `routed_scaling_factor`.

`experts_held` says which experts this program holds (an expert-parallel
rank's share: the result is the part they give; `absent_picks="folded"`
answers a pick on an absent expert with the held expert congruent to it, so
every pick is answered: the rows the rank's experts see when all ranks
bring such a batch). The blocks behind the mixer and the frame around the
layers are `models/moe_decoder.py`'s, which `models/kimi_linear.py` shares.
"""
from paddle_tpu import layers
from paddle_tpu.models import moe_decoder

PREFIX = "kvl"


class KimiVLConfig(object):
    def __init__(self, vocab_size=163840, hidden_size=2048, num_heads=16,
                 qk_nope_dim=128, qk_rope_dim=64, v_dim=128, kv_rank=512,
                 rope_theta=800000.0, ff_size=11264, moe_ff_size=1408,
                 num_experts=64, top_k=6, num_shared_experts=2,
                 first_k_dense=1, num_layers=27,
                 published_layer_index=None, experts_held=None,
                 absent_picks="nothing", norm_eps=1e-5, norm_topk_prob=True,
                 routed_scaling_factor=2.446, expert_bias_update_rate=0.0,
                 initializer_range=0.02, dtype="float32", recompute=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.qk_nope_dim = qk_nope_dim
        self.qk_rope_dim = qk_rope_dim
        self.v_dim = v_dim
        self.kv_rank = kv_rank
        self.rope_theta = rope_theta
        self.ff_size = ff_size
        self.moe_ff_size = moe_ff_size
        self.num_experts = num_experts
        self.top_k = top_k
        self.num_shared_experts = num_shared_experts
        self.first_k_dense = first_k_dense
        self.published_layer_index = list(
            range(num_layers) if published_layer_index is None
            else published_layer_index)
        self.experts_held = tuple(experts_held or (0, num_experts))
        self.absent_picks = absent_picks
        self.norm_eps = norm_eps
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.expert_bias_update_rate = expert_bias_update_rate
        self.initializer_range = initializer_range
        self.dtype = dtype
        self.recompute = recompute

    @classmethod
    def from_published(cls, config, **more):
        """From the keys of the published `config.json`'s text decoder.
        Beside them the dict may hold this program's share: `experts_held`
        (then `num_experts_routed` is the router's width and
        `n_routed_experts` the count held), `absent_experts`, and
        `published_layer_index` (from 0; default: every layer). What the
        program does not build is refused by name."""
        for key, built in (("q_lora_rank", None), ("rope_scaling", None),
                           ("scoring_func", "sigmoid"), ("n_group", 1),
                           ("topk_group", 1), ("moe_layer_freq", 1),
                           ("hidden_act", "silu")):
            if config.get(key, built) != built:
                raise ValueError("kimi_vl builds %s = %r only, not %r"
                                 % (key, built, config[key]))
        read = dict(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_heads=config["num_attention_heads"],
            qk_nope_dim=config["qk_nope_head_dim"],
            qk_rope_dim=config["qk_rope_head_dim"],
            v_dim=config["v_head_dim"], kv_rank=config["kv_lora_rank"],
            rope_theta=float(config["rope_theta"]),
            ff_size=config["intermediate_size"],
            moe_ff_size=config["moe_intermediate_size"],
            num_experts=config.get("num_experts_routed",
                                   config["n_routed_experts"]),
            top_k=config["num_experts_per_tok"],
            num_shared_experts=config["n_shared_experts"],
            first_k_dense=config["first_k_dense_replace"],
            num_layers=config["num_hidden_layers"],
            published_layer_index=config.get("published_layer_index"),
            experts_held=config.get("experts_held"),
            absent_picks=config.get("absent_experts", "nothing"),
            norm_eps=config["rms_norm_eps"],
            norm_topk_prob=config["norm_topk_prob"],
            routed_scaling_factor=config["routed_scaling_factor"],
            expert_bias_update_rate=config.get("expert_bias_update_rate",
                                               0.0),
            initializer_range=config.get("initializer_range", 0.02))
        read.update(more)       # what the caller says wins
        return cls(**read)

    @property
    def num_layers(self):
        return len(self.published_layer_index)

    def is_dense(self, i):
        return self.published_layer_index[i] < self.first_k_dense


def mixer(u, cfg, i, name):
    return layers.mla_attention(
        u, cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_dim,
        cfg.kv_rank, epsilon=cfg.norm_eps,
        param_initializer=moe_decoder.init(cfg), name=name + "_mla",
        rope_theta=cfg.rope_theta)


def kimi_vl_decoder(token_ids, cfg, is_test=False):
    """Embed -> the layers -> final RMS norm; (B, T, d) in cfg.dtype."""
    return moe_decoder.decoder(token_ids, cfg, PREFIX, mixer,
                               is_test=is_test)


def kimi_vl_pretrain_program(cfg, batch_size, seq_len, optimizer_fn=None,
                             is_test=False):
    """Next-token LM over token ids alone: feeds token_ids/labels (N,T,1)
    int64 + loss_mask (N,T,1) float32 (1 = predict here). Fetches: `loss`,
    and `expert_load`, each expert layer's kept count of picks an expert
    (`<layer>_expert_load`, int32 (num_experts,)), in layer order."""
    main, startup, feeds, fetch = moe_decoder.pretrain_program(
        cfg, seq_len, PREFIX, mixer, optimizer_fn=optimizer_fn,
        is_test=is_test)
    block = main.global_block()
    fetch["expert_load"] = [
        block.var("%s_layer_%d_expert_load" % (PREFIX, i))
        for i in range(cfg.num_layers) if not cfg.is_dense(i)]
    return main, startup, feeds, fetch
