"""The `nemotron_h` stack (static graph): Mamba-2 layers, sparse-expert
layers with non-gated relu^2 experts and a shared expert, and grouped-query
attention layers, each layer ONE block alone behind one norm. It is the
stack the published `config.json` of
huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-BF16 describes,
fed token ids and trained on next-token loss. That model's second, denoiser
tower (adaLN, bidirectional in-block attention, cross-tower conditioning,
block-diffusion decoding) is NOT built: its config has no key for any of it.

`hybrid_override_pattern` names the layers, one letter each: with
u = RMSNorm(x) (a learned scale, epsilon `norm_eps`), y = x + block(u), no
bias but the convolution's; a final norm and an untied head.
  - M (`layers.mamba2_mixer`): [z | xBC | dt] = u W_in; xBC =
    silu(causal_conv1d(xBC, 4) + b); x, B, C = split; the `mamba2_scan` op
    (one scalar decay a head, H heads of P channels, B and C shared by G
    groups at a state of N, chunks of `chunk_size`); out =
    GroupRMS(y * silu(z)) W_out.
  - E (`moe_decoder.expert_ffn`, `expert_act` "relu2"): a sigmoid router
    over all `num_experts` in float32, top `top_k` of scores + bias (a plain
    top-k), the picks' scores over their sum, times
    `routed_scaling_factor`; experts W2(relu(W1 u)^2) at `moe_ff_size`; the
    shared expert the same at `shared_ff_size` on every token, unweighted.
  - * (`attention`): q, k, v by one (d, (Hq + 2 Hkv) D) matrix, causal
    grouped-query attention at scale D^-1/2 through the flash kernels, out
    = concat W_o. NO rotary turn: the published `nemotron_h` attention
    applies none (the Mamba layers carry position).

`experts_held` says which experts this program holds (an expert-parallel
rank's share; `absent_picks="folded"` answers a pick on an absent expert
with the held expert congruent to it). The frame and the expert layer are
`models/moe_decoder.py`'s, shared with `kimi_linear` and `kimi_vl`.
"""
from paddle_tpu import layers
from paddle_tpu.layers.attention import fused_attention
from paddle_tpu.models import moe_decoder

PREFIX = "nh"
KINDS = {"M": "mamba", "E": "experts", "*": "attention"}


class NemotronHConfig(object):
    expert_act = "relu2"

    def __init__(self, vocab_size=131072, hidden_size=2688,
                 pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*"
                         "EMEMEMEME",
                 mamba_heads=64, mamba_head_dim=64, mamba_groups=8,
                 ssm_state=128, conv_width=4, chunk_size=128, num_heads=32,
                 num_kv_heads=2, head_dim=128, moe_ff_size=1856,
                 shared_ff_size=3712, num_experts=128, top_k=6,
                 num_shared_experts=1, experts_held=None,
                 absent_picks="nothing", norm_eps=1e-5, norm_topk_prob=True,
                 routed_scaling_factor=2.5, expert_bias_update_rate=0.0,
                 initializer_range=0.02, dtype="float32", recompute=False):
        unknown = sorted(set(pattern) - set(KINDS))
        if unknown:
            raise ValueError("nemotron_h builds the layers %s only, not %r "
                             "of the pattern %r"
                             % (sorted(KINDS), unknown, pattern))
        if num_heads % num_kv_heads:
            raise ValueError("%d query heads do not group over %d key/value "
                             "heads" % (num_heads, num_kv_heads))
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.pattern = pattern
        self.mamba_heads = mamba_heads
        self.mamba_head_dim = mamba_head_dim
        self.mamba_groups = mamba_groups
        self.ssm_state = ssm_state
        self.conv_width = conv_width
        self.chunk_size = chunk_size
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.moe_ff_size = moe_ff_size
        self.shared_ff_size = shared_ff_size
        self.num_experts = num_experts
        self.top_k = top_k
        self.num_shared_experts = num_shared_experts
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
        """From the keys of the published `config.json`. Beside them the
        dict may hold this program's share: `experts_held` (then
        `num_experts_routed` is the router's width and `n_routed_experts`
        the count held) and `absent_experts`. What the program does not
        build is refused by name."""
        for key, built in (("mlp_hidden_act", "relu2"),
                           ("mamba_hidden_act", "silu"), ("n_group", 1),
                           ("topk_group", 1), ("use_conv_bias", True),
                           ("mamba_proj_bias", False), ("mlp_bias", False),
                           ("attention_bias", False), ("use_bias", False),
                           ("sliding_window", None),
                           ("tie_word_embeddings", False)):
            if config.get(key, built) != built:
                raise ValueError("nemotron_h builds %s = %r only, not %r"
                                 % (key, built, config[key]))
        if len(config["hybrid_override_pattern"]) \
                != config["num_hidden_layers"]:
            raise ValueError("hybrid_override_pattern needs one letter a "
                             "layer")
        read = dict(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            pattern=config["hybrid_override_pattern"],
            mamba_heads=config["mamba_num_heads"],
            mamba_head_dim=config["mamba_head_dim"],
            mamba_groups=config["n_groups"],
            ssm_state=config["ssm_state_size"],
            conv_width=config["conv_kernel"],
            chunk_size=config["chunk_size"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            moe_ff_size=config["moe_intermediate_size"],
            shared_ff_size=config["moe_shared_expert_intermediate_size"],
            num_experts=config.get("num_experts_routed",
                                   config["n_routed_experts"]),
            top_k=config["num_experts_per_tok"],
            num_shared_experts=config["n_shared_experts"],
            experts_held=config.get("experts_held"),
            absent_picks=config.get("absent_experts", "nothing"),
            norm_eps=config["layer_norm_epsilon"],
            norm_topk_prob=config["norm_topk_prob"],
            routed_scaling_factor=config["routed_scaling_factor"],
            expert_bias_update_rate=config.get("expert_bias_update_rate",
                                               0.0),
            initializer_range=config.get("initializer_range", 0.02))
        read.update(more)       # what the caller says wins
        return cls(**read)

    @property
    def num_layers(self):
        return len(self.pattern)

    def kind(self, i):
        return KINDS[self.pattern[i]]


def attention(u, cfg, name):
    """Causal grouped-query attention, position-free: query head h reads
    key/value head h // (Hq / Hkv)."""
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def heads(m, count):
        return layers.transpose(layers.reshape(m, [0, 0, count, dh]),
                                [0, 2, 1, 3])

    q, k, v = layers.split(
        layers.fc(u, (hq + 2 * hkv) * dh, num_flatten_dims=2,
                  param_attr=moe_decoder.weight(cfg, name + "_qkv.w_0"),
                  bias_attr=False),
        [hq * dh, hkv * dh, hkv * dh], dim=2)
    o = fused_attention(heads(q, hq), heads(k, hkv), heads(v, hkv),
                        scale=dh ** -0.5, causal=True)
    o = layers.reshape(layers.transpose(o, [0, 2, 1, 3]), [0, 0, hq * dh])
    return layers.fc(o, cfg.hidden_size, num_flatten_dims=2,
                     param_attr=moe_decoder.weight(cfg, name + "_out.w_0"),
                     bias_attr=False)


def block(u, cfg, i, name):
    """Layer i's one block over its normed input: out, or (out, load)."""
    kind = cfg.kind(i)
    if kind == "mamba":
        return layers.mamba2_mixer(
            u, cfg.mamba_heads, cfg.mamba_head_dim, cfg.mamba_groups,
            cfg.ssm_state, conv_width=cfg.conv_width,
            chunk_size=cfg.chunk_size, epsilon=cfg.norm_eps,
            param_initializer=moe_decoder.init(cfg), name=name + "_mamba")
    if kind == "experts":
        return moe_decoder.expert_ffn(u, cfg, name)
    return attention(u, cfg, name + "_attn")


def nemotron_h_decoder(token_ids, cfg, is_test=False):
    """Embed -> the layers -> final RMS norm; (B, T, d) in cfg.dtype."""
    return moe_decoder.decoder(token_ids, cfg, PREFIX, block,
                               is_test=is_test,
                               frame=moe_decoder.one_block_layer)


def nemotron_h_pretrain_program(cfg, batch_size, seq_len, optimizer_fn=None,
                                is_test=False):
    """Next-token LM over token ids: feeds token_ids/labels (N,T,1) int64 +
    loss_mask (N,T,1) float32 (1 = predict here). Fetches: `loss`, and
    `expert_load`, each expert layer's kept count of picks an expert
    (`<layer>_expert_load`, int32 (num_experts,)), in layer order."""
    main, startup, feeds, fetch = moe_decoder.pretrain_program(
        cfg, seq_len, PREFIX, block, optimizer_fn=optimizer_fn,
        is_test=is_test, frame=moe_decoder.one_block_layer)
    blk = main.global_block()
    fetch["expert_load"] = [
        blk.var("%s_layer_%d_expert_load" % (PREFIX, i))
        for i in range(cfg.num_layers) if cfg.kind(i) == "experts"]
    return main, startup, feeds, fetch
