"""What the decoders over sparse experts share (`models/kimi_linear.py`,
`models/kimi_vl.py`, `models/nemotron_h.py`, `models/sdar_moe.py`): the
blocks behind a layer's mixer and the frame around the layers. A model
brings its config, its mixer and a prefix for its parameter names; `cfg` is
read for `hidden_size`, `vocab_size`, `norm_eps`, `initializer_range`,
`dtype`, `recompute`, `num_layers`, and for the expert layer `moe_ff_size`,
`num_experts`, `top_k`, `num_shared_experts`, `experts_held`,
`norm_topk_prob`, `routed_scaling_factor`, `expert_bias_update_rate` and,
where it has them, `absent_picks`, `scoring` ("softmax": the top-k of the
logits, weights the softmax over the picks' logits, no expert bias; default
"sigmoid"), `expert_act` ("relu2": the experts and the shared expert are NOT
gated, W_d(relu(W_u u)^2); default the gated silu form) and `shared_ff_size`
(the shared expert's width where that is no multiple of the experts').

Two frames. `layer` (the default; the Kimis'): every layer is
`h = x + mixer(RMSNorm(x)); y = h + FFN(RMSNorm(h))`, the FFN a dense gated
MLP of width `cfg.ff_size` where `cfg.is_dense(i)`, else
`shared(u) + moe_ffn(u)`. `one_block_layer` (`nemotron_h`'s): a layer is ONE
block alone behind one norm, `y = x + block(RMSNorm(x))`, the block a mixer
or the expert feed-forward, by `mixer(u, cfg, i, name)`'s own choice.
Either way a final norm and an untied float32 head through
`fused_mlm_head_loss`. Each layer is a `recompute_segment` under
`cfg.recompute`; an expert layer's load count leaves its segment as a second
result and `layers.moe_balance` keeps it there."""
import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.initializer import TruncatedNormalInitializer
from paddle_tpu.models.gpt import masked_mean_weights
from paddle_tpu.param_attr import ParamAttr


def init(cfg):
    return TruncatedNormalInitializer(scale=cfg.initializer_range)


def weight(cfg, name):
    return ParamAttr(name=name, initializer=init(cfg))


def norm(x, cfg, name):
    return layers.rms_norm(x, epsilon=cfg.norm_eps,
                           param_attr=ParamAttr(name=name + "_s"))


def gated_mlp(u, width, cfg, name):
    """W_d(silu(W_g u) * W_u u); W_g and W_u are one (d, 2 width) matrix."""
    gate, up = layers.split(
        layers.fc(u, 2 * width, num_flatten_dims=2,
                  param_attr=weight(cfg, name + "_gate_up.w_0"),
                  bias_attr=False), 2, dim=2)
    return layers.fc(layers.elementwise_mul(layers.silu(gate), up),
                     cfg.hidden_size, num_flatten_dims=2,
                     param_attr=weight(cfg, name + "_down.w_0"),
                     bias_attr=False)


def relu2_mlp(u, width, cfg, name):
    """W_d(relu(W_u u)^2): the non-gated MLP."""
    up = layers.fc(u, width, num_flatten_dims=2,
                   param_attr=weight(cfg, name + "_up.w_0"), bias_attr=False)
    return layers.fc(layers.square(layers.relu(up)), cfg.hidden_size,
                     num_flatten_dims=2,
                     param_attr=weight(cfg, name + "_down.w_0"),
                     bias_attr=False)


def expert_ffn(u, cfg, name):
    """(shared(u) + the held experts' part (B,T,d), load): a sigmoid router
    over all `num_experts`, top `top_k` of scores + bias, the picks' scores
    over their sum, times `routed_scaling_factor` (under `cfg.scoring`
    "softmax" `moe_ffn`'s softmax form); `num_shared_experts`
    shared experts side by side are ONE MLP of their widths' sum (the same
    products; `cfg.shared_ff_size` where the config states that width),
    every token, added unweighted. Experts and shared expert are gated silu
    MLPs, or under `cfg.expert_act` "relu2" the non-gated
    W_d(relu(W_u u)^2)."""
    plain = getattr(cfg, "expert_act", "silu") == "relu2"
    out, load = layers.moe_ffn(
        layers.reshape(u, [-1, cfg.hidden_size]), cfg.num_experts, cfg.top_k,
        cfg.moe_ff_size, experts_held=cfg.experts_held,
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor,
        router_attr=weight(cfg, name + "_router.w_0"),
        gate_up_attr=weight(cfg, name + ("_experts_up" if plain
                                         else "_experts_gate_up")),
        down_attr=weight(cfg, name + "_experts_down"), name=name,
        absent=getattr(cfg, "absent_picks", "nothing"),
        scoring=getattr(cfg, "scoring", "sigmoid"),
        gate="relu2" if plain else "silu")
    out = layers.reshape(out, [-1, u.shape[1], cfg.hidden_size])
    if cfg.num_shared_experts:
        width = getattr(cfg, "shared_ff_size", None) \
            or cfg.moe_ff_size * cfg.num_shared_experts
        out = layers.elementwise_add(out, (relu2_mlp if plain else gated_mlp)(
            u, width, cfg, name + "_shared"))
    return out, load


def layer(x, cfg, i, name, mixer):
    """Layer i under the parameter prefix `name`, `mixer(u, cfg, i, name)`
    its mixer: [x'] for a dense layer, [x', load] for an expert layer."""
    h = layers.elementwise_add(
        x, mixer(norm(x, cfg, name + "_attn_norm"), cfg, i, name))
    u = norm(h, cfg, name + "_ffn_norm")
    if cfg.is_dense(i):
        return [layers.elementwise_add(
            h, gated_mlp(u, cfg.ff_size, cfg, name + "_mlp"))]
    out, load = expert_ffn(u, cfg, name)
    return [layers.elementwise_add(h, out), load]


def one_block_layer(x, cfg, i, name, block):
    """Layer i as ONE block behind one norm, `y = x + block(RMSNorm(x))`:
    `block(u, cfg, i, name)` gives out, or (out, load) where it is an
    expert layer. [y] or [y, load]."""
    got = block(norm(x, cfg, name + "_norm"), cfg, i, name)
    out, load = got if isinstance(got, tuple) else (got, None)
    y = layers.elementwise_add(x, out)
    return [y] if load is None else [y, load]


def decoder(token_ids, cfg, prefix, mixer, is_test=False, frame=layer):
    """Embed -> the layers -> final RMS norm; (B, T, d) in cfg.dtype.
    Parameters are `<prefix>_word_embedding`, `<prefix>_layer_<i>_*`,
    `<prefix>_norm_f_s`. `frame` is `layer` or `one_block_layer`."""
    x = layers.embedding(token_ids, [cfg.vocab_size, cfg.hidden_size],
                         param_attr=weight(cfg, prefix + "_word_embedding"),
                         dtype="float32")
    if cfg.dtype == "bfloat16":
        x = layers.cast(x, "bfloat16")
    for i in range(cfg.num_layers):
        name = "%s_layer_%d" % (prefix, i)

        def run(h, i=i, name=name):
            return frame(h, cfg, i, name, mixer)

        if cfg.recompute and not is_test:
            outs = layers.recompute_segment(run, [x])
        else:
            outs = run(x)
        outs = outs if isinstance(outs, (list, tuple)) else [outs]
        x = outs[0]
        if len(outs) > 1:
            layers.moe_balance(
                outs[1], name, cfg.experts_held,
                0.0 if is_test else cfg.expert_bias_update_rate)
    return norm(x, cfg, prefix + "_norm_f")


def pretrain_program(cfg, seq_len, prefix, mixer, optimizer_fn=None,
                     is_test=False, frame=layer):
    """Next-token LM: feeds token_ids/labels (N,T,1) int64 + loss_mask
    (N,T,1) float32 (1 = predict here). The head is its own (vocab, d)
    matrix `<prefix>_lm_head` (untied), through the fused head, in bf16
    with f32 accumulation when cfg.dtype is bfloat16."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        tok = layers.data("token_ids", [seq_len, 1], dtype="int64")
        lbl = layers.data("labels", [seq_len, 1], dtype="int64")
        lmask = layers.data("loss_mask", [seq_len, 1], dtype="float32")
        h = decoder(tok, cfg, prefix, mixer, is_test=is_test, frame=frame)
        head = layers.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], "float32",
            attr=weight(cfg, prefix + "_lm_head"))
        loss = layers.fused_mlm_head_loss(
            layers.reshape(h, [-1, cfg.hidden_size]), head,
            layers.reshape(lbl, [-1, 1]), cast_bf16=cfg.dtype == "bfloat16",
            token_weight=masked_mean_weights(lmask))
        if optimizer_fn is not None:
            optimizer_fn(loss)
    return main, startup, ["token_ids", "labels", "loss_mask"], {"loss": loss}
