"""SDAR-30B-A3B-Chat (`sdar_moe`, static graph), trained as a block-diffusion
model (SDAR, arXiv:2510.06303; the objective is BD3-LMs', arXiv:2503.09573):
a Qwen3-shaped decoder over sparse experts that sees, in ONE pass, a noisy
and a clean copy of every document under the block-diffusion mask, and
learns to fill in the noisy copy's masked tokens.

A document x of T tokens is cut into blocks of `block_length` L. The host
masks each token of block b with probability t_b (its noise level) and feeds
the noisy copy beside the clean one; the model runs its layers once over the
2T rows z = [noisy | clean]. Row r has position r mod T and block
(r mod T) // L. Every layer, RMS norm with a learned scale, no bias:
    u  = RMSNorm(h)
    h1 = h + attention(u)
    x  = RMSNorm(h1)
    h' = h1 + experts(x)
- attention: q, k, v by one (d, (Hq + 2 Hkv) D) matrix; q and k get an RMS
  norm over each head's D numbers with a learned scale, then rotary
  positions over the whole head at r mod T (`rope_qk_norm(position_period=)`);
  grouped-query attention at scale D^-1/2 under the block-diffusion mask
  (`fused_attention(block_diffusion=(L, T))`): a clean query sees the clean
  keys of its own and earlier blocks, a noisy query the noisy keys of its
  own block (both directions) and the clean keys of strictly earlier blocks,
  no clean query a noisy key. The flash kernels compute the rule from row
  indices and skip the tiles nobody sees; the clean half's keys and values
  are computed once for both halves.
- experts (`moe_decoder.expert_ffn`, `scoring` "softmax"): 128 float32
  logits, the top `top_k` of them, weights the softmax over the picks'
  logits; gated silu experts of width `moe_ff_size`; no shared expert, no
  expert bias, every layer an expert layer. `experts_held` / `absent_picks`
  as in the other expert decoders (an expert-parallel rank's share).
Head and loss over the NOISY half's T rows alone: the untied (vocab, d)
float32 head through `fused_mlm_head_loss`, the label of row i the clean
token x_i itself (no shift), each row weighted by the feed `loss_weight`
(m_i / t_block(i) / (B T), made on the host with the noise, so the program
holds no random state).

What is NOT built: generation (a step that unmasks a block over a number of
denoising steps against a block-wise KV cache); that is a serving path.

The blocks behind the mixer and the frame around the layers are
`models/moe_decoder.py`'s. While obs is on, each step records a `bd.noise`
span: `masked_rows`, `rows`, `weight_sum`.
"""
import numpy as np

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.initializer import ConstantInitializer
from paddle_tpu.layer_helper import LayerHelper
from paddle_tpu.layers.attention import fused_attention
from paddle_tpu.models import moe_decoder
from paddle_tpu.param_attr import ParamAttr

PREFIX = "sdar"
NOISE_SPAN = "bd.noise"


class SdarMoeConfig(object):
    #: what `moe_decoder.expert_ffn` reads and the published config fixes
    num_shared_experts = 0
    scoring = "softmax"
    routed_scaling_factor = 1.0
    expert_bias_update_rate = 0.0

    def __init__(self, vocab_size=151936, hidden_size=2048, num_heads=32,
                 num_kv_heads=4, head_dim=128, moe_ff_size=768,
                 num_experts=128, top_k=8, num_layers=48, experts_held=None,
                 absent_picks="nothing", block_length=4, mask_token_id=None,
                 rope_theta=1000000.0, norm_eps=1e-6, norm_topk_prob=True,
                 initializer_range=0.02, dtype="float32", recompute=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.moe_ff_size = moe_ff_size
        self.num_experts = num_experts
        self.top_k = top_k
        self.num_layers = num_layers
        self.experts_held = tuple(experts_held or (0, num_experts))
        self.absent_picks = absent_picks
        self.block_length = int(block_length)
        self.mask_token_id = vocab_size - 1 if mask_token_id is None \
            else int(mask_token_id)
        self.rope_theta = rope_theta
        self.norm_eps = norm_eps
        self.norm_topk_prob = norm_topk_prob
        self.initializer_range = initializer_range
        self.dtype = dtype
        self.recompute = recompute
        if num_heads % num_kv_heads:
            raise ValueError("%d query heads do not group over %d key/value "
                             "heads" % (num_heads, num_kv_heads))
        if head_dim % 2:
            raise ValueError("rotary positions pair a head's halves: "
                             "head_dim %d is odd" % head_dim)
        if not 0 <= self.mask_token_id < vocab_size:
            raise ValueError("mask_token_id %d is no row of a table of %d"
                             % (self.mask_token_id, vocab_size))

    def is_dense(self, i):
        return False        # `mlp_only_layers` empty, `decoder_sparse_step` 1


def _heads(m, count, dh):
    return layers.transpose(layers.reshape(m, [0, 0, count, dh]),
                            [0, 2, 1, 3])


def mixer(u, cfg, i, name):
    """Grouped-query attention of layer i over the 2T rows u (B, 2T, d)
    under the block-diffusion mask, positions 0..T-1 on each half."""
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    half = u.shape[1] // 2
    if u.shape[1] != 2 * half or half % cfg.block_length:
        raise ValueError(
            "sdar_moe: %d rows are no noisy and clean copy of a document of "
            "whole blocks of %d" % (u.shape[1], cfg.block_length))
    q, k, v = layers.split(
        layers.fc(u, (hq + 2 * hkv) * dh, num_flatten_dims=2,
                  param_attr=moe_decoder.weight(cfg, name + "_qkv.w_0"),
                  bias_attr=False),
        [hq * dh, hkv * dh, hkv * dh], dim=2)
    q, k = layers.rope_qk_norm(
        q, k, dh, theta=cfg.rope_theta, epsilon=cfg.norm_eps,
        q_norm_attr=ParamAttr(name=name + "_q_norm_s"),
        k_norm_attr=ParamAttr(name=name + "_k_norm_s"),
        position_period=half)
    o = fused_attention(q, k, _heads(v, hkv, dh), scale=dh ** -0.5,
                        block_diffusion=(cfg.block_length, half))
    o = layers.reshape(layers.transpose(o, [0, 2, 1, 3]), [0, 0, hq * dh])
    return layers.fc(o, cfg.hidden_size, num_flatten_dims=2,
                     param_attr=moe_decoder.weight(cfg, name + "_out.w_0"),
                     bias_attr=False)


def sdar_decoder(rows, cfg, is_test=False):
    """Embed -> the layers -> final RMS norm over the 2T rows [noisy |
    clean] (B, 2T, 1) int64; (B, 2T, d) in cfg.dtype."""
    return moe_decoder.decoder(rows, cfg, PREFIX, mixer, is_test=is_test)


def _record_noise(loss_weight):
    """Keep (masked rows, rows, the weights' sum) of the step's
    `loss_weight` as the persistable `sdar_noise`; while obs is on
    `Executor.run` records it as a `bd.noise` span after the step."""
    helper = LayerHelper("sdar_noise")
    flat = layers.reshape(loss_weight, [-1, 1])
    masked = layers.cast(layers.greater_than(flat, layers.zeros_like(flat)),
                         "float32")
    stats = layers.concat([layers.reduce_sum(masked),
                           layers.reduce_sum(layers.ones_like(flat)),
                           layers.reduce_sum(flat)], axis=0)
    kept = helper.create_or_get_global_variable(
        PREFIX + "_noise", persistable=True, dtype="float32", shape=[3])
    helper.set_variable_initializer(kept, ConstantInitializer(0.0))
    layers.assign(stats, output=kept)

    def summarize(value):
        masked_rows, rows, weight_sum = np.asarray(value, np.float64)
        return {"masked_rows": int(masked_rows), "rows": int(rows),
                "weight_sum": float(weight_sum)}

    helper.main_program.record_step_state(NOISE_SPAN, kept.name, {},
                                          summarize)


def sdar_pretrain_program(cfg, seq_len, optimizer_fn=None, is_test=False):
    """Masked-denoising training over blocks: feeds `noisy_ids` and
    `token_ids` (N, T, 1) int64 (the document with its masked tokens
    replaced by `cfg.mask_token_id`, and the document) and `loss_weight`
    (N, T, 1) float32 (m_i / t_block(i) / (N T): 0 where the token was not
    masked). One decoder pass over the 2T rows; the head and the loss over
    the noisy half's T rows, labels the clean tokens at the same
    positions. Fetches: `loss`."""
    if seq_len % cfg.block_length:
        raise ValueError("sdar_moe: blocks of %d do not divide %d tokens"
                         % (cfg.block_length, seq_len))
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        noisy = layers.data("noisy_ids", [seq_len, 1], dtype="int64")
        tok = layers.data("token_ids", [seq_len, 1], dtype="int64")
        weight = layers.data("loss_weight", [seq_len, 1], dtype="float32")
        h = sdar_decoder(layers.concat([noisy, tok], axis=1), cfg,
                         is_test=is_test)
        h = layers.slice(h, axes=[1], starts=[0], ends=[seq_len])
        head = layers.create_parameter(
            [cfg.vocab_size, cfg.hidden_size], "float32",
            attr=moe_decoder.weight(cfg, PREFIX + "_lm_head"))
        loss = layers.fused_mlm_head_loss(
            layers.reshape(h, [-1, cfg.hidden_size]), head,
            layers.reshape(tok, [-1, 1]), cast_bf16=cfg.dtype == "bfloat16",
            token_weight=layers.reshape(weight, [-1, 1]))
        _record_noise(weight)
        if optimizer_fn is not None:
            optimizer_fn(loss)
    return main, startup, ["noisy_ids", "token_ids", "loss_weight"], \
        {"loss": loss}
