"""Phi-4-mini-flash-reasoning (static graph): a decoder-hybrid-decoder
(SambaY, arXiv:2507.06607) with differential attention (arXiv:2410.05258).

Every layer is `h = x + Mixer(LN1(x)); x' = h + MLP(LN2(h))` with the gated
MLP `(silu(u Wg) * (u Wu)) Wd`. The mixer of layer i of L (L % 4 == 0):
  - i < L/2, even: "mamba" — in-projection to [xs, z], depthwise causal
    conv + silu, the selective scan, gate by silu(z), out-projection;
  - i < L/2, odd: "window" — differential attention over the last W keys;
  - i = L/2: "memory" — a Mamba layer whose scan output before the gate
    is kept as the memory M;
  - i = L/2 + 1: "full" — full causal differential attention whose keys
    and values are kept as K*, V*;
  - i >= L/2 + 2, even: "gmu" — gated memory unit `(M * silu(u W1)) W2`;
  - i >= L/2 + 2, odd: "cross" — differential attention with its own
    query and output projections over K*, V* (causal).
Differential attention, grouped: 40 query heads of 64 pair up (q_2j,
q_2j+1), 20 key heads pair up (k_2g, k_2g+1), values concatenate to 10 heads
of 128; pair j reads group j // 2; `o_j = (P1_j - lam * P2_j) v_g`, then an
RMS norm over 128 and the factor (1 - lam_init). Both softmaxes of all pairs
are ONE flash call: the even heads and the odd heads stack on the batch axis
(q (2B, 20, T, 64), k (2B, 10, T, 64), v (2B, 10, T, 128) with v repeated),
so the kernels see plain grouped-query attention with Dv = 2 D.

TPU-first choices as models/gpt.py: bf16 activations, the fused tied head
(`fused_mlm_head_loss`), each layer a `recompute_segment`; M, K*, V* leave
their layer's segment as extra outputs and enter every later cross-decoder
segment as inputs, so their cotangents add up over the consumers.
"""
import math

import numpy as np

import paddle_tpu as pt
from paddle_tpu import layers
from paddle_tpu.initializer import (ConstantInitializer,
                                    NormalInitializer,
                                    NumpyArrayInitializer,
                                    TruncatedNormalInitializer)
from paddle_tpu.layers.attention import fused_attention
from paddle_tpu.models.gpt import masked_mean_weights
from paddle_tpu.param_attr import ParamAttr

KINDS = ("mamba", "window", "memory", "full", "gmu", "cross")


def layout(num_layers):
    """(layer kinds, published layer indices) of the published rule."""
    if num_layers % 4:
        raise ValueError("the layout rule needs num_layers %% 4 == 0, got %d"
                         % num_layers)
    half = num_layers // 2
    kinds = []
    for i in range(num_layers):
        if i < half:
            kinds.append("mamba" if i % 2 == 0 else "window")
        elif i == half:
            kinds.append("memory")
        elif i == half + 1:
            kinds.append("full")
        else:
            kinds.append("gmu" if i % 2 == 0 else "cross")
    return kinds, list(range(num_layers))


class Phi4FlashConfig(object):
    def __init__(self, vocab_size=200064, hidden_size=2560, num_layers=32,
                 num_heads=40, num_kv_heads=20, head_dim=64, ff_size=10240,
                 ssm_inner=None, ssm_state=16, ssm_conv=4, ssm_dt_rank=None,
                 window=512, layer_kinds=None, published_layer_index=None,
                 layer_norm_eps=1e-5, initializer_range=0.02,
                 dtype="float32", recompute=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.ff_size = ff_size
        self.ssm_inner = ssm_inner or 2 * hidden_size
        self.ssm_state = ssm_state
        self.ssm_conv = ssm_conv
        self.ssm_dt_rank = ssm_dt_rank or -(-hidden_size // 16)
        self.window = window
        if layer_kinds is None:
            layer_kinds, published_layer_index = layout(num_layers)
        self.layer_kinds = list(layer_kinds)
        self.published_layer_index = list(
            published_layer_index or range(len(layer_kinds)))
        self.layer_norm_eps = layer_norm_eps
        self.initializer_range = initializer_range
        self.dtype = dtype
        self.recompute = recompute
        self._check()

    def _check(self):
        kinds = self.layer_kinds
        if set(kinds) - set(KINDS):
            raise ValueError("unknown layer kinds %r" % (set(kinds)
                                                         - set(KINDS)))
        if len(self.published_layer_index) != len(kinds):
            raise ValueError("published_layer_index needs one entry a layer")
        if self.num_heads % 2 or self.num_kv_heads % 2 \
                or (self.num_heads // 2) % (self.num_kv_heads // 2):
            raise ValueError(
                "differential attention pairs heads: %d query and %d "
                "key/value heads do not pair into whole groups"
                % (self.num_heads, self.num_kv_heads))
        for i, kind in enumerate(kinds):
            if kind == "gmu" and "memory" not in kinds[:i]:
                raise ValueError("layer %d: a gmu before any memory layer"
                                 % i)
            if kind == "cross" and "full" not in kinds[:i]:
                raise ValueError("layer %d: cross attention before any "
                                 "full-attention layer" % i)

    @property
    def num_layers(self):
        return len(self.layer_kinds)


def lambda_init(published_index):
    return 0.8 - 0.6 * math.exp(-0.3 * published_index)


def _w(cfg, name):
    return ParamAttr(name=name, initializer=TruncatedNormalInitializer(
        scale=cfg.initializer_range))


def _ln(x, cfg, name):
    return layers.layer_norm(x, begin_norm_axis=2,
                             epsilon=cfg.layer_norm_eps,
                             param_attr=ParamAttr(name=name + "_s"),
                             bias_attr=ParamAttr(name=name + "_b"))


def gated_mlp(u, cfg, name):
    """(silu(u Wg) * (u Wu)) Wd; Wg and Wu are one (d, 2F) matrix."""
    both = layers.fc(u, 2 * cfg.ff_size, num_flatten_dims=2,
                     param_attr=_w(cfg, name + "_mlp_gate_up.w_0"),
                     bias_attr=False)
    gate, up = layers.split(both, 2, dim=2)
    return layers.fc(layers.elementwise_mul(layers.silu(gate), up),
                     cfg.hidden_size, num_flatten_dims=2,
                     param_attr=_w(cfg, name + "_mlp_down.w_0"),
                     bias_attr=False)


def _dt_bias_init(e, seed=0, dt_min=1e-3, dt_max=0.1):
    """Mamba's dt bias: inverse softplus of dt log-uniform in
    [dt_min, dt_max]."""
    rng = np.random.RandomState(seed)
    dt = np.exp(rng.uniform(size=e) * (math.log(dt_max) - math.log(dt_min))
                + math.log(dt_min)).clip(min=1e-4)
    return (dt + np.log(-np.expm1(-dt))).astype("float32")


def mamba_mixer(u, cfg, name):
    """The Mamba-1 mixer. Returns (out (B,T,d), y (B,T,E)): y is the scan's
    output before the gate, the memory M of the "memory" layer."""
    e, n, r = cfg.ssm_inner, cfg.ssm_state, cfg.ssm_dt_rank
    both = layers.fc(u, 2 * e, num_flatten_dims=2,
                     param_attr=_w(cfg, name + "_in_proj.w_0"),
                     bias_attr=False)
    xs, z = layers.split(both, 2, dim=2)
    xc = layers.causal_conv1d(
        xs, cfg.ssm_conv, act="silu",
        param_attr=_w(cfg, name + "_conv.w_0"),
        bias_attr=ParamAttr(name=name + "_conv.b_0"))
    dbc = layers.fc(xc, r + 2 * n, num_flatten_dims=2,
                    param_attr=_w(cfg, name + "_x_proj.w_0"),
                    bias_attr=False)
    dr, b, c = layers.split(dbc, [r, n, n], dim=2)
    delta = layers.fc(
        dr, e, num_flatten_dims=2, act="softplus",
        param_attr=_w(cfg, name + "_dt_proj.w_0"),
        bias_attr=ParamAttr(name=name + "_dt_proj.b_0",
                            initializer=NumpyArrayInitializer(
                                _dt_bias_init(e))))
    a_log = layers.create_parameter(
        [e, n], "float32", name=name + "_A_log",
        default_initializer=NumpyArrayInitializer(np.tile(np.log(np.arange(
            1, n + 1, dtype="float32")), (e, 1))))
    d = layers.create_parameter(
        [e], "float32", name=name + "_D",
        default_initializer=ConstantInitializer(1.0))
    a = layers.scale(layers.exp(a_log), scale=-1.0)
    y = layers.selective_scan(xc, delta, a, b, c, d)
    out = layers.fc(layers.elementwise_mul(y, layers.silu(z)),
                    cfg.hidden_size, num_flatten_dims=2,
                    param_attr=_w(cfg, name + "_out_proj.w_0"),
                    bias_attr=False)
    return out, y


def gmu_mixer(u, memory, cfg, name):
    """Gated memory unit: (M * silu(u W1)) W2."""
    gate = layers.fc(u, cfg.ssm_inner, num_flatten_dims=2, act="silu",
                     param_attr=_w(cfg, name + "_gmu_in.w_0"),
                     bias_attr=False)
    return layers.fc(layers.elementwise_mul(memory, gate), cfg.hidden_size,
                     num_flatten_dims=2,
                     param_attr=_w(cfg, name + "_gmu_out.w_0"),
                     bias_attr=False)


def _stack_pairs(x, heads, width):
    """(B, T, heads*width) with heads interleaved (even, odd, ...) ->
    (2B, heads/2, T, width): the even heads of every row, then the odd."""
    t = x.shape[1]
    x = layers.reshape(x, [0, 0, heads // 2, 2, width])
    x = layers.transpose(x, [3, 0, 2, 1, 4])
    return layers.reshape(x, [-1, heads // 2, t, width])


def _stack_values(v, kv_heads, width):
    """(B, T, kv_heads*width) -> (2B, kv_heads/2, T, 2*width): each group's
    two value heads side by side, the same for both softmaxes."""
    v = layers.reshape(v, [0, 0, kv_heads // 2, 2 * width])
    v = layers.transpose(v, [0, 2, 1, 3])
    return layers.concat([v, v], axis=0)


def _lambda(cfg, name, lam_init):
    """exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init, a float32 [1]."""
    def dot(a, b):
        pa, pb = (layers.create_parameter(
            [cfg.head_dim], "float32", name="%s_lambda_%s" % (name, s),
            default_initializer=NormalInitializer(0.0, 0.1)) for s in (a, b))
        return layers.exp(layers.reduce_sum(layers.elementwise_mul(pa, pb)))
    return layers.scale(layers.elementwise_sub(dot("q1", "k1"),
                                               dot("q2", "k2")),
                        bias=lam_init)


def diff_attention(u, cfg, name, published_index, window=None, kv=None):
    """Differential grouped attention. With `kv` None the layer projects
    its own q, k, v; else `kv` = (K*, V*) in the stacked layout and only q
    is projected. Returns (out (B,T,d), (k, v) in the stacked layout)."""
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    if kv is None:
        qkv = layers.fc(u, (hq + 2 * hkv) * dh, num_flatten_dims=2,
                        param_attr=_w(cfg, name + "_qkv.w_0"),
                        bias_attr=ParamAttr(name=name + "_qkv.b_0"))
        q, k, v = layers.split(qkv, [hq * dh, hkv * dh, hkv * dh], dim=2)
        kv = (_stack_pairs(k, hkv, dh), _stack_values(v, hkv, dh))
    else:
        q = layers.fc(u, hq * dh, num_flatten_dims=2,
                      param_attr=_w(cfg, name + "_q.w_0"),
                      bias_attr=ParamAttr(name=name + "_q.b_0"))
    o = fused_attention(_stack_pairs(q, hq, dh), kv[0], kv[1],
                        scale=1.0 / math.sqrt(dh), causal=True,
                        window=window)
    o1, o2 = layers.split(layers.cast(o, "float32"), 2, dim=0)
    lam_init = lambda_init(published_index)
    o = layers.elementwise_sub(
        o1, layers.elementwise_mul(o2, _lambda(cfg, name, lam_init)))
    o = layers.rms_norm(o, epsilon=cfg.layer_norm_eps,
                        param_attr=ParamAttr(name=name + "_subln_s"))
    o = layers.cast(layers.scale(o, scale=1.0 - lam_init), u.dtype)
    o = layers.reshape(layers.transpose(o, [0, 2, 1, 3]),
                       [0, 0, hq * dh])
    out = layers.fc(o, cfg.hidden_size, num_flatten_dims=2,
                    param_attr=_w(cfg, name + "_out.w_0"),
                    bias_attr=ParamAttr(name=name + "_out.b_0"))
    return out, kv


def hybrid_layer(x, cfg, i, shared):
    """Layer i. `shared` = [M, K*, V*] as far as earlier layers have made
    them (None before). Returns [x', then whatever this layer adds to
    `shared`: M for "memory", K*, V* for "full"]."""
    kind = cfg.layer_kinds[i]
    name = "phi_layer_%d" % i
    u = _ln(x, cfg, name + "_ln1")
    extra = []
    if kind in ("mamba", "memory"):
        mix, y = mamba_mixer(u, cfg, name)
        if kind == "memory":
            extra = [y]
    elif kind == "gmu":
        mix = gmu_mixer(u, shared[0], cfg, name)
    else:
        mix, kv = diff_attention(
            u, cfg, name, cfg.published_layer_index[i],
            window=cfg.window if kind == "window" else None,
            kv=(shared[1], shared[2]) if kind == "cross" else None)
        if kind == "full":
            extra = list(kv)
    h = layers.elementwise_add(x, mix)
    out = layers.elementwise_add(
        h, gated_mlp(_ln(h, cfg, name + "_ln2"), cfg, name))
    return [out] + extra


def phi4flash_decoder(token_ids, cfg, is_test=False):
    """Embed -> the hybrid layers -> final LN; (B, T, d) in cfg.dtype."""
    x = layers.embedding(token_ids, [cfg.vocab_size, cfg.hidden_size],
                         param_attr=_w(cfg, "phi_word_embedding"),
                         dtype="float32")
    if cfg.dtype == "bfloat16":
        x = layers.cast(x, "bfloat16")
    shared = [None, None, None]         # M, K*, V*
    for i, kind in enumerate(cfg.layer_kinds):
        reads = {"gmu": [0], "cross": [1, 2]}.get(kind, [])
        ins = [x] + [shared[j] for j in reads]

        def run(h, *got, i=i, reads=reads):
            have = list(shared)
            for j, v in zip(reads, got):
                have[j] = v
            return hybrid_layer(h, cfg, i, have)

        if cfg.recompute and not is_test:
            outs = layers.recompute_segment(run, ins)
        else:
            outs = run(*ins)
        outs = outs if isinstance(outs, (list, tuple)) else [outs]
        x = outs[0]
        if kind == "memory":
            shared[0] = outs[1]
        elif kind == "full":
            shared[1], shared[2] = outs[1], outs[2]
    return _ln(x, cfg, "phi_lnf")


def phi4flash_pretrain_program(cfg, batch_size, seq_len, optimizer_fn=None,
                               is_test=False):
    """Next-token LM: feeds token_ids/labels (N,T,1) int64 + loss_mask
    (N,T,1) float32 (1 = predict here). Tied-embedding decode through the
    fused head, in bf16 with f32 accumulation when cfg.dtype is bfloat16."""
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        tok = layers.data("token_ids", [seq_len, 1], dtype="int64")
        lbl = layers.data("labels", [seq_len, 1], dtype="int64")
        lmask = layers.data("loss_mask", [seq_len, 1], dtype="float32")
        h = phi4flash_decoder(tok, cfg, is_test=is_test)
        emb = main.global_block().var("phi_word_embedding")
        loss = layers.fused_mlm_head_loss(
            layers.reshape(h, [-1, cfg.hidden_size]), emb,
            layers.reshape(lbl, [-1, 1]), cast_bf16=cfg.dtype == "bfloat16",
            token_weight=masked_mean_weights(lmask))
        if optimizer_fn is not None:
            optimizer_fn(loss)
    return main, startup, ["token_ids", "labels", "loss_mask"], {"loss": loss}
