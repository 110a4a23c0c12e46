"""Linear and latent attention mixers: `kda_attention` (Kimi Delta
Attention: the gated delta rule of ops/linear_attn_ops.py with its
projections, short convolutions, gates and output norm) and `mla_attention`
(latent attention in its training form: the low-rank key/value latent is
decompressed in front of the flash kernels; positions are optional: with
`rope_theta` the decoupled rotary part of every query head and the one key
part the heads share are turned, by interleaved pairs, the key part once,
before it is broadcast to the heads; without it nothing is turned). Both take `heads_held=(first, count)`, as `moe_ffn` takes
`experts_held`: the layer then builds those heads' columns and rows alone
and returns the part they give (a tensor-parallel rank's share; the sum
over the ranks' results is the whole mixer's)."""
from ..initializer import ConstantInitializer
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr
from .attention import fused_attention, partial_rope
from .nn import expand, fc, reshape, split, transpose, unsqueeze
from .ssm import causal_conv1d, rms_norm
from .tensor import concat

__all__ = ["kda_attention", "mla_attention"]


def _held(heads_held, num_heads, who):
    first, count = heads_held or (0, num_heads)
    if first < 0 or count < 1 or first + count > num_heads:
        raise ValueError("%s: heads_held=(%d, %d) is no range of %d heads"
                         % (who, first, count, num_heads))
    return int(count)


def _attrs(name, param_initializer):
    def attr(suffix):
        return ParamAttr(name=name + suffix, initializer=param_initializer)
    return attr


def kda_attention(x, num_heads, head_dim, gate_rank=None, conv_width=4,
                  heads_held=None, epsilon=1e-5, param_initializer=None,
                  name=None):
    """x (B, T, d) -> (B, T, d). With H heads held, K = `head_dim`:
      q = l2norm_head(silu(conv(x Wq))), k likewise, v = silu(conv(x Wv)):
        one (d, 3 H K) matrix and one depthwise causal convolution of width
        `conv_width` over its 3 H K channels (`<name>_qkv.w_0`,
        `<name>_qkv_conv.w_0`: the same products as three of each);
      g = -exp(A_log[h]) * softplus((x W_fa) W_fb + dt_bias), the log of
        the per-channel decay, W_fa (d, `gate_rank`), W_fb (rank, H K);
      beta = sigmoid(x W_beta), one a head;
      o = the gated delta rule over (q, k, v, g, beta) at scale K^-1/2
        (`kda_attention` op: chunked, float32 state);
      out = (rmsnorm_head(o; scale of K) * sigmoid((x W_ga) W_gb)) W_o.
    `A_log` (H,) and `dt_bias` (H K,) are float32 and start at zeros (a
    decay of 1/2 a token; a checkpoint brings its own)."""
    helper = LayerHelper("kda_attention", name=name)
    name = helper.name
    attr = _attrs(name, param_initializer)
    held = _held(heads_held, num_heads, "kda_attention")
    width = held * head_dim
    rank = gate_rank or head_dim

    def proj(u, size, suffix, act=None):
        return fc(u, size, num_flatten_dims=2, param_attr=attr(suffix),
                  bias_attr=False, act=act)

    qkv = causal_conv1d(proj(x, 3 * width, "_qkv.w_0"), conv_width,
                        param_attr=attr("_qkv_conv.w_0"), bias_attr=False,
                        act="silu")
    q, k, v = split(qkv, 3, dim=2)

    def op(op_type, inputs, shape, dtype, attrs):
        out = helper.create_variable_for_type_inference(dtype, shape)
        helper.append_op(op_type, inputs=inputs, outputs={"Out": [out.name]},
                         attrs=attrs)
        return out

    by_head = (x.shape[0], x.shape[1], held, head_dim)
    q, k = (op("head_l2_norm", {"X": [m.name]}, by_head, x.dtype,
               {"head_dim": int(head_dim), "epsilon": 1e-6})
            for m in (q, k))
    a_log = helper.create_parameter(
        ParamAttr(name=name + "_A_log"), shape=[held], dtype="float32",
        default_initializer=ConstantInitializer(0.0))
    dt_bias = helper.create_parameter(
        ParamAttr(name=name + "_dt_bias"), shape=[width], dtype="float32",
        default_initializer=ConstantInitializer(0.0))
    g = op("kda_gate",
           {"X": [proj(proj(x, rank, "_f_a.w_0"), width, "_f_b.w_0").name],
            "ALog": [a_log.name], "DtBias": [dt_bias.name]},
           by_head, "float32", {"head_dim": int(head_dim)})
    beta = proj(x, held, "_beta.w_0", act="sigmoid")
    o = op("kda_attention",
           {"Q": [q.name], "K": [k.name],
            "V": [reshape(v, [0, 0, held, head_dim]).name], "G": [g.name],
            "Beta": [beta.name]},
           by_head, x.dtype, {"scale": float(head_dim) ** -0.5})
    o_scale = helper.create_parameter(
        ParamAttr(name=name + "_o_norm_s"), shape=[head_dim],
        dtype="float32", default_initializer=ConstantInitializer(1.0))
    gated = op("kda_out_norm",
               {"X": [o.name], "Scale": [o_scale.name],
                "Gate": [proj(proj(x, rank, "_g_a.w_0"), width,
                              "_g_b.w_0").name]},
               (x.shape[0], x.shape[1], width), x.dtype,
               {"epsilon": float(epsilon)})
    return proj(gated, x.shape[-1], "_out.w_0")


def mla_attention(x, num_heads, qk_nope_dim, qk_rope_dim, v_dim, kv_rank,
                  heads_held=None, epsilon=1e-5, param_initializer=None,
                  name=None, rope_theta=None):
    """Latent attention, causal, x (B, T, d) -> (B, T, d). With H heads
    held:
      q = x W_q -> (H, nope + rope) a token;
      [c | k_pe] = x W_kva -> (`kv_rank` | rope), the latent and the part
        of the key every head shares (W_kva is whole on every rank);
      [k_nope | v] = rmsnorm(c; scale of kv_rank) W_kvb -> (H, nope | v);
      where `rope_theta` is a number, rotary positions t = 0..T-1 on the
        last `rope` numbers of every query head and on k_pe, ONE vector a
        token, turned before it is broadcast to the heads (`partial_rope`:
        pair i is the neighbours (2i, 2i + 1), as the published
        latent-attention checkpoints store them, and turns by
        t * rope_theta^(-2i/rope)); where it is None
        (a `mla_use_nope` model) nothing is turned and no such op is built:
        the rope numbers are plain features;
      k = [k_nope | k_pe]; softmax(q k^T (nope + rope)^-1/2) v, causal,
      through `fused_attention` (D = nope + rope, Dv = v_dim: the flash
      kernels' fused backward); out = concat W_o.
    The latent is decompressed before the kernel and nothing is cached:
    the training form."""
    helper = LayerHelper("mla_attention", name=name)
    name = helper.name
    attr = _attrs(name, param_initializer)
    held = _held(heads_held, num_heads, "mla_attention")
    d_qk = qk_nope_dim + qk_rope_dim

    def proj(u, size, suffix):
        return fc(u, size, num_flatten_dims=2, param_attr=attr(suffix),
                  bias_attr=False)

    def head_major(m):
        return transpose(m, [0, 2, 1, 3])

    def by_head(m):
        return head_major(reshape(m, [0, 0, held, d_qk]))

    q = proj(x, held * d_qk, "_q.w_0")
    if rope_theta is None:      # op for op the program it always built
        q = by_head(q)
    latent, k_pe = split(proj(x, kv_rank + qk_rope_dim, "_kv_a.w_0"),
                         [kv_rank, qk_rope_dim], dim=2)
    if rope_theta is not None:
        q, k_pe = partial_rope(q, k_pe, qk_nope_dim, qk_rope_dim,
                               theta=rope_theta)
        q = by_head(q)
    latent = rms_norm(latent, epsilon=epsilon,
                      param_attr=ParamAttr(name=name + "_kv_a_norm_s"))
    k_nope, v = split(
        reshape(proj(latent, held * (qk_nope_dim + v_dim), "_kv_b.w_0"),
                [0, 0, held, qk_nope_dim + v_dim]),
        [qk_nope_dim, v_dim], dim=3)
    k = concat([k_nope, expand(unsqueeze(k_pe, [2]), [1, 1, held, 1])],
               axis=3)
    o = fused_attention(q, head_major(k), head_major(v),
                        scale=float(d_qk) ** -0.5, causal=True)
    o = reshape(head_major(o), [0, 0, held * v_dim])
    return proj(o, x.shape[-1], "_out.w_0")
