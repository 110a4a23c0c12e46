"""State-space and gated-layer building blocks: the selective scan, the
depthwise causal convolution in front of it, and RMS norm. A Mamba layer,
a gated memory unit or a gated MLP composes from these with `fc`, `silu`,
`softplus` and `elementwise_mul` (models/phi4flash.py). `mamba2_mixer` is
the whole Mamba-2 mixer (models/nemotron_h.py): its projections, the
convolution, the `mamba2_scan` op and the gated group norm behind it."""
from ..initializer import ConstantInitializer
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr

__all__ = ["selective_scan", "causal_conv1d", "rms_norm", "mamba2_mixer"]


def selective_scan(x, delta, a, b, c, d, name=None):
    """h_t = exp(delta_t * A) * h_{t-1} + (delta_t * x_t) * B_t;
    y_t = h_t . C_t + D * x_t. x, delta: (B, T, E); a: (E, N), negative;
    b, c: (B, T, N); d: (E,). Returns y (B, T, E) in x's dtype; the state
    and exp are float32 (ops/pallas/selective_scan.py)."""
    helper = LayerHelper("selective_scan", name=name)
    out = helper.create_variable_for_type_inference(x.dtype, x.shape)
    helper.append_op(
        "selective_scan",
        inputs={"X": [x.name], "Delta": [delta.name], "A": [a.name],
                "B": [b.name], "C": [c.name], "D": [d.name]},
        outputs={"Out": [out.name]})
    return out


def mamba2_mixer(x, num_heads, head_dim, n_groups, state_size,
                 conv_width=4, chunk_size=128, epsilon=1e-5,
                 param_initializer=None, name=None):
    """The Mamba-2 mixer, causal, x (B, T, d) -> (B, T, d). With H heads of
    P channels (E = H P), G groups and a state of N:
      [z | xBC | dt] = x W_in, widths E | E + 2 G N | H (no bias);
      xBC = silu(causal_conv1d(xBC, conv_width) + bias), depthwise;
      x' (T, H, P), B, C (T, G, N) = split(xBC);
      y = mamba2_scan(x', dt, dt_bias, A_log, B, C, D)  (ops/ssm_ops.py:
        dt_t = softplus(dt_t + dt_bias), a_t = exp(-dt_t exp(A_log)) a
        scalar a head, S_t = a_t S_{t-1} + dt_t B_t x'_t^T, head h reads
        group h // (H / G), y_t = S_t^T C_t + D_h x'_t; chunks of
        `chunk_size`);
      out = GroupRMS(y * silu(z); G groups of E / G, a scale of E) W_out.
    `A_log` and `dt_bias` (H,) are float32 and start at zeros, `D` at ones
    (a checkpoint brings its own)."""
    from .nn import fc, reshape, split
    helper = LayerHelper("mamba2_mixer", name=name)
    name = helper.name
    inner, bc = num_heads * head_dim, n_groups * state_size
    if num_heads % n_groups or inner % n_groups:
        raise ValueError("mamba2_mixer: %d heads of %d do not split over %d "
                         "groups" % (num_heads, head_dim, n_groups))

    def attr(suffix):
        return ParamAttr(name=name + suffix, initializer=param_initializer)

    def per_head(suffix, value):
        return helper.create_parameter(
            ParamAttr(name=name + suffix), shape=[num_heads],
            dtype="float32", default_initializer=ConstantInitializer(value))

    def op(op_type, inputs, slot, shape, attrs):
        out = helper.create_variable_for_type_inference(x.dtype, shape)
        helper.append_op(op_type, inputs=inputs, outputs={slot: [out.name]},
                         attrs=attrs)
        return out

    z, xbc, dt = split(
        fc(x, 2 * inner + 2 * bc + num_heads, num_flatten_dims=2,
           param_attr=attr("_in_proj.w_0"), bias_attr=False),
        [inner, inner + 2 * bc, num_heads], dim=2)
    xbc = causal_conv1d(xbc, conv_width, act="silu",
                        param_attr=attr("_conv.w_0"),
                        bias_attr=ParamAttr(name=name + "_conv.b_0"))
    xs, b, c = split(xbc, [inner, bc, bc], dim=2)
    y = op("mamba2_scan",
           {"X": [reshape(xs, [0, 0, num_heads, head_dim]).name],
            "Dt": [dt.name], "DtBias": [per_head("_dt_bias", 0.0).name],
            "ALog": [per_head("_A_log", 0.0).name],
            "B": [reshape(b, [0, 0, n_groups, state_size]).name],
            "C": [reshape(c, [0, 0, n_groups, state_size]).name],
            "D": [per_head("_D", 1.0).name]},
           "Out", (x.shape[0], x.shape[1], num_heads, head_dim),
           {"chunk_size": int(chunk_size)})
    scale = helper.create_parameter(
        ParamAttr(name=name + "_norm_s"), shape=[inner], dtype="float32",
        default_initializer=ConstantInitializer(1.0))
    gated = op("mamba2_gate_norm",
               {"X": [reshape(y, [0, 0, inner]).name], "Z": [z.name],
                "Scale": [scale.name]},
               "Y", (x.shape[0], x.shape[1], inner),
               {"groups": int(n_groups), "epsilon": float(epsilon)})
    return fc(gated, x.shape[-1], num_flatten_dims=2,
              param_attr=attr("_out_proj.w_0"), bias_attr=False)


def causal_conv1d(input, width, param_attr=None, bias_attr=None, act=None,
                  name=None):
    """Depthwise causal convolution along time of (B, T, E): a (width, E)
    weight and an (E,) bias (`bias_attr=False`: none), in the input's
    dtype; `act` is applied to the result. The models' uses: width 4 with a
    bias and `act="silu"` in front of the selective scan (phi4flash), width
    3 without bias or activation between two gates (lfm2moe), width 4
    without bias and `act="silu"` over the q, k, v projections of a
    delta-rule layer (`layers.kda_attention`)."""
    helper = LayerHelper("causal_conv1d", param_attr=param_attr,
                         bias_attr=bias_attr, act=act, name=name)
    e = input.shape[-1]
    w = helper.create_parameter(helper.param_attr, shape=[width, e],
                                dtype=input.dtype)
    inputs = {"X": [input.name], "W": [w.name]}
    if bias_attr is not False:
        b = helper.create_parameter(helper.bias_attr, shape=[e],
                                    dtype=input.dtype, is_bias=True)
        inputs["Bias"] = [b.name]
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    helper.append_op("causal_conv1d", inputs=inputs,
                     outputs={"Out": [out.name]})
    return helper.append_activation(out)


def rms_norm(input, epsilon=1e-5, param_attr=None, name=None):
    """RMS norm over the last axis with a learned float32 scale
    (`param_attr=False`: none)."""
    helper = LayerHelper("rms_norm", param_attr=param_attr, name=name)
    inputs = {"X": [input.name]}
    if param_attr is not False:
        s = helper.create_parameter(
            helper.param_attr, shape=[input.shape[-1]], dtype="float32",
            default_initializer=ConstantInitializer(1.0))
        inputs["Scale"] = [s.name]
    out = helper.create_variable_for_type_inference(input.dtype, input.shape)
    helper.append_op("rms_norm", inputs=inputs, outputs={"Y": [out.name]},
                     attrs={"epsilon": epsilon})
    return out
