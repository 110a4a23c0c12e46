"""State-space and gated-layer building blocks: the selective scan, the
depthwise causal convolution in front of it, and RMS norm. A Mamba layer,
a gated memory unit or a gated MLP composes from these with `fc`, `silu`,
`softplus` and `elementwise_mul` (models/phi4flash.py)."""
from ..initializer import ConstantInitializer
from ..layer_helper import LayerHelper

__all__ = ["selective_scan", "causal_conv1d", "rms_norm"]


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
