"""Sparse experts: `moe_ffn`, a dropless expert layer that is told which
experts it holds (ops/moe_ops.py), and `moe_balance`, what the step does with
the layer's load once a step: keep the count and move the expert bias."""
import numpy as np

from ..initializer import ConstantInitializer
from ..layer_helper import LayerHelper
from ..ops import moe_ops
from ..ops.pallas import grouped_matmul as gmm
from ..param_attr import ParamAttr
from .tensor import assign

__all__ = ["moe_ffn", "moe_balance"]


def moe_ffn(x, num_experts, top_k, ffn_size, experts_held=None,
            norm_topk_prob=True, routed_scaling_factor=1.0,
            router_attr=None, gate_up_attr=None, down_attr=None, name=None,
            router_input=None, scoring="sigmoid", gate="silu",
            absent="nothing"):
    """(out, load). out is the sum over the picks e of
    w_e * W2_e(silu(W1_e x) * W3_e x) for the tokens x (tokens, d): the
    scores are sigmoid(x W_r) over all `num_experts`, the picks the top
    `top_k` of scores + bias, w the picks' scores (over their sum + 1e-6
    where `norm_topk_prob`, times `routed_scaling_factor`). load is the
    count of picks each of the `num_experts` experts received this step
    (int32 (num_experts,)): hand it to `moe_balance`, out of the
    `recompute_segment` if the layer is in one.

    Three things a model may say otherwise (the defaults are LFM2's and
    Kimi's layer, op for op):
    - `router_input` (tokens, d): what the router reads where that is not
      the experts' input x (SmallThinker's router reads its block's input,
      ahead of attention: the picks and the plan then wait for nothing the
      mixer computes);
    - `scoring="softmax"`: the picks are the top `top_k` of the LOGITS
      r W_r, w the softmax over the picks' own logits; no expert bias is
      made or read;
    - `gate="relu"`: the experts are W2_e(relu(W1_e x) * W3_e x);
    - `gate="relu2"`: the experts are NOT gated, W2_e(relu(W1_e x)^2)
      (`nemotron_h`): the first stacked leaf is (count, d, ffn_size), named
      `<name>_experts_up` unless `gate_up_attr` names it;
    - `absent="folded"`: a pick on an absent expert is answered by the held
      expert congruent to it modulo `count`, with the weight the router
      gave it, so every pick is answered and the layer lays out tokens x
      top_k rows whatever the router does (`load` is then over the held
      experts alone).

    `experts_held=(first, count)` says which experts live here (default:
    all): the result is the part THEY give, and a pick on an absent expert
    adds nothing (an expert-parallel rank's share; the sum over the ranks'
    results is the whole layer's). No row is dropped under any imbalance.

    w_e is applied in `moe_combine`, in float32, as each pick's row is
    added to its token's sum; the rows change between token order and
    expert order as plain row gathers in x's dtype (ops/moe_ops.py).

    The router's (d, num_experts) matrix is float32; each of the held
    experts' two matrices is one stacked leaf in x's dtype, (count, d,
    2*ffn_size) gate and up side by side and (count, ffn_size, d). The
    expert bias is a persistable float32 buffer of zeros named
    `<name>_expert_bias`, not a Parameter: it takes no gradient and the
    optimizer never sees it; `moe_balance` moves it."""
    helper = LayerHelper("moe_ffn", name=name)
    name = helper.name
    first, count = experts_held or (0, num_experts)
    if first < 0 or count < 1 or first + count > num_experts:
        raise ValueError("moe_ffn: experts_held=(%d, %d) is no range of "
                         "%d experts" % (first, count, num_experts))
    if not 1 <= top_k <= num_experts:
        raise ValueError("moe_ffn: top_k %d of %d experts"
                         % (top_k, num_experts))
    d = x.shape[-1]

    def attr(given, suffix):
        given = ParamAttr._to_attr(given)
        if given.name is None:
            given.name = name + suffix
        return given

    plain = gate in moe_ops.PLAIN
    if scoring not in moe_ops.SCORINGS or not (plain
                                               or gate in moe_ops.GATES):
        raise ValueError("moe_ffn: scoring %r is one of %r and gate %r of "
                         "%r" % (scoring, moe_ops.SCORINGS, gate,
                                 sorted(moe_ops.GATES)
                                 + sorted(moe_ops.PLAIN)))
    w_r = helper.create_parameter(attr(router_attr, "_router.w_0"),
                                  shape=[d, num_experts], dtype="float32")
    w13 = helper.create_parameter(
        attr(gate_up_attr, "_experts_up" if plain else "_experts_gate_up"),
        shape=[count, d, ffn_size if plain else 2 * ffn_size],
        dtype=x.dtype)
    w2 = helper.create_parameter(attr(down_attr, "_experts_down"),
                                 shape=[count, ffn_size, d], dtype=x.dtype)
    held = [int(first), int(count)]
    # the attrs a default layer carries are the ones it always carried
    route_ins = {"X": [(x if router_input is None else router_input).name],
                 "W": [w_r.name]}
    route_attrs = {"top_k": int(top_k),
                   "norm_topk_prob": bool(norm_topk_prob),
                   "routed_scaling_factor": float(routed_scaling_factor)}
    if scoring == "sigmoid":
        route_ins["Bias"] = [_expert_bias(helper, name, num_experts).name]
    else:
        route_attrs["scoring"] = scoring
    if absent not in ("nothing", "folded"):
        raise ValueError("moe_ffn: absent %r is neither 'nothing' nor "
                         "'folded'" % (absent,))
    if absent == "folded":
        route_attrs["fold_onto"] = held

    def tmp(dtype, shape=None, stop_gradient=False):
        return helper.create_variable_for_type_inference(
            dtype, shape, stop_gradient=stop_gradient)

    tokens = x.shape[0]
    top_w = tmp("float32", (tokens, top_k))
    top_e = tmp("int32", (tokens, top_k), True)
    load = tmp("int32", (num_experts,), True)
    helper.append_op(
        "moe_route", inputs=route_ins,
        outputs={"TopW": [top_w.name], "TopE": [top_e.name],
                 "Load": [load.name]},
        attrs=route_attrs)
    rows, pos, row_pair = tmp(x.dtype), tmp("int32", None, True), \
        tmp("int32", None, True)
    held_pair = tmp("int32", None, True)
    sizes, tile_group = tmp("int32", (count,), True), tmp("int32", None, True)
    helper.append_op(
        "moe_dispatch", inputs={"X": [x.name], "TopE": [top_e.name]},
        outputs={"Rows": [rows.name], "Pos": [pos.name],
                 "RowPair": [row_pair.name], "HeldPair": [held_pair.name],
                 "GroupSizes": [sizes.name], "TileGroup": [tile_group.name]},
        attrs={"experts_held": held})
    y = tmp(x.dtype)
    helper.append_op(
        "moe_experts",
        inputs={"Rows": [rows.name], "W13": [w13.name], "W2": [w2.name],
                "GroupSizes": [sizes.name], "TileGroup": [tile_group.name]},
        outputs={"Out": [y.name]},
        attrs={} if gate == "silu" else {"gate": gate})
    out = tmp(x.dtype, x.shape)
    helper.append_op(
        "moe_combine",
        inputs={"Y": [y.name], "TopW": [top_w.name], "Pos": [pos.name],
                "RowPair": [row_pair.name], "HeldPair": [held_pair.name],
                "GroupSizes": [sizes.name]},
        outputs={"Out": [out.name]})
    return out, load


def _expert_bias(helper, layer, num_experts):
    block, name = helper.main_program.global_block(), layer + "_expert_bias"
    if block.has_var(name):
        return block.var(name)
    bias = helper.create_global_variable(
        name=name, persistable=True, dtype="float32", shape=[num_experts])
    helper.set_variable_initializer(bias, ConstantInitializer(0.0))
    return bias


def moe_balance(load, layer, experts_held=None, bias_update_rate=0.0):
    """What a step does once with `load` (`moe_ffn`'s second result for the
    layer named `layer`), in the main block, where a segment's results
    arrive (a segment's own writes do not leave it, and its replay in the
    backward would write twice):
    - keeps it as the persistable `<layer>_expert_load`. While obs is on,
      `Executor.run` reads it back after the step and records a `moe.load`
      span: `layer`, and over the experts held `rows_held` (their sum: the
      picks that landed here), `rows_max`, `rows_mean`, `rows_in_use` (the
      tile-padded rows the plan laid out for those counts), `rows_buffer`
      (the worst case for the step's pairs: the counts' sum over ALL
      experts is tokens x top_k), and `bounded` (1 where the layer's row
      passes followed the rows in use on that step:
      `moe_ops.takes_bounded_form`, the rule the ops' own `cond`s go by);
    - where `bias_update_rate` > 0, the loss-free balance step on
      `<layer>_expert_bias`: + rate for every expert under the mean load,
      - rate for every one over it. The load over ALL experts is known on
      every expert-parallel rank, so each rank makes the same update alone.
      The forward pass and its replay read the bias the step began with."""
    helper = LayerHelper("moe_balance")
    program = helper.main_program
    if program.current_block().idx != 0:
        raise ValueError(
            "moe_balance(%r) inside a sub-block would be lost with the "
            "block's own values: call it where the segment's results "
            "arrive" % layer)
    num_experts = int(load.shape[0])
    first, count = experts_held or (0, num_experts)
    kept = helper.create_or_get_global_variable(
        layer + "_expert_load", persistable=True, dtype="int32",
        shape=[num_experts])
    helper.set_variable_initializer(kept, ConstantInitializer(0))
    assign(load, output=kept)

    def summarize(counts, first=int(first), count=int(count)):
        counts = np.asarray(counts)
        rows = counts[first:first + count]
        pairs = int(counts.sum())       # every pick fell on some expert
        tm = gmm.row_tile(pairs)
        in_use = int(moe_ops.rows_laid_out(rows, tm))
        buffer = gmm.buffer_rows(pairs, count, tm)
        return {"rows_held": int(rows.sum()), "rows_max": int(rows.max()),
                "rows_mean": float(rows.mean()), "rows_in_use": in_use,
                "rows_buffer": buffer, "bounded": int(
                    moe_ops.takes_bounded_form(in_use, buffer))}

    program.record_step_state("moe.load", kept.name, {"layer": layer},
                              summarize)
    if bias_update_rate:
        bias = _expert_bias(helper, layer, num_experts)
        helper.append_op(
            "moe_bias_update",
            inputs={"Bias": [bias.name], "Load": [load.name]},
            outputs={"Out": [bias.name]},
            attrs={"rate": float(bias_update_rate)})
    return kept
