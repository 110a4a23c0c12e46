"""Operations and bytes the algorithms need, computed from shapes. The
yardstick's copy: `bench.py` has the same two training counts
(`bert_train_flops`, `gpt_train_flops`); later PRs may change those, not
these. Matmul terms only, backward counted as twice the forward, recomputed
operations not counted.
"""
import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks_for(device_kind, path=_PEAKS):
    """The table's entry for exactly `device_kind`; an unknown kind is an
    error, never a default."""
    with open(path) as f:
        table = json.load(f)["device_kinds"]
    if device_kind not in table:
        raise KeyError("no peaks for device_kind %r in %s (have %s)"
                       % (device_kind, path, sorted(table)))
    return table[device_kind]


def bert_train_flops(hidden, layers, ff, vocab, batch, seq, preds):
    """Per-step training FLOPs of BERT's MLM+NSP pre-training."""
    tokens = batch * seq
    proj = 8 * tokens * hidden * hidden          # Q, K, V, O projections
    attn = 4 * batch * seq * seq * hidden        # scores + context
    ffn = 4 * tokens * hidden * ff               # two FFN matmuls
    fwd = layers * (proj + attn + ffn)
    fwd += 2 * batch * preds * hidden * vocab    # MLM vocabulary decode
    fwd += 2 * batch * preds * hidden * hidden   # MLM transform
    return 3 * fwd


def gpt_train_flops(hidden, layers, ff, vocab, batch, seq):
    """Per-step training FLOPs of the causal LM; causal attention counts
    the lower triangle only."""
    tokens = batch * seq
    proj = 8 * tokens * hidden * hidden
    attn = 4 * batch * seq * seq * hidden // 2
    ffn = 4 * tokens * hidden * ff
    fwd = layers * (proj + attn + ffn) + 2 * tokens * hidden * vocab
    return 3 * fwd


def flash_call_flops(batch, heads, seq, head_dim, causal):
    """(forward, backward) FLOPs of one attention call on (batch, heads,
    seq, head_dim): forward is QK^T and PV; backward recomputes QK^T and
    forms dV, dP, dQ, dK: five matmuls of the same size. Causal halves
    them."""
    one = 2 * batch * heads * seq * seq * head_dim
    if causal:
        one //= 2
    return 2 * one, 5 * one


def flash_call_bytes(batch, heads, seq, head_dim, itemsize):
    """(forward, backward) bytes the calls must move: forward reads q, k,
    v and writes o; backward reads q, k, v, o, do and writes dq, dk, dv."""
    tensor = batch * heads * seq * head_dim * itemsize
    return 4 * tensor, 8 * tensor


def roofline_seconds(flops, nbytes, peaks):
    """(least seconds, which bound applies) for `flops` and `nbytes`."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
