"""Operations and bytes the hybrid (state-space + attention) layers need,
computed from shapes: the counts behind `mfu_pct`, `attn_roofline_pct` and
`ssm_scan_roofline_pct` of the `phi4flash` family. Matmul terms only for the
training count (the scan is vector work, not MXU work, and is left out),
backward counted as twice the forward, recomputed operations not counted.
Every count is the least the algorithm needs: attention by the area a query
can see, bytes once per tensor.
"""


def visible_area(seq, window=None):
    """(query, key) pairs a causal mask lets through, of a sequence against
    itself: query t sees keys max(0, t - window + 1)..t."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def hybrid_train_flops(s, batch, seq):
    """Per-step training FLOPs of the hybrid decoder. `s` holds the widths:
    d, ff, hq, hkv, dh, e, n, r, window, vocab, kinds."""
    tokens = batch * seq
    d, ff, e, n, r, dh = s["d"], s["ff"], s["e"], s["n"], s["r"], s["dh"]
    q_width, kv_width = s["hq"] * dh, s["hkv"] * dh
    fwd = 0
    for kind in s["kinds"]:
        fwd += 2 * tokens * d * 2 * ff + 2 * tokens * ff * d      # gated MLP
        if kind in ("mamba", "memory"):
            fwd += 2 * tokens * (d * 2 * e + e * (r + 2 * n) + r * e + e * d)
        elif kind == "gmu":
            fwd += 2 * tokens * (d * e + e * d)
        else:
            proj = q_width if kind == "cross" else q_width + 2 * kv_width
            fwd += 2 * tokens * d * proj + 2 * tokens * q_width * d
            area = batch * visible_area(
                seq, s["window"] if kind == "window" else None)
            # every query head: scores at width dh, PV at width 2 dh
            fwd += 2 * s["hq"] * area * (dh + 2 * dh)
    fwd += 2 * tokens * d * s["vocab"]
    return 3 * fwd


def attention_call_flops(call):
    """(forward, backward) FLOPs of one grouped attention call
    (`attention_calls`' dict): forward is QK^T (width d_qk) and PV (width
    d_v) over the visible area; backward recomputes QK^T and forms dP
    (d_v), dV (d_v), dQ (d_qk), dK (d_qk)."""
    area = call["batch"] * call["q_heads"] * visible_area(call["seq"],
                                                          call["window"])
    qk, pv = 2 * area * call["d_qk"], 2 * area * call["d_v"]
    return qk + pv, 3 * qk + 2 * pv


def attention_call_bytes(call, itemsize):
    """(forward, backward) bytes one call must move: forward reads q, k, v
    and writes o; backward reads q, k, v, o, dO and writes dq, dk, dv. Keys
    and values count once a kv head."""
    rows = call["batch"] * call["seq"] * itemsize
    q, o = rows * call["q_heads"] * call["d_qk"], \
        rows * call["q_heads"] * call["d_v"]
    k, v = rows * call["kv_heads"] * call["d_qk"], \
        rows * call["kv_heads"] * call["d_v"]
    return q + k + v + o, 2 * q + 2 * k + 2 * v + 2 * o


def scan_call_bytes(batch, seq, channels, state, itemsize):
    """(forward, backward) HBM bytes one selective-scan call must move:
    forward reads xc, delta (B,T,E), B, C (B,T,N) and writes y; backward
    reads those and dy and writes dxc, ddelta, dB, dC. A, D and their
    gradients are a rounding error beside them and left out."""
    wide = batch * seq * channels * itemsize
    narrow = batch * seq * state * itemsize
    return 3 * wide + 2 * narrow, 5 * wide + 4 * narrow
