"""Operations and bytes a block-diffusion attention call needs, computed
from shapes: the counts behind `bd_attn_roofline_pct` and the attention term
of the `sdarmoe` family's `train_flops`. The call runs 2T rows, a noisy and a
clean copy of a T-token document cut into blocks of L; a head's queries see
T (T + L) / 2 pairs clean on clean (block-causal), T L noisy on noisy (the
own block, both directions) and T (T - L) / 2 noisy on clean (the strict
past): T^2 + T L of the doubled square's 4 T^2. Every count is the least the
algorithm needs, whatever implements the mask: FLOPs by the visible pairs,
bytes once per tensor.
"""


def visible_area_bd(seq, length):
    """(query, key) pairs the block-diffusion mask lets through, a head and
    sequence of `seq` tokens in blocks of `length`."""
    if seq % length:
        raise ValueError("blocks of %d do not divide %d tokens"
                         % (length, seq))
    return seq * seq + seq * length


def attention_call_flops(call):
    """(forward, backward) FLOPs of one grouped attention call (the
    family's `attention_calls` dict; `seq` is T): forward is QK^T (width
    d_qk) and PV (width d_v) over the visible pairs; backward recomputes
    QK^T and forms dP (d_v), dV (d_v), dQ (d_qk), dK (d_qk)."""
    area = call["batch"] * call["q_heads"] * visible_area_bd(
        call["seq"], call["block_length"])
    qk, pv = 2 * area * call["d_qk"], 2 * area * call["d_v"]
    return qk + pv, 3 * qk + 2 * pv


def attention_call_bytes(call, itemsize):
    """(forward, backward) bytes one call must move over its 2T rows:
    forward reads q, k, v and writes o; backward reads q, k, v, o, dO and
    writes dq, dk, dv. Keys and values count once a kv head."""
    rows = call["batch"] * 2 * call["seq"] * itemsize
    q, o = rows * call["q_heads"] * call["d_qk"], \
        rows * call["q_heads"] * call["d_v"]
    k, v = rows * call["kv_heads"] * call["d_qk"], \
        rows * call["kv_heads"] * call["d_v"]
    return q + k + v + o, 2 * q + 2 * k + 2 * v + 2 * o
