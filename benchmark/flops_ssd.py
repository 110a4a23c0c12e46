"""Operations and bytes the Mamba-2 recurrence needs (the `mamba2_scan` op
of `paddle_tpu/ops/ssm_ops.py`), computed from shapes: the count behind the
scan's term of `mfu_pct` in the `nemotronh` family, and the least anything
that implements the op must do (`ssd_roofline_pct`). The count is the
RECURRENT form's required work, whatever form implements it (a chunked form
does more: the (chunk, chunk) scores and the chunks' own states are its
choice, not required work): a token and head, on an (N, P) state, take the
decay a S, the rank-1 update dt B x^T and the output S^T C, 2 N P each.
Bytes once per tensor.
"""


def token_flops(head_dim, state):
    """Forward FLOPs a token and head: decay, rank-1 update, S^T C."""
    return 3 * 2 * state * head_dim


def call_flops(batch, seq, heads, head_dim, state):
    """(forward, backward) FLOPs of one call on (batch, seq, heads):
    backward counted as twice the forward, as every training count here."""
    fwd = batch * seq * heads * token_flops(head_dim, state)
    return fwd, 2 * fwd


def call_bytes(batch, seq, heads, head_dim, groups, state, itemsize):
    """(forward, backward) HBM bytes one call must move: forward reads x
    (H P wide), B and C (G N wide each) and dt (H wide) in the activations'
    type and writes y (H P); backward reads those and dy and writes dx, dB,
    dC and d dt. The state never leaves the chip's fast memory in the least
    form; A_log, dt_bias, D and their gradients are a rounding error beside
    them and left out."""
    tokens = batch * seq * itemsize
    fwd = tokens * (2 * heads * head_dim + 2 * groups * state + heads)
    return fwd, 2 * fwd
