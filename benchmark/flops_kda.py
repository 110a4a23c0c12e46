"""Operations and bytes the gated delta rule with a per-channel decay needs
(the `kda_attention` op of `paddle_tpu/ops/linear_attn_ops.py`), computed
from shapes: the count behind the delta rule's term of `mfu_pct` in the
`kimilinear` family, and the least any `kda_*` kernel must do. The count is
the RECURRENT form's required work, whatever form implements it (a chunked
form does more: the triangular solve and the in-chunk score matrices are its
own choice, not required work): a token and head, on a (K, V) state, take
the decay Diag(a) S, the read k^T S, the rank-1 update k u^T and the output
S^T q, 2 K V each. Bytes once per tensor.
"""


def token_flops(d_k, d_v):
    """Forward FLOPs a token and head: decay, k^T S, the rank-1 update,
    S^T q."""
    return 4 * 2 * d_k * d_v


def call_flops(batch, seq, heads, d_k, d_v):
    """(forward, backward) FLOPs of one call on (batch, seq, heads):
    backward counted as twice the forward, as every training count here."""
    fwd = batch * seq * heads * token_flops(d_k, d_v)
    return fwd, 2 * fwd


def call_bytes(batch, seq, heads, d_k, d_v, itemsize):
    """(forward, backward) HBM bytes one call must move: forward reads q, k
    (K wide), v (V wide) and beta in the activations' type and the
    log-decay g (K wide) in float32, and writes o; backward reads those and
    dO and writes dq, dk, dv, dbeta and dg (float32). The state never
    leaves the chip's fast memory in the least form."""
    rows = batch * seq * heads
    narrow = rows * itemsize
    fwd = narrow * (2 * d_k + d_v + 1) + rows * d_k * 4 + narrow * d_v
    return fwd, 2 * fwd
