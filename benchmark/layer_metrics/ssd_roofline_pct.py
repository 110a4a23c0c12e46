"""Step program: the least time the step's Mamba-2 scan calls could take
(`flops_ssd`: the recurrent form's required FLOPs, decay, rank-1 update and
output on an (N, P) state a token and head, and bytes, x, B, C, dt in and y
out, once a tensor, over `benchmark/peaks.json`, the larger of the two a
call; recompute's second forward in both terms) over the device time of the
instructions under the `mamba2_scan` op type, in %: the same work whatever
implements the op, so a later kernel is judged by it unchanged. It reads low
by construction while the op is XLA's chunked form (the (chunk, chunk) decay
scores and the chunks' own states are that form's choice, not required
work). None on a program without the op."""
from benchmark.layer_metrics import _ssd


def read(record):
    return _ssd.roofline_pct(record)
