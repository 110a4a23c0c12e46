"""Step program: per traced step, the device time under the delta-rule
layers' own op types (`kda_attention`: the chunk passes, the triangular
solves and the scan over chunks; `causal_conv1d`, `head_l2_norm`, `kda_gate`,
`kda_out_norm` around it; forward, replayed forward and backward) over the
step's device time, in %. The mixers' projections are `mul` and are not in
it. Read from the `tf_op` of each operation's metadata (`_scopes.py`)."""
from benchmark.layer_metrics import _kda


def read(record):
    return _kda.share_pct(record)
