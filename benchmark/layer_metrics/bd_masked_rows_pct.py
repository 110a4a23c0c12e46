"""Step program: of the noisy half's rows, the share the host masked (the
rows that carry a loss weight), in %, from the `bd.noise` spans'
`masked_rows` and `rows` over the traced steps: the mean of the traffic's
noise range where the loss works on what the traffic says."""
from benchmark.layer_metrics import _bd


def read(record):
    return _bd.masked_rows_pct(record)
