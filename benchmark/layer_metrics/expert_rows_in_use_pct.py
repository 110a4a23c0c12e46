"""Step program: of the expert layers' worst-case row buffers, the share
the step's plans laid out (tile-padded rows, all expert layers together),
in %, from the `rows_in_use` and `rows_buffer` labels of the `moe.load`
spans; median over the traced steps. What the layer's row passes cost by
since they follow the rows in use. None where the spans carry no such
labels (a program from before them)."""
import statistics

from benchmark.layer_metrics import _moe


def read(record):
    by_layer = _moe.loads(record)
    if not by_layer:
        return None
    shares = []
    for step in zip(*by_layer.values()):
        if not all(s.get("rows_buffer") for s in step):
            return None
        shares.append(100.0 * sum(s["rows_in_use"] for s in step)
                      / sum(s["rows_buffer"] for s in step))
    return statistics.median(shares) if shares else None
