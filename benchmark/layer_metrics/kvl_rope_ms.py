"""Step program: per traced step, the device ms under the `partial_rope` op
type (latent attention's decoupled rotary part: the last 64 of each query
head's 192 numbers and the one 64-wide key part the heads share, turned by
a signed-permutation matmul that hands every number its partner and one
elementwise float32 pass; forward, replayed forward and backward); median
over steps. None on a program without the op."""
from benchmark.layer_metrics import _hybrid

OP_TYPES = ("partial_rope",)


def read(record):
    return _hybrid.op_type_ms(record, OP_TYPES)
