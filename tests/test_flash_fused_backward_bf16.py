"""The fused flash backward kernel against the split pair at equal tiles,
to the bit, in bfloat16: the other half of
`test_flash_fused_backward.py`'s cases, a file of its own so that neither
holds a worker for more than a sixth of a tier-1 run."""
from _flash_cases import fused_backward_against_the_split_kernels

test_fused_backward_equals_the_split_kernels_to_the_bit = \
    fused_backward_against_the_split_kernels("bfloat16")
