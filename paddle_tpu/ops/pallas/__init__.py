"""Pallas TPU kernel library.

Hand-written kernels for the per-step hot path, each behind the oracle
pattern: a pure-JAX reference in tests, interpret-mode execution on CPU
(tier-1 exercises the real kernel logic) and an XLA fallback where a
shape does not tile. Which path a call takes is decided from the call's
own shapes, beside the kernel, and by nothing else.

  flash_attention   VMEM-tiled online-softmax attention, forward and
                    backward. ``attention_path`` maps a call's shapes to
                    "xla" or "flash", each kernel's tile
                    (``pick_blocks``) and the fused or the split
                    backward (``backward_rule``); bf16 operands go to
                    the MXU as they are (exported as the MODULE:
                    bench.py and the attention layers call
                    ``flash_attention.flash_attention(...)``)
  selective_scan    the state-space layer's scan, forward and backward,
                    in chunks ``pick_chunk`` sizes from the shape
  delta_rule        the chunked gated delta rule (``kda_attention``),
                    ``kda_fwd`` and ``kda_bwd``: the (K, V) state of a
                    head in VMEM across a sequential chunk axis, the chunk
                    math (decay differences, the unit-lower solve, the WY
                    factors) in VMEM, several heads a grid step side by side;
                    ``plan`` maps a call's shapes to the tiling or to
                    None (the XLA form of ``linear_attn_ops``)
  interpret         ``default_interpret``: interpret mode off the TPU,
                    or as PADDLE_TPU_PALLAS_INTERPRET says
"""
from . import flash_attention  # noqa: F401  (module — see docstring)
