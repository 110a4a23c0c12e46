"""Pallas TPU kernel library.

Hand-fused kernels for the per-step hot path, each behind the oracle
pattern: a pure-JAX reference in tests, interpret-mode execution on CPU
(tier-1 exercises the real kernel logic), XLA fallback when shapes
don't tile, and — for the registry-wired ops — trace-time dispatch via
``BuildStrategy.use_pallas`` + the ``ops.pallas_dispatch`` scope.

  flash_attention   VMEM-tiled online-softmax attention, forward and
                    backward; each kernel's tile comes from the call's
                    shape (``pick_blocks``), bf16 operands go to the
                    MXU as they are (exported as the MODULE for
                    back-compat: bench.py and the attention layers
                    call ``flash_attention.flash_attention(...)``)
  blockwise_softmax_cross_entropy / fused_mlm_head_loss
                    blockwise CE + fused MLM head (the [tokens, vocab]
                    logits never materialize; ``blockwise_ce``)
  fused_adam        one-pass m/v/param Adam update per parameter
  fused_layer_norm  one-pass LayerNorm fwd + bwd with saved residuals
  AutotuneCache / autotune_op
                    per-(op, shape, dtype, mesh, backend) block-size
                    sweep with a persistent, versioned JSON cache
                    (tools/autotune.py is the CLI), cost-model-pruned
                    to ``top_k`` measured candidates
  CostModel         analytic+fitted kernel cost model (costmodel):
                    ranks candidate configs, predicts configs for
                    never-swept shapes at trace time, prunes sweeps
"""
from . import flash_attention  # noqa: F401  (module — see docstring)
from .blockwise_ce import (  # noqa: F401
    blockwise_softmax_cross_entropy, fused_mlm_head_loss)
from .fused_adam import fused_adam  # noqa: F401  (function shadows its
#                                      submodule; internal callers import
#                                      from .fused_adam directly)
from .layer_norm import fused_layer_norm  # noqa: F401
from .autotune import (  # noqa: F401
    AutotuneCache, autotune_op, default_cache_path, CANDIDATES,
    fit_cost_model, banked_cache_path)
from .costmodel import CostModel  # noqa: F401
from ..pallas_dispatch import (  # noqa: F401
    PallasConfig, KernelChoice, cache_key, scope as pallas_scope,
    enabled as pallas_enabled, PALLAS_OPS, KERNEL_POLICIES)
