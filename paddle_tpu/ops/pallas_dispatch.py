"""Pallas kernel dispatch scope — the light half of the Pallas library.

`BuildStrategy.use_pallas={"softmax_with_cross_entropy","adam","layer_norm"}`
makes `CompiledProgram` trace the step inside :func:`scope`; the op kernels
in nn_ops/optimizer_ops consult :func:`enabled` at trace time and route to
the fused Pallas implementation (``ops/pallas/``), falling back to their
XLA lowering otherwise. The same thread-local pattern as
``collective_ops.grad_sync_scope``: the scope is entered around the
function jax.jit traces, so the decision is baked into the compiled
executable — which is why the option must participate in the executor's
compile-cache token.

This module deliberately imports NEITHER jax.experimental.pallas nor the
kernel modules: every softmax_with_cross_entropy/adam/layer_norm trace
pays one thread-local read when Pallas is off. The heavy kernels load
lazily inside the enabled branch.

Autotuning: a :class:`PallasConfig` may carry a tuning cache (any object
with ``lookup(key) -> entry-dict-or-None``, normally
``ops.pallas.autotune.AutotuneCache``) and a fitted
:class:`~.pallas.costmodel.CostModel`. :func:`choose` resolves the
per-(op, shape, dtype, mesh, backend) :class:`KernelChoice` at trace
time — ONE decision per call site instead of three independent knobs:

  * a cached entry is a MEASURED verdict: it overrides the kernel's
    default block sizes, routes the op back to XLA when the sweep found
    Pallas losing, or selects the quantized variant (``impl:
    "pallas_q"`` — bf16-cast inputs with f32 accumulation, banked only
    from a sweep that measured its numerics envelope);
  * a cache MISS with a cost model attached gets a PREDICTED config
    (the model ranks the candidate space for the never-swept shape)
    instead of the hardcoded kernel default;
  * no signal at all keeps the legacy kernel defaults.

Every decision is exported through the PR 12 observability layer: a
``kernel_choice`` span (op, impl, source, predicted vs measured
seconds) when tracing is enabled, plus cumulative
``kernel_choice_total{op=,impl=,source=}`` counters in
``resilience.metrics()``. Decisions happen at TRACE time only, so the
export rides compiles, never the step hot path.
"""
import contextlib
import os
import threading

#: ops with a Pallas lowering behind this dispatch scope (flash attention
#: has its own auto-engaging entry in layers.attention and is not listed)
PALLAS_OPS = ("softmax_with_cross_entropy", "adam", "layer_norm",
              "fused_mlm_head_loss")

_local = threading.local()


#: kernel_policy values BuildStrategy accepts — the one front door
KERNEL_POLICIES = ("auto", "xla", "pallas")


class PallasConfig(object):
    """Per-compile Pallas dispatch state.

    ops:        iterable of op-type names to route through Pallas
    interpret:  None = decide per kernel call from the effective default
                device (CPU -> interpret mode, same contract as
                flash_attention); True/False forces it
    tuning:     autotune cache (``lookup(key)``) or None for defaults
    mesh_axes:  dict axis->size of the compile's mesh (cache-key part)
    backend:    platform string the executable targets (cache-key part)
    cost_model: fitted ``costmodel.CostModel`` (or None) — resolves a
                cache MISS to a predicted config instead of defaults
    policy:     the BuildStrategy.kernel_policy that built this config
                (labeling/diagnostics; "xla" never builds a config)
    """

    def __init__(self, ops, interpret=None, tuning=None, mesh_axes=None,
                 backend=None, cost_model=None, policy=None):
        unknown = sorted(set(ops) - set(PALLAS_OPS))
        if unknown:
            raise ValueError(
                "use_pallas names ops with no Pallas lowering: %r "
                "(available: %r)" % (unknown, list(PALLAS_OPS)))
        self.ops = frozenset(ops)
        self.interpret = interpret
        self.tuning = tuning
        self.mesh_axes = dict(mesh_axes or {})
        self.backend = backend
        self.cost_model = cost_model
        self.policy = policy


@contextlib.contextmanager
def scope(config):
    """Install `config` for the current thread (the jit trace runs under
    it). Nesting restores the outer config on exit."""
    prev = getattr(_local, "config", None)
    _local.config = config
    try:
        yield config
    finally:
        _local.config = prev


def active():
    return getattr(_local, "config", None)


def enabled(op_type):
    """The active PallasConfig if `op_type` is routed to Pallas, else
    None — the one-line check every wired kernel starts with."""
    cfg = getattr(_local, "config", None)
    if cfg is not None and op_type in cfg.ops:
        return cfg
    return None


def cache_key(op, shape, dtype, mesh_axes=None, backend=None):
    """Autotune cache key — same ingredients as the executor's step
    cache: problem shape + mesh axes + backend. One winning config per
    (op, shape, dtype, topology, platform)."""
    axes = ",".join("%s=%d" % (a, int(s))
                    for a, s in sorted((mesh_axes or {}).items()))
    return "%s|%s|%s|%s|%s" % (
        op, "x".join(str(int(d)) for d in shape), str(dtype),
        axes or "-", backend or "-")


class KernelChoice(tuple):
    """One per-call-site kernel decision, unpackable as the legacy
    ``(impl, tuned_kwargs)`` pair (it IS that tuple) plus provenance:

      impl        -- "pallas" | "xla" | "pallas_q" (quantized variant:
                     bf16-cast inputs, f32 accumulation)
      config      -- tuned/predicted block kwargs, or None = defaults
      source      -- "measured" (banked sweep verdict), "predicted"
                     (fitted cost model), "analytic" (no-data proxy),
                     "default" (no signal)
      predicted_s -- model-predicted seconds (predicted/analytic)
      measured_s  -- banked sweep seconds (measured)
    """

    def __new__(cls, impl, config=None, source="default",
                predicted_s=None, measured_s=None):
        self = tuple.__new__(cls, (impl, config))
        self.impl = impl
        self.config = config
        self.source = source
        self.predicted_s = predicted_s
        self.measured_s = measured_s
        return self


def _export_choice(op, shape, dtype, choice):
    """Ship one trace-time decision through the observability layer:
    cumulative counters always, a retroactive span when tracing is on.
    Trace-rate only (compiles), never the step hot path; any obs
    hiccup must not fail a trace."""
    try:
        from ..framework import resilience
        resilience.record_kernel_choice(op, choice.impl, choice.source)
    except Exception:  # pragma: no cover - obs must never break a trace
        pass
    try:
        from ..framework import obs
        if obs.enabled():
            t = obs.now()
            obs.record(
                "kernel_choice", t, t, op=op,
                shape="x".join(str(int(d)) for d in shape),
                dtype=str(dtype), impl=choice.impl, source=choice.source,
                predicted_s=choice.predicted_s,
                measured_s=choice.measured_s)
    except Exception:  # pragma: no cover
        pass


def choose(cfg, op, shape, dtype):
    """Resolve the :class:`KernelChoice` for one kernel call at trace
    time (unpacks as the legacy ``(impl, tuned_kwargs)`` pair).

    Priority: banked MEASURED verdict (exact key, then the mesh-less
    key — a verdict swept without a mesh serves every topology of its
    backend) > cost-model PREDICTION for a never-swept shape > kernel
    defaults. impl "xla" means the sweep measured Pallas losing here —
    the caller must take its XLA branch; "pallas_q" asks the caller
    for its quantized (bf16-cast) variant where it has one."""
    if cfg is None:
        return KernelChoice("pallas", None)
    choice = None
    entry = None
    if cfg.tuning is not None:
        entry = cfg.tuning.lookup(
            cache_key(op, shape, dtype, cfg.mesh_axes, cfg.backend))
        if not entry and cfg.mesh_axes:
            entry = cfg.tuning.lookup(
                cache_key(op, shape, dtype, None, cfg.backend))
    if entry:
        if entry.get("impl") == "xla":
            choice = KernelChoice("xla", None, "measured",
                                  measured_s=entry.get("xla_s"))
        else:
            config = entry.get("config")
            # a --cost-model-only banked entry was never measured: its
            # provenance stays "predicted" so the kernel_choice export
            # cannot pass a zero-probe prediction off as a sweep verdict
            src = "predicted" if entry.get("source") == "costmodel" \
                else "measured"
            choice = KernelChoice(
                entry.get("impl") or "pallas",
                dict(config) if config else None, src,
                predicted_s=entry.get("predicted_s"),
                measured_s=entry.get("pallas_s"))
    elif cfg.cost_model is not None:
        interp = cfg.interpret if cfg.interpret is not None \
            else default_interpret()
        pred = cfg.cost_model.predict_config(
            op, shape, backend=cfg.backend, interpret=interp)
        if pred is not None:
            choice = KernelChoice(
                "pallas", pred["config"],
                "predicted" if pred["source"] == "fitted"
                else "analytic", predicted_s=pred["predicted_s"])
    if choice is None:
        choice = KernelChoice("pallas", None)
    _export_choice(op, shape, dtype, choice)
    return choice


def default_interpret():
    """interpret-mode default shared by every kernel entry: honor
    PADDLE_TPU_PALLAS_INTERPRET, else interpret off-TPU — decided from
    the EFFECTIVE default device, not the process backend list (a
    jax.default_device(cpu) pin routes this computation to CPU even when
    a chip is attached)."""
    env = os.environ.get("PADDLE_TPU_PALLAS_INTERPRET")
    if env is not None:
        return env not in ("0", "false", "")
    import jax
    pinned = getattr(jax.config, "jax_default_device", None)
    if pinned is None:
        platform = jax.default_backend()
    elif isinstance(pinned, str):
        platform = pinned
    else:
        platform = getattr(pinned, "platform", None)
    return platform != "tpu"
