"""CompiledProgram / BuildStrategy — whole-program pjit lowering.

Reference parity: python/paddle/fluid/compiler.py + parallel_executor.py +
framework/details/build_strategy.cc. The reference's ParallelExecutor fuses
the SSA graph and inserts NCCL allreduce ops; here the SAME role is played by
pjit over a jax.sharding.Mesh: parameters/feeds get NamedShardings, XLA
partitions the single fused HLO and inserts ICI collectives (AllReduce/
AllGather/ReduceScatter) automatically — the north-star design.
"""
import os
import threading

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# XLA's CPU backend runs MANUAL collectives (shard_map ppermute /
# all_gather — "cross_module" kind) through a process-global rendezvous:
# two executions in flight from different threads interleave their
# per-device participant arrivals across run_ids and deadlock (observed
# live: pipeline steps from 3 simulated pod hosts each stuck waiting for
# "all participants"). Executions that embed manual collectives
# therefore serialize through this lock ON CPU ONLY — real accelerator
# backends rendezvous per-execution, and production pods are one
# process per host anyway.
_MANUAL_COLLECTIVE_LOCK = threading.Lock()


def _env_verify_default():
    """Suite-wide verifier arming without code changes:
    BuildStrategy.verify_program defaults to PADDLE_TPU_VERIFY
    ("strict" | "warn" | "off"; unset/unknown = "warn" — diagnostics
    are logged, never fatal). The test suite pins "strict"."""
    from .analysis import env_verify_mode
    return env_verify_mode()


def verify_for_compile(program, build_strategy=None, feeds=None,
                       fetch_names=None, source="compile"):
    """Run the Program verifier at a compile seam (framework/analysis).

    Mode comes from BuildStrategy.verify_program (env default for the
    plain-Executor path): "off" returns immediately — byte-for-byte
    inert on the compile path; "warn" logs errors/warnings and records
    the analysis metrics; "strict" raises ProgramVerificationError
    when any error-severity diagnostic survives, listing ALL of them.

    Memoized per (program version, mode, mesh, feed/fetch signature) on
    the program object, so only compile-cache misses pay the walk and
    repeat dispatches cost one dict probe."""
    mode = getattr(build_strategy, "verify_program", None) \
        if build_strategy is not None else None
    if mode is None:
        mode = _env_verify_default()
    if mode == "off":
        return None
    feed_sig = None if feeds is None else tuple(
        sorted((k, tuple(np.shape(v)) if not isinstance(v, tuple)
                else v) for k, v in feeds.items()))
    bs = build_strategy
    if bs is None:
        mesh, strat_sig = None, None
    else:
        mesh = getattr(bs, "mesh_axes", None)
        # every strategy knob a pass consumes joins the memo key — two
        # strategies sharing one Program must never share a verdict
        strat_sig = (getattr(bs, "data_axis", "dp"),
                     getattr(bs, "quantize_collectives", False),
                     getattr(bs, "pp_stages", None),
                     getattr(bs, "pp_micro_batches", 1),
                     getattr(bs, "pp_schedule", "1f1b"),
                     getattr(bs, "pp_recut_slots", None))
    key = (program._version, mode,
           None if mesh is None else tuple(sorted(mesh.items())),
           strat_sig, feed_sig,
           None if fetch_names is None else tuple(fetch_names))
    cache = getattr(program, "_verify_cache", None)
    if cache is None:
        cache = program._verify_cache = {}
    if key in cache:
        result = cache[key]
    else:
        # evict verdicts of older program versions — a mutate-run loop
        # must not accumulate one AnalysisResult per historical version
        for k in [k for k in cache if k[0] != program._version]:
            del cache[k]
        from . import analysis
        result = analysis.verify_program(
            program, feeds=feeds, fetch_list=fetch_names,
            build_strategy=build_strategy)
        analysis.report(result, mode=mode, source=source)
        cache[key] = result
        if result.errors() or result.warnings():
            import logging
            logging.getLogger("paddle_tpu").warning(
                "program verification (%s mode): %s", mode,
                result.summary())
    if mode == "strict" and result.errors():
        from .analysis import ProgramVerificationError
        raise ProgramVerificationError(result)
    return result


def _env_timeout_default():
    """Fleet-wide watchdog arming without code changes: BuildStrategy's
    collective_timeout_s defaults to PADDLE_TPU_COLLECTIVE_TIMEOUT_S
    (seconds; unset/empty = no guard)."""
    raw = os.environ.get("PADDLE_TPU_COLLECTIVE_TIMEOUT_S", "").strip()
    if not raw:
        return None
    try:
        return float(raw)
    except ValueError:
        raise ValueError(
            "PADDLE_TPU_COLLECTIVE_TIMEOUT_S=%r is not a number of "
            "seconds (use e.g. '30' or '12.5', or unset for no guard)"
            % raw)


class BuildStrategy(object):
    """Knobs mirroring reference BuildStrategy, TPU-reinterpreted:
      - mesh_axes: dict axis name -> size, e.g. {"dp": 2, "mp": 4}
      - data_axis: mesh axis feeds are batch-sharded over (default "dp")
      - check_numerics: insert NaN/Inf guards (reference check_nan_inf)
      - pp_stages / pp_micro_batches / pp_schedule: pipeline parallelism
        as a first-class mesh axis (see the pipeline section below)
    Any knob can be passed as a constructor kwarg:
    ``BuildStrategy(pp_stages=2, pp_schedule="1f1b")``.
    Reference flags like fuse_all_reduce_ops / memory_optimize are
    no-ops: XLA fuses and plans memory itself (kept for API parity)."""

    def __init__(self, **kw):
        self.mesh_axes = None
        self.data_axis = "dp"
        self.check_numerics = False
        # what happens when check_numerics trips (framework/executor):
        #   "raise"  -- today's behavior: FloatingPointError, state
        #               already written back (donated buffers), caller
        #               (ResilientTrainer) restores. The in-graph guard
        #               also LOCALIZES the first offending fetch/var by
        #               name, so the error and the numeric_fault event
        #               say WHICH tensor blew up, not just "somewhere".
        #   "skip"   -- discard the step in-graph: every state leaf
        #               (optimizer moments + PRNG counter included)
        #               reverts to its pre-step value under a jnp.where
        #               on the all-finite flag, the data cursor moves
        #               past the poison batch, and a numeric_fault
        #               event names the culprit. Bounded by
        #               numeric_skip_budget CONSECUTIVE skips — a
        #               persistent fault escalates to
        #               SkipBudgetExceededError instead of silently
        #               dropping the stream.
        #   "rewind" -- raise resilience.NumericFaultError (a
        #               FloatingPointError carrying step + culprit):
        #               the (Pod/Elastic) trainer's existing
        #               consensus-rewind recovery restores the last
        #               checkpoint and REPLAYS WITH THE POISON BATCH
        #               SKIPPED, so the recovered trajectory equals the
        #               uninterrupted run without that batch, bitwise.
        # Implies check_numerics when set to "skip"/"rewind". Part of
        # the compile-cache token: the lowered step differs per policy.
        self.numeric_policy = "raise"
        # max CONSECUTIVE steps numeric_policy="skip" may discard
        # before escalating (a clean step resets the streak)
        self.numeric_skip_budget = 3
        # halt detection: bound each step's completion (None = no guard);
        # consumed by the run_step watchdog (framework/watchdog.py)
        self.collective_timeout_s = _env_timeout_default()
        # block-quantized data-parallel gradient sync (EQuARX, PAPERS.md):
        # the step is lowered through shard_map over data_axis and every
        # parameter gradient is synced quantize -> psum -> dequantize
        # (int8 payload + per-block fp32 scale) instead of riding pjit's
        # implicit full-width psum. Gradient-merge-aware: accumulation
        # buffers add the already-synced fp32 value, so only the
        # cross-host sync is quantized. Pure-dp meshes only (every other
        # axis must have size 1); fetches are dp-averaged (float) /
        # AND-ed (bool flags). Wire accounting lands in
        # resilience.metrics() as collective_bytes_total{kind=raw|wire}.
        self.quantize_collectives = False
        self.quantize_block_size = 256
        self.quantize_bits = 8
        # gradients below this element count ride the exact full-width
        # sync (sub-block payloads cost MORE quantized); None = one block
        self.quantize_min_size = None
        # Pipeline parallelism (reference PipelineOptimizer/section_worker,
        # TPU-native): pp_stages=K cuts the traced Program at its
        # pp_stage stamps (or an even op-count auto-cut when unstamped)
        # and lowers the whole fwd+bwd+optimizer step through the
        # GPipe/1F1B ppermute-ring schedules over the mesh's "pp" axis,
        # composing with dp gradient sync (quantize_collectives
        # included) on the data axis. Stage params/optimizer state are
        # stacked (n_stage, ...) and live only on their pp slice of the
        # mesh. pp_micro_batches=M splits each batch into M microbatches
        # (bubble fraction ~ (K-1)/(M+K-1)); pp_schedule picks "1f1b"
        # (bounded activation stash, rematerialized backward) or "gpipe"
        # (autodiff through the forward ring). All three join the
        # compile-cache token: toggling re-lowers.
        self.pp_stages = None
        self.pp_micro_batches = 1
        self.pp_schedule = "1f1b"
        # Elastic pp re-cut (ISSUE 18): n_slots < pp_stages re-stacks the
        # K logical stages over n_slots mesh slots (multiple stages per
        # slot, (n_slots, k_per, ...) stacked state INSIDE the jit; the
        # scope keeps the flat per-stage layout, so checkpoints/elastic
        # state-shipping stay wire-compatible). The mesh's "pp" axis must
        # equal pp_recut_slots while armed. ElasticTrainer arms this on a
        # survivable pp host loss and clears it on re-grow; joins the
        # compile-cache token — a re-cut re-lowers, repeats hit.
        self.pp_recut_slots = None
        # Program IR verification at CompilePlan build time
        # (framework/analysis.py): "strict" fails the compile on any
        # error-severity diagnostic (ALL violations listed, not
        # first-error-wins), "warn" (default; env PADDLE_TPU_VERIFY
        # overrides) logs + exports analysis metrics, "off" skips the
        # verifier entirely. Diagnostics-only — the knob can never
        # change the lowered executable, so it is deliberately NOT part
        # of the compile-cache token (tools/codelint.py allowlists it).
        self.verify_program = _env_verify_default()
        # once-per-k quantized sync for gradient-merge windows (OPT-IN):
        # when a grad-merge accumulator structure is detected, the
        # quantized dp sync moves from every micro step's raw gradient
        # to the MERGE BOUNDARY (the gated merged gradient, under
        # lax.cond on the program's own apply predicate) — k-1 of every
        # k steps ship zero gradient bytes. Accumulation buffers then
        # hold LOCAL fp32 sums (still exact/bitwise per shard), which
        # means they are NOT dp-replicated mid-window: a checkpoint
        # taken off a merge boundary (straggler_ckpt, admission saves)
        # captures one shard's buffer, and a consensus rewind restoring
        # it everywhere drops the other shards' accumulation. Enable
        # only when every snapshot lands on a k-aligned boundary
        # (checkpoint_every % k == 0 and no unscheduled saves) or the
        # run tolerates a non-bitwise merge window across a rewind.
        # False (default) = legacy every-step sync.
        self.quantize_merge_sync = False
        # parity no-ops
        self.fuse_all_reduce_ops = True
        self.fuse_elewise_add_act_ops = True
        self.memory_optimize = True
        self.enable_inplace = True
        self.num_trainers = 1
        self.trainer_id = 0
        for k, v in kw.items():
            if not hasattr(self, k):
                raise TypeError("BuildStrategy has no knob %r" % k)
            setattr(self, k, v)
        if self.numeric_policy not in ("raise", "skip", "rewind"):
            raise ValueError(
                "numeric_policy must be 'raise', 'skip' or 'rewind', "
                "got %r" % (self.numeric_policy,))
        if int(self.numeric_skip_budget) < 1:
            raise ValueError("numeric_skip_budget must be >= 1")
        if self.pp_recut_slots is not None:
            if int(self.pp_recut_slots) < 1:
                raise ValueError("pp_recut_slots must be >= 1 (a re-cut "
                                 "keeps every logical stage resident)")
            if not self.pp_stages:
                raise ValueError(
                    "pp_recut_slots needs pp_stages: the re-cut maps K "
                    "logical stages (pp_stages) onto n_slots mesh slots")


class ExecutionStrategy(object):
    def __init__(self):
        self.num_threads = 1
        self.num_iteration_per_drop_scope = 1
        self.use_experimental_executor = True


class CompilePlan(object):
    """How a (program, strategy) pair lowers: trace -> cut -> schedule ->
    jit. Retires the old single-jit assumption: the executor consults
    the plan's ``kind`` to route the step build, and ``token`` (mesh
    axes + quantize knobs + pp cut + schedule) keys its compile
    cache, so toggling the cut or schedule re-lowers while repeat runs
    hit the cached executable.

      kind      -- "single_jit" | "pipeline"
      token     -- the strategy cache token (includes pp knobs)
      cut       -- distributed.pipeline_program.CompiledPPCut (pipeline)
      schedule  -- "1f1b" | "gpipe" (pipeline)
      n_micro   -- microbatches per step (pipeline)
      recut     -- distributed.pipeline_program.RecutPlan when the
                   elastic re-cut is armed (K stages over n_slots < K
                   mesh slots), else None
    """

    __slots__ = ("kind", "token", "cut", "schedule", "n_micro", "recut")

    def __init__(self, kind, token, cut=None, schedule=None, n_micro=1,
                 recut=None):
        self.kind = kind
        self.cut = cut
        self.schedule = schedule
        self.n_micro = int(n_micro)
        self.recut = recut
        # the cut signature joins the token: two programs whose strategy
        # knobs agree but whose cuts differ must not share an executable
        self.token = token if cut is None else token + (cut.signature(),)
        if recut is not None:
            self.token = self.token + (recut.signature(),)


def make_mesh(mesh_axes, devices=None):
    devices = devices if devices is not None else jax.devices()
    sizes = list(mesh_axes.values())
    n = int(np.prod(sizes))
    if n > len(devices):
        raise ValueError("mesh %r needs %d devices, only %d available"
                         % (mesh_axes, n, len(devices)))
    dev_array = np.array(devices[:n]).reshape(sizes)
    return Mesh(dev_array, tuple(mesh_axes.keys()))


def _place_feed(v, sharding):
    """Stage one feed onto the mesh.

    Single-host: a plain sharded device_put.  Multi-host (jax.distributed
    initialized, mesh spanning several processes): each host passes only
    its LOCAL batch rows and the global array is assembled from the
    process-local shards — the TPU-native replacement for the reference's
    per-trainer reader splits (trainer_id/num_trainers slicing in
    distribute_transpiler).  Batch-split feeds use the local-shard path;
    replicated feeds (P()) must carry identical data on every host.
    """
    if jax.process_count() > 1 and sharding.spec and \
            any(a is not None for a in sharding.spec):
        return jax.make_array_from_process_local_data(
            sharding, np.asarray(v))
    return jax.device_put(v, sharding)


class CompiledProgram(object):
    """fluid.CompiledProgram work-alike.

    with_data_parallel(...) without an explicit mesh shards the batch over
    all devices ("dp" axis) — the direct analogue of the reference's
    all-device data parallelism via NCCL allreduce.
    """

    def __init__(self, program, build_strategy=None):
        self._program = program
        self._build_strategy = build_strategy or BuildStrategy()
        self._exec_strategy = ExecutionStrategy()
        self._mesh = None

    def with_data_parallel(self, loss_name=None, build_strategy=None,
                           exec_strategy=None, share_vars_from=None,
                           places=None):
        if build_strategy is not None:
            self._build_strategy = build_strategy
        if exec_strategy is not None:
            self._exec_strategy = exec_strategy
        if self._build_strategy.mesh_axes is None:
            n_dev = len(places or jax.devices())
            k = int(getattr(self._build_strategy, "pp_stages", 0) or 0)
            if k > 1:
                # pp as a first-class axis: "all-device data parallel"
                # on a pipeline strategy means pp x dp over the devices
                self._build_strategy.mesh_axes = {
                    "pp": k, "dp": max(1, n_dev // k)}
            else:
                self._build_strategy.mesh_axes = {"dp": n_dev}
        return self

    def with_mesh(self, mesh_axes, devices=None):
        """TPU-native entry: explicit mesh, e.g. {"dp": 2, "mp": 4}."""
        self._build_strategy.mesh_axes = dict(mesh_axes)
        self._devices = devices
        return self

    def set_mesh_axes(self, mesh_axes, devices=None):
        """Re-target onto a new mesh topology (elastic shrink/grow).

        Drops the cached Mesh so the next run builds one over the new
        axes. The Executor's step cache is keyed by the axes
        (:meth:`_cache_token`), so returning to a previously-seen
        topology — shrink -> grow -> shrink — re-uses that topology's
        compiled executable instead of recompiling."""
        self._build_strategy.mesh_axes = dict(mesh_axes)
        if devices is not None:
            self._devices = devices
        self._mesh = None
        return self

    # ------------------------------------------------------------------
    def _cache_token(self):
        bs = self._build_strategy
        return (tuple(sorted((bs.mesh_axes or {}).items())), bs.data_axis,
                getattr(bs, "collective_timeout_s", None),
                (getattr(bs, "quantize_collectives", False),
                 getattr(bs, "quantize_block_size", 256),
                 getattr(bs, "quantize_bits", 8),
                 getattr(bs, "quantize_min_size", None),
                 getattr(bs, "quantize_merge_sync", False)),
                # the pipeline cut/schedule selects a whole different
                # lowering — toggling pp_stages or the schedule must
                # re-lower, never reuse a single-jit executable
                (getattr(bs, "pp_stages", None),
                 int(getattr(bs, "pp_micro_batches", 1) or 1),
                 getattr(bs, "pp_schedule", "1f1b"),
                 # the elastic re-cut slot map selects a different
                 # stacking geometry + ring size: arming/clearing it
                 # must re-lower, repeats at the same slot count hit
                 getattr(bs, "pp_recut_slots", None)),
                # numeric_policy changes the lowered step (per-var
                # finite mask, in-graph skip select) — "skip" and
                # "raise" must never share an executable
                getattr(bs, "numeric_policy", "raise"))

    # -- pipeline parallelism ---------------------------------------------
    def _pp_enabled(self):
        bs = self._build_strategy
        if getattr(bs, "pp_stages", None):
            return True
        return int((bs.mesh_axes or {}).get("pp", 1) or 1) > 1

    def compile_plan(self):
        """The lowering route of this (program, strategy) pair — the
        compile plan object: trace -> cut -> schedule -> jit. A plain
        strategy lowers as one jit (kind "single_jit"); a pipeline
        strategy (pp_stages set, or a >1 "pp" mesh axis) cuts the
        program first (kind "pipeline") and the executor routes the
        step through the GPipe/1F1B lowering. The plan's token keys the
        executor step cache: (mesh axes, pp cut, schedule) ride along-
        side the existing strategy token.

        The Program verifier runs HERE, before any lowering work — on
        the pp route that means pipeline misconfiguration surfaces as a
        complete diagnostics list BEFORE extract_compiled_pp_plan's
        first-named-error (framework/analysis.py). Skipped when this
        program version was already verified (the executor's pp seam
        runs a STRONGER feed-ful walk just before calling here — a
        second feed-less walk would only double-count the analysis
        metrics)."""
        cache = getattr(self._program, "_verify_cache", None)
        if not cache or all(k[0] != self._program._version
                            for k in cache):
            verify_for_compile(self._program, self._build_strategy,
                               source="compile_plan")
        if not self._pp_enabled():
            return CompilePlan("single_jit", self._cache_token())
        from ..distributed import pipeline_program as ppp
        bs = self._build_strategy
        if getattr(bs, "numeric_policy", "raise") != "raise":
            raise ValueError(
                "numeric_policy=%r is not supported with pipeline "
                "parallelism yet — the pp lowering keeps raise-only "
                "check_numerics" % (bs.numeric_policy,))
        axes = dict(bs.mesh_axes or {})
        k = int(bs.pp_stages) if getattr(bs, "pp_stages", None) else None
        recut_n = getattr(bs, "pp_recut_slots", None)
        recut_n = int(recut_n) if recut_n else None
        # with the elastic re-cut armed the mesh's pp axis counts SLOTS
        # (one per surviving pp rank), not logical stages
        ring = recut_n if recut_n is not None else k
        if "pp" not in axes:
            if ring is None:
                raise ValueError("pipeline strategy needs pp_stages or a "
                                 "'pp' mesh axis")
            # first-class default: pp x dp over all devices
            n_dev = len(getattr(self, "_devices", None) or jax.devices())
            if axes:
                raise ValueError(
                    "mesh_axes %r has no 'pp' axis but pp_stages=%d is "
                    "set — include pp in the mesh (e.g. {'pp': %d, "
                    "'dp': %d})" % (axes, k, ring, max(1, n_dev // ring)))
            axes = {"pp": ring, "dp": max(1, n_dev // ring)}
            bs.mesh_axes = dict(axes)
        if ring is not None and int(axes["pp"]) != ring:
            if recut_n is not None:
                raise ValueError(
                    "pp_recut_slots=%d does not match the mesh's pp axis "
                    "(%d) — the re-cut mesh carries one slot per "
                    "surviving pp rank" % (recut_n, int(axes["pp"])))
            raise ValueError(
                "pp_stages=%d does not match the mesh's pp axis (%d)"
                % (k, int(axes["pp"])))
        if k is None:
            k = int(axes["pp"])
        schedule = getattr(bs, "pp_schedule", "1f1b")
        n_micro = int(getattr(bs, "pp_micro_batches", 1) or 1)
        cache = getattr(self._program, "_pp_cut_cache", None)
        ck = (k, schedule, n_micro)
        if cache is not None and cache[0] == (self._program._version,) + ck:
            cut = cache[1]
        else:
            cut = ppp.extract_compiled_pp_plan(
                self._program, n_stage=k, schedule=schedule,
                n_micro=n_micro)
            # store POST-extract version: the auto-cut stamps attrs and
            # bumps it once
            self._program._pp_cut_cache = (
                (self._program._version,) + ck, cut)
        # identity re-cut (n_slots == K) lowers through the ordinary
        # 1-stage-per-slot path; n_slots > K raises the typed
        # PPRecutInfeasibleError from recut_plan
        rplan = ppp.recut_plan(k, recut_n) \
            if recut_n is not None and recut_n != k else None
        return CompilePlan("pipeline", self._cache_token(),
                           cut=cut, schedule=schedule, n_micro=n_micro,
                           recut=rplan)

    def _mesh_obj(self):
        if self._mesh is None:
            self._mesh = make_mesh(self._build_strategy.mesh_axes,
                                   getattr(self, "_devices", None))
        return self._mesh

    def _var_sharding(self, name, mesh):
        blk = self._program.global_block()
        var = blk._find_var_recursive(name)
        axes = set(mesh.axis_names)
        if var is not None and var.sharding:
            # every annotation site (fleet ZeRO, transpiler tables,
            # tp attrs) meets the REAL mesh here: drop any axis the
            # mesh doesn't have, and any axis whose dim doesn't divide
            # the mesh size — those dims stay replicated instead of
            # failing the jit with a non-divisible NamedSharding
            spec = []
            shape = var.shape or ()
            for i, a in enumerate(var.sharding):
                if a not in axes:
                    spec.append(None)
                elif i < len(shape) and shape[i] not in (None, -1) and \
                        shape[i] % mesh.shape[a] != 0:
                    spec.append(None)
                else:
                    spec.append(a)
            return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())  # replicated

    def _feed_sharding(self, name, mesh):
        data_axis = self._build_strategy.data_axis
        if data_axis not in mesh.axis_names:
            return NamedSharding(mesh, P())
        # batch-shard feeds over the data axis — but config-like feeds
        # (e.g. a (3,) task_weight schedule vector) whose leading dim can't
        # split over dp stay replicated
        var = self._program.global_block()._find_var_recursive(name)
        if var is not None and var.shape:
            d0 = var.shape[0]
            if d0 not in (None, -1) and d0 % mesh.shape[data_axis] != 0:
                return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(data_axis))

    def _build_multi_step(self, multi, state_names, feed_names):
        """Sharded scan window (Executor.run_steps on a CompiledProgram):
        `multi` is the executor-built scan over stacked feeds with the
        state as donated carry. Feed shardings get a replicated leading
        steps axis prepended; collectives inside the step ride ICI once
        per scanned step with zero host round-trips."""
        mesh = self._mesh_obj()
        state_sh = tuple(self._var_sharding(n, mesh) for n in state_names)
        feed_sh = tuple(
            NamedSharding(mesh, P(*((None,) + tuple(s.spec))))
            for s in (self._feed_sharding(n, mesh) for n in feed_names))
        return self._wrap_sharded(multi, mesh, state_sh, feed_sh,
                                  (None, state_sh), window=True)

    def _build_step(self, executor, step, program, state_names, feed_names,
                    feed_vals, check_numerics=False):
        mesh = self._mesh_obj()
        state_sh = tuple(self._var_sharding(n, mesh) for n in state_names)
        feed_sh = tuple(self._feed_sharding(n, mesh) for n in feed_names)
        out_sh = (None, state_sh, None) if check_numerics \
            else (None, state_sh)
        return self._wrap_sharded(step, mesh, state_sh, feed_sh, out_sh)

    # -- quantized collectives --------------------------------------------
    def _quantize_ctx(self, mesh, allow_pp=False):
        """Build the per-compile QuantizedSyncContext, or None when the
        quantized path does not apply (option off / no data axis).
        allow_pp: the pipeline lowering runs its own shard_map over
        pp x dp and applies the quantized sync explicitly on the dp
        axis, so a pp axis is fine THERE — everywhere else a >1 model
        axis would silently lose its XLA-inserted collectives."""
        bs = self._build_strategy
        if not getattr(bs, "quantize_collectives", False):
            return None
        if bs.data_axis not in mesh.axis_names:
            return None
        skip = {bs.data_axis} | ({"pp"} if allow_pp else set())
        bad = {a: int(s) for a, s in mesh.shape.items()
               if a not in skip and int(s) > 1}
        if bad:
            raise ValueError(
                "quantize_collectives lowers the step through shard_map "
                "over the %r axis with LOCAL per-shard semantics, so it "
                "supports pure data-parallel meshes only; model axes %r "
                "would lose their XLA-inserted collectives. Drop the "
                "option or the model axes." % (bs.data_axis, bad))
        if getattr(bs, "numeric_policy", "raise") == "skip":
            raise ValueError(
                "numeric_policy='skip' reverts state in-graph from the "
                "GLOBAL all-finite verdict, but the quantized shard_map "
                "lowering evaluates per-shard flags before the sync — "
                "shards could revert divergently. Use "
                "numeric_policy='rewind' (host-side, sees the AND-ed "
                "flag) or disable quantize_collectives.")
        from ..ops.collective_ops import QuantizedSyncContext
        return QuantizedSyncContext(
            bs.data_axis,
            block_size=int(getattr(bs, "quantize_block_size", 256)),
            bits=int(getattr(bs, "quantize_bits", 8)),
            min_size=getattr(bs, "quantize_min_size", None),
            merge_window=bool(getattr(bs, "quantize_merge_sync", False)))

    def _quantized_fn(self, fn, mesh, state_sh, feed_sh, out_sh, qctx):
        """shard_map the step over the data axis with explicit quantized
        gradient sync (the trace hook fires inside the scope) and
        replicated-consistent outputs: float fetches are dp-averaged
        (local-mean loss -> global-mean loss), bool flags (check_numerics)
        are AND-ed across shards, state passes through untouched — it is
        replicated by construction because every shard applies the same
        synced gradients."""
        from ..ops import collective_ops as cops
        from ..distributed.mesh import shard_map_unchecked
        axis = self._build_strategy.data_axis

        def _spec_of(s):
            return P() if s is None else s.spec

        in_specs = (tuple(s.spec for s in state_sh),
                    tuple(s.spec for s in feed_sh))
        out_specs = jax.tree_util.tree_map(
            _spec_of, out_sh,
            is_leaf=lambda s: s is None or isinstance(s, NamedSharding))

        def _sync_leaf(v):
            if jnp.issubdtype(jnp.result_type(v), jnp.bool_):
                return jnp.all(jax.lax.all_gather(v, axis), axis=0)
            if jnp.issubdtype(jnp.result_type(v), jnp.inexact):
                return jax.lax.pmean(v, axis)
            return v

        def quant_step(state_tuple, feed_tuple):
            with cops.grad_sync_scope(qctx):
                out = fn(state_tuple, feed_tuple)
            head = jax.tree_util.tree_map(_sync_leaf, out[0])
            tail = jax.tree_util.tree_map(_sync_leaf, out[2:])
            return (head, out[1]) + tail

        return shard_map_unchecked(quant_step, mesh, in_specs, out_specs)

    # -- pipeline lowering -------------------------------------------------
    def _build_pp_step(self, program, cplan, fetch_names, micro_shapes,
                       check_numerics=False, windowed=False):
        """Lower the whole fwd+bwd+optimizer step through the pipeline
        schedule inside ONE shard_map over the pp(xdp) mesh.

        Per pp shard: run this stage's slice of the stacked params
        through the GPipe/1F1B ring (distributed.pipeline local bodies
        — the schedule's own autodiff replaces the program's backward
        section), dp-sync the stage grads (plain pmean, or the
        quantized collectives when quantize_collectives is on), then
        trace the program's OWN update section (optimizer ops, LR
        schedule, gradient-merge accumulation) on the stage-0 template
        over this shard's state slice. Stage state is stacked
        (n_stage, ...) and NamedSharded P("pp") — each stage's params
        and optimizer moments live only on their pp slice of the mesh.

        Returns (state_info, run_step): state_info tells the executor
        how to stack scope state ((stacked_names, stage_cols,
        shared_names, feed_order)); run_step has the usual
        (state_tuple, feed_tuple) dispatch signature."""
        from ..distributed import pipeline_program as ppp
        from ..distributed.pipeline import (pipeline_1f1b_local,
                                            pipeline_gpipe_local,
                                            pipeline_forward_local)
        from ..distributed.mesh import shard_map_unchecked
        mesh = self._mesh_obj()
        cut = cplan.cut
        plan = cut.plan
        n_stage = plan.n_stage
        rec = cplan.recut
        # with the elastic re-cut armed the ring runs over n_slots SLOTS
        # (each a super-stage iterating its resident logical stages);
        # otherwise one slot per stage, ring size n_stage
        n_ring = rec.n_slots if rec is not None else n_stage
        if int(mesh.shape.get("pp", 0)) != n_ring:
            if rec is not None:
                raise ValueError(
                    "re-cut plan stacks %d pipeline stages over %d slots "
                    "but the mesh 'pp' axis has %d devices — they must "
                    "match" % (n_stage, n_ring,
                               int(mesh.shape.get("pp", 0))))
            raise ValueError(
                "program cuts into %d pipeline stages but the mesh 'pp' "
                "axis has %d devices — they must match"
                % (n_stage, int(mesh.shape.get("pp", 0))))
        bs = self._build_strategy
        dp_axis = bs.data_axis if (bs.data_axis in mesh.axis_names and
                                   mesh.shape[bs.data_axis] > 1) else None
        bad = {a: int(s) for a, s in mesh.shape.items()
               if a not in ("pp", dp_axis) and int(s) > 1}
        if bad:
            raise ValueError(
                "the pipeline lowering supports pp x %s meshes only; "
                "axes %r are unsupported (v1)" % (bs.data_axis, bad))
        qctx = self._quantize_ctx(mesh, allow_pp=True)

        tail_produced = {n for op in plan.tail_ops
                         for n in op.output_names()}
        aux_names = [n for n in fetch_names if n != cut.loss_name]
        unknown = [n for n in aux_names if n not in tail_produced]
        if unknown:
            raise ValueError(
                "pipeline fetch_list entries must be the loss or vars "
                "computed by the unstamped loss section; %r are not "
                "(stage activations stay sharded on the pp ring)"
                % (unknown,))

        stage_fn = ppp.make_stage_fn(program, plan)
        if rec is not None:
            # the ring body sees ONE callable per slot; the wrapper
            # iterates the slot's resident stages over its (k_per, ...)
            # rows of the stacked state
            stage_fn = ppp.make_slot_stage_fn(stage_fn, rec, "pp")
        loss_fn = ppp.make_loss_fn(program, plan)
        tail_fn = ppp.make_tail_fn(program, plan, tuple(aux_names)) \
            if aux_names else None
        update = ppp.make_update_trace_fn(program, cut)
        stacked_names = sorted(cut.stage_state)
        shared_names = list(cut.shared_state)
        n_stacked = len(stacked_names)
        tmpl_params = list(plan.template_params)
        n_micro = plan.n_micro
        feed_order = [plan.x_feed] + list(plan.y_feeds)
        from .trace import GRAD_SUFFIX

        if cplan.schedule == "1f1b":
            sched = pipeline_1f1b_local(stage_fn, loss_fn, n_ring,
                                        n_micro, "pp", dp_axis)
        elif cplan.schedule == "gpipe":
            sched = pipeline_gpipe_local(stage_fn, loss_fn, n_ring,
                                         n_micro, "pp", dp_axis)
        else:
            raise ValueError("unknown pp_schedule %r" % cplan.schedule)
        fwd = pipeline_forward_local(stage_fn, n_ring, n_micro, "pp",
                                     dp_axis) if tail_fn else None

        def _unmicro(a):
            return a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])

        def _feed_spec(name):
            # (n_micro, micro_batch, ...): micro dim replicated, batch
            # dim dp-sharded when it divides; an indivisible batch stays
            # replicated (every dp shard computes the same full batch)
            shape = micro_shapes[name]
            mb = shape[2 if windowed else 1] if len(shape) > \
                (2 if windowed else 1) else None
            if dp_axis is not None and mb is not None \
                    and mb % mesh.shape[dp_axis] == 0:
                return P(None, dp_axis)
            return P()
        feed_specs = tuple(_feed_spec(n) for n in feed_order)
        feed_sharded = tuple(dp_axis in tuple(s) for s in feed_specs)

        def _gather_rows(a, sharded):
            # reassemble the FULL batch on every dp shard (contiguous
            # dim-1 blocks, so tiled all_gather restores serial order)
            if dp_axis is None or not sharded:
                return a
            return jax.lax.all_gather(a, dp_axis, axis=1, tiled=True)

        def local_step(state_tuple, feed_tuple):
            stacked = dict(zip(stacked_names, state_tuple[:n_stacked]))
            shared = dict(zip(shared_names, state_tuple[n_stacked:]))
            x_local = feed_tuple[0]
            ys_local = tuple(feed_tuple[1:])
            params_me = {t: stacked[t][0] for t in tmpl_params}
            loss, grads = sched(params_me, x_local, ys_local)
            if dp_axis is not None:
                loss = jax.lax.pmean(loss, dp_axis)
                if qctx is not None:
                    grads = {t: qctx.sync(t + GRAD_SUFFIX, g)
                             for t, g in grads.items()}
                else:
                    grads = {t: jax.lax.pmean(g, dp_axis)
                             for t, g in grads.items()}
            aux_vals = ()
            if tail_fn is not None:
                # aux fetches get EXACT serial semantics: gather the
                # pp-replicated chain output + label feeds to the full
                # batch on every dp shard, then run the unstamped tail
                # un-microbatched — every shard computes the identical
                # (replicated) value, scalar or per-row
                h = _gather_rows(fwd(params_me, x_local),
                                 feed_sharded[0])
                ys_full = tuple(
                    _gather_rows(y, sh)
                    for y, sh in zip(ys_local, feed_sharded[1:]))
                aux_vals = tail_fn(_unmicro(h),
                                   tuple(_unmicro(y) for y in ys_full))
            env = dict(shared)
            env.update({t: stacked[t][0] for t in stacked_names})
            env.update({t + GRAD_SUFFIX: grads[t] for t in tmpl_params})
            update(env)
            new_state = tuple(env[t][None] for t in stacked_names) \
                + tuple(env[n] for n in shared_names)
            fetches = tuple(
                loss if n == cut.loss_name
                else aux_vals[aux_names.index(n)] for n in fetch_names)
            return fetches, new_state

        stacked_spec = tuple(P("pp") for _ in stacked_names)
        shared_spec = tuple(P() for _ in shared_names)
        state_specs = stacked_spec + shared_spec
        fetch_specs = tuple(P() for _ in fetch_names)

        body = shard_map_unchecked(local_step, mesh,
                                   (state_specs, feed_specs),
                                   (fetch_specs, state_specs))

        def _finite(parts):
            flag = jnp.asarray(True)
            for v in parts:
                if jnp.issubdtype(jnp.result_type(v), jnp.inexact):
                    flag = jnp.logical_and(flag,
                                           jnp.all(jnp.isfinite(v)))
            return flag

        # The step's EXTERNAL state signature is flat per-stage
        # replicated vars (the scope layout every other path —
        # checkpoints, elastic shipping — already speaks); the stacking
        # onto the pp axis and the unstack back happen INSIDE the jit,
        # so no eager multi-device op ever races another host thread's
        # dispatch (concurrent eager gathers deadlock the CPU
        # backend's collective rendezvous), and a run_steps window
        # carries the pp-sharded stacked state across the whole scan
        # with zero boundary crossings.
        def _dstack(vals):
            # NOT jnp.stack: on this jax a concatenate feeding a
            # NESTED shard_map mis-partitions the operand (every shard
            # reads a blend instead of its P("pp") slice — repro: stack
            # two (8,8) into (2,8,8), pass through shard_map in jit).
            # dynamic_update_index_in_dim lowers to updates the SPMD
            # partitioner handles correctly.
            out = jnp.zeros((len(vals),) + tuple(vals[0].shape),
                            jnp.result_type(vals[0]))
            for i, v in enumerate(vals):
                out = jax.lax.dynamic_update_index_in_dim(
                    out, v.astype(out.dtype), i, 0)
            return out

        def _dstack_recut(vals):
            # re-cut geometry: (n_slots, k_per, ...) with row (j, i)
            # holding logical stage rec.stage_idx[j][i] (pads repeat the
            # slot's last real stage — never read back). Same
            # dynamic_update lowering as _dstack for the same
            # partitioner reason.
            shape = tuple(vals[0].shape)
            dt = jnp.result_type(vals[0])
            out = jnp.zeros((rec.n_slots, rec.k_per) + shape, dt)
            for j in range(rec.n_slots):
                for i in range(rec.k_per):
                    v = vals[rec.stage_idx[j][i]].astype(dt)
                    out = jax.lax.dynamic_update_slice(
                        out, v[None, None], (j, i) + (0,) * len(shape))
            return out

        stack_vals = _dstack if rec is None else _dstack_recut

        def _stack_in(state_tuple):
            stacked = tuple(
                stack_vals(state_tuple[i * n_stage:(i + 1) * n_stage])
                for i in range(n_stacked))
            return stacked + tuple(state_tuple[n_stacked * n_stage:])

        def _unstack_out(new_state):
            out = []
            for arr in new_state[:n_stacked]:
                if rec is None:
                    out.extend(arr[s] for s in range(n_stage))
                else:
                    out.extend(
                        arr[rec.slot_of[s],
                            s - rec.starts[rec.slot_of[s]]]
                        for s in range(n_stage))
            out.extend(new_state[n_stacked:])
            return tuple(out)

        if windowed:
            def target(state_tuple, feed_stack_tuple):
                def scan_body(carry, xs):
                    fetches, new_state = body(carry, xs)
                    ys = (fetches,)
                    if check_numerics:
                        ys += (_finite(list(fetches) + list(new_state)),)
                    return new_state, ys
                final_state, ys = jax.lax.scan(scan_body,
                                               _stack_in(state_tuple),
                                               feed_stack_tuple)
                return ys, _unstack_out(final_state)
        elif check_numerics:
            def target(state_tuple, feed_tuple):
                fetches, new_state = body(_stack_in(state_tuple),
                                          feed_tuple)
                return fetches, _unstack_out(new_state), \
                    _finite(list(fetches) + list(new_state))
        else:
            def target(state_tuple, feed_tuple):
                fetches, new_state = body(_stack_in(state_tuple),
                                          feed_tuple)
                return fetches, _unstack_out(new_state)

        n_flat = n_stacked * n_stage + len(shared_names)
        state_sh = tuple(NamedSharding(mesh, P()) for _ in range(n_flat))
        feed_sh = tuple(
            NamedSharding(mesh, P(*((None,) + tuple(s))))
            if windowed else NamedSharding(mesh, s)
            for s in feed_specs)
        if check_numerics and not windowed:
            out_sh = (None, state_sh, None)
        else:
            out_sh = (None, state_sh)
        run_step = self._wrap_sharded(target, mesh, state_sh, feed_sh,
                                      out_sh, window=windowed, qctx=qctx,
                                      pipeline=True)
        state_info = (tuple(stacked_names),
                      {t: tuple(cut.stage_state[t])
                       for t in stacked_names},
                      tuple(shared_names), tuple(feed_order))
        return state_info, run_step

    def _wrap_sharded(self, fn, mesh, state_sh, feed_sh, out_sh,
                      window=False, qctx="auto", pipeline=False):
        """Shared step/window machinery: jit over the mesh, stage inputs
        onto their shardings, and arm the one-behind collective-timeout
        watchdog. With quantize_collectives on, the fn is first lowered
        through shard_map with quantized gradient sync; the per-step wire
        accounting (static, accumulated at trace time) is recorded per
        dispatch (x window length for run_steps windows).

        qctx: "auto" builds the QuantizedSyncContext here and wraps fn in
        the dp shard_map; a caller that already lowered its own shard_map
        (the pipeline path) passes its context — byte accounting and the
        watchdog still apply, the extra wrap does not."""
        if qctx == "auto":
            qctx = self._quantize_ctx(mesh)
            if qctx is not None:
                fn = self._quantized_fn(fn, mesh, state_sh, feed_sh,
                                        out_sh, qctx)
        jitted = jax.jit(fn, in_shardings=(state_sh, feed_sh),
                         out_shardings=out_sh, donate_argnums=(0,))
        timeout_s = getattr(self._build_strategy, "collective_timeout_s",
                            None)
        # manual collectives on the CPU backend serialize process-wide
        # (see _MANUAL_COLLECTIVE_LOCK): any quantized or pipeline step
        # embeds shard_map ppermute/all_gather
        try:
            platform = next(iter(mesh.devices.flat)).platform
        except Exception:  # pragma: no cover - exotic mesh
            platform = jax.default_backend()
        serialize = (qctx is not None or pipeline) and platform == "cpu"
        pending = []  # previous call's outputs (one-behind watchdog)

        def run_step(state_vals, feed_tuple):
            with mesh:
                if timeout_s is not None and pending:
                    # Bound-wait on the PREVIOUS dispatch so async
                    # dispatch (host stages batch N+1 while the chip runs
                    # batch N) survives; a hung collective surfaces at
                    # the next call's entry — same one-step-late
                    # semantics as the reference's NCCL watchdog thread.
                    from .watchdog import wait_with_timeout
                    wait_with_timeout(
                        pending.pop(), timeout_s,
                        what="CompiledProgram step over mesh %r"
                        % (tuple(mesh.axis_names),))
                placed_state = tuple(
                    v if isinstance(v, jax.Array) and
                    getattr(v, "sharding", None) == s
                    else jax.device_put(v, s)
                    for v, s in zip(state_vals, state_sh))
                placed_feed = tuple(
                    _place_feed(v, s)
                    for v, s in zip(feed_tuple, feed_sh))
                if serialize:
                    # hold the lock through COMPLETION: a second
                    # thread's enqueue against a still-running manual
                    # collective is exactly the rendezvous interleaving
                    # that deadlocks the CPU backend
                    with _MANUAL_COLLECTIVE_LOCK:
                        out = jitted(placed_state, placed_feed)
                        jax.block_until_ready(out)
                else:
                    out = jitted(placed_state, placed_feed)
                if timeout_s is not None:
                    pending.append(out)
                if qctx is not None and qctx.raw_bytes:
                    # static per-step totals (populated by the first
                    # call's trace), multiplied by the window length:
                    # one record per dispatch, zero device syncs
                    from . import resilience
                    n = int(np.shape(feed_tuple[0])[0]) \
                        if window and feed_tuple else 1
                    # int-cast: merge-boundary syncs amortize bytes by
                    # 1/k, leaving fractional trace-time totals
                    resilience.record_bytes("collective",
                                            int(qctx.raw_bytes * n),
                                            int(qctx.wire_bytes * n))
                return out
        return run_step
