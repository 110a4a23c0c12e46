"""Executor: run a Program on TPU as one fused XLA computation.

Reference parity: python/paddle/fluid/executor.py + framework/executor.cc.
The reference interprets the ProgramDesc op-by-op, dispatching device kernels.
TPU-native design: on first run of a (program, feed-signature) pair we trace
every op's JAX kernel into a single jax.jit'd step function

    step(state, feeds) -> (fetches, new_state)

where ``state`` is every persistable var (parameters, optimizer moments, LR
counters) resident in HBM. State buffers are DONATED, so XLA updates
parameters in place — zero-copy, the whole train step is one HLO module, and
XLA fuses across forward/backward/optimizer exactly like the reference's
fused ParallelExecutor graph, but compiler-driven.

Programs with no fetch_list (e.g. the startup program) run eagerly op-by-op —
initializers don't deserve a compile.
"""
import collections
import contextlib
import logging
import threading
import time
import warnings

import numpy as np
import jax
import jax.numpy as jnp

from . import faultinject
from . import obs
from . import resilience
from . import trace as trace_mod
from . import watchdog
from .dtypes import to_jax_dtype
from .place import CPUPlace, TPUPlace, _current_expected_place  # noqa: F401
from .program import Program, default_main_program
from .scope import global_scope
from ..ops.registry import get_op, has_op
from .trace import TraceContext, trace_block, GRAD_OP_TYPE, STEP_VAR

logger = logging.getLogger("paddle_tpu")


def _feed_signature(feed):
    # NB: use .dtype/.shape attributes — np.asarray on a jax.Array would
    # sync it to host, putting a D2H round-trip on every step.
    return tuple(sorted((k, tuple(v.shape), str(v.dtype))
                        for k, v in feed.items()))


def _want_vjp_set(program):
    """desc_ids of forward ops that some grad_of op in the program refers to."""
    want = set()
    for blk in program.blocks:
        for op in blk.ops:
            if op.type == GRAD_OP_TYPE:
                want.add(op.attrs["fwd_id"])
    return frozenset(want)


def _fetch_names(fetch_list):
    return [f.name if hasattr(f, "name") else f for f in fetch_list]


def _persistable_names(program):
    names = set()
    for blk in program.blocks:
        for v in blk.vars.values():
            if v.persistable:
                names.add(v.name)
    return names


def _uses_rng(program):
    for blk in program.blocks:
        for op in blk.ops:
            if op.type != GRAD_OP_TYPE and has_op(op.type) \
                    and get_op(op.type).uses_rng:
                return True
    return False


def _numeric_config(program, strategy):
    """Resolve (check_numerics, policy, skip_budget) for one run.

    A numeric_policy other than "raise" implies the finite guard even
    when check_numerics was left False — "skip"/"rewind" without the
    mask would be dead knobs."""
    policy, budget = "raise", 3
    if strategy is not None:
        bs = strategy._build_strategy
        policy = getattr(bs, "numeric_policy", "raise") or "raise"
        budget = int(getattr(bs, "numeric_skip_budget", 3) or 1)
    check = bool(
        getattr(program, "_check_numerics", False)
        or (strategy is not None and
            getattr(strategy._build_strategy, "check_numerics", False))
        or policy != "raise")
    return check, policy, budget


def _skip_guard(step):
    """numeric_policy="skip", the in-graph half: when ANY fetch/state
    var went non-finite this step, every state leaf (params, optimizer
    moments, PRNG counter) reverts to its pre-step value under one
    scalar select — the step simply never happened on-device. Works
    WITH buffer donation because the select runs inside the jitted
    computation; the host never has to resurrect a donated input."""
    def guarded(state_tuple, feed_tuple):
        fetches, new_state, finite = step(state_tuple, feed_tuple)
        ok = jnp.all(finite)
        new_state = tuple(jnp.where(ok, n, o)
                          for o, n in zip(state_tuple, new_state))
        return fetches, new_state, finite
    return guarded


def _first_offender(finite_row, fetch_names, state_names):
    """Name the first non-finite var from one per-var finite mask row
    (mask order: fetches, then carried state)."""
    finite_row = np.asarray(finite_row)
    if finite_row.ndim == 0:    # legacy scalar flag: no localization
        return None
    names = list(fetch_names) + list(state_names)
    idx = int(np.argmin(finite_row))
    return names[idx] if idx < len(names) else None


def _hit_step_feed(feed):
    """executor.step failpoint: lets a chaos schedule NaN-poison or
    bit-flip a named feed array (or raise/delay) at a chosen step."""
    out = faultinject.hit("executor.step", feed)
    return feed if out is faultinject.DROP else out


def _reader_feed(program, feed):
    """``feed`` (a fresh dict) with the batches of the program's started
    py_readers (reference create_py_reader_op: run-without-feed training
    loops); an exhausted reader raises layers.io.EOFException here.
    Two-phase so a sibling reader's EOF pushes already-dequeued batches
    back (no lost data), and user-fed names are never overwritten."""
    feed = dict(feed or {})
    pulled = []
    try:
        for rdr in getattr(program, "_py_readers", ()):
            if rdr._started and any(n not in feed for n in rdr._names):
                pulled.append((rdr, rdr._next_feed()))
    except Exception:
        for rdr, batch in pulled:
            rdr._push_back(batch)
        raise
    for rdr, batch in pulled:
        for n, v in batch.items():
            feed.setdefault(n, v)
    return feed


def _boundary(sp, name, kind=None, since=None):
    """The one place a step reads the clock: at a phase boundary, once.
    The reading closes ``exec.step``'s open phase and opens the phase
    ``name`` (obs on; None opens none), feeds the always-on
    ``executor_step_seconds{kind=}`` histogram of the phase that began at
    ``since`` and ends here, and is returned: the next boundary's
    ``since``, and what the straggler detector's latency is made of.
    Phases no histogram reads (feed, prepare, records) open through
    ``sp.phase`` alone, which reads nothing while obs is off."""
    at = obs.now()
    if kind is not None:
        resilience.observe_executor_step(kind, at - since)
    sp.phase(name, at)
    return at


# ---------------------------------------------------------------------------
# the miss log: where a step-cache miss's time went
# ---------------------------------------------------------------------------
# jax.jit is lazy: `_compile` only builds a closure and a wrapper, and the
# trace, the lowering and the backend compile all run inside the first
# call of the step, under ``exec.execute``. JAX times those stages itself
# (dispatch.log_elapsed_time) and hands them to jax.monitoring listeners;
# a miss listens and changes nothing of what it executes.
_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"

_miss_tls = threading.local()       # .open: the thread's _OpenMiss, or None
_misses = collections.deque(maxlen=32)
_listen_lock = threading.Lock()
_listening = False                  # True once the listeners are in


class _OpenMiss(object):
    """What the listeners gather between a miss's ``exec.compile``
    boundary and the return of the step's first call."""

    __slots__ = ("entry", "program", "version", "t0", "skew", "spans",
                 "requests", "hits", "retrieval_s")

    def __init__(self, entry, program, t0):
        self.entry, self.t0 = entry, t0
        self.program, self.version = id(program), program._version
        # JAX stamps its stages with time.time(); obs's clock is
        # wall-anchored monotonic: one reading of both places them
        self.skew = t0 - time.time()
        self.spans = {"trace": [], "lower": [], "backend": []}
        self.requests = self.hits = 0
        self.retrieval_s = 0.0


def _on_time_span(event, start, end, **_kw):
    miss = getattr(_miss_tls, "open", None)
    if miss is None:
        return
    stage = _STAGE_OF.get(event)
    if stage is not None:
        miss.spans[stage].append((start, end))


def _on_event(event, **_kw):
    miss = getattr(_miss_tls, "open", None)
    if miss is None:
        return
    if event == _CACHE_REQUEST:
        miss.requests += 1
    elif event == _CACHE_HIT:
        miss.hits += 1


def _on_duration(event, seconds, **_kw):
    miss = getattr(_miss_tls, "open", None)
    if miss is None:
        return
    if event == _CACHE_RETRIEVAL:
        miss.retrieval_s += seconds


def _open_miss(entry, program, t0):
    """Mark this thread's miss open at its ``exec.compile`` boundary
    ``t0``; the listeners go in at the process's first miss (so a
    ``jax.monitoring.clear_event_listeners()`` before it costs nothing)."""
    global _listening
    with _listen_lock:
        if not _listening:
            jax.monitoring.register_event_time_span_listener(_on_time_span)
            jax.monitoring.register_event_listener(_on_event)
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _listening = True
    _miss_tls.open = miss = _OpenMiss(entry, program, t0)
    return miss


def _merged(spans):
    out = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _without(spans, holes):
    """Merged ``spans`` minus merged ``holes``."""
    out = []
    for s, e in spans:
        for hs, he in holes:
            if he <= s or hs >= e:
                continue
            if hs > s:
                out.append([s, hs])
            s = max(s, he)
            if s >= e:
                break
        if s < e:
            out.append([s, e])
    return out


def _end_execute(sp, miss, t_execute):
    """The ``exec.writeback`` boundary, one clock reading: on a hit
    (``miss`` None) that is all; on a miss the first call has returned, so
    make the miss's log entry from what the listeners gathered and feed
    the always-on histograms from it. Returns (the reading, the entry or
    None).

    A stage's time is the UNION of its intervals (a jitted function
    called inside the step fires its own trace event inside the outer
    one's), and an instant belongs to one stage: backend over lower over
    trace (a lowering rule that traces is lowering). With obs on the four
    stages become children of this step's ``exec.execute``."""
    if miss is None:
        return _boundary(sp, "exec.writeback", "execute", t_execute), None
    at = obs.now()
    _miss_tls.open = None
    backend = _merged(miss.spans["backend"])
    lower = _merged(miss.spans["lower"])
    trace = _without(_without(_merged(miss.spans["trace"]), backend), lower)
    stages = {"trace": trace, "lower": _without(lower, backend),
              "backend": backend}
    seconds = {k: sum(e - s for s, e in v) for k, v in stages.items()}
    # the first run begins where the last compile ended (nothing
    # compiled: where the call began)
    ran_from = backend[-1][1] + miss.skew if backend else t_execute
    ran_from = min(max(ran_from, t_execute), at)
    # JAX asks its cache even where no directory is placed: that is "off"
    requests = miss.requests if jax.config.jax_compilation_cache_dir else 0
    cache = "off" if not requests else \
        "hit" if miss.hits == requests else "miss"
    builder_s = t_execute - miss.t0
    compile_s = min(builder_s + sum(seconds.values()), at - miss.t0)
    entry = {"entry": miss.entry, "program": miss.program,
             "version": miss.version, "t0": miss.t0, "t1": at,
             "trace_s": seconds["trace"], "lower_s": seconds["lower"],
             "backend_s": seconds["backend"], "cache": cache,
             "cache_requests": requests, "cache_hits": miss.hits,
             "retrieval_s": miss.retrieval_s,
             "first_run_s": at - ran_from, "builder_s": builder_s,
             "compile_s": compile_s}
    _misses.append(entry)
    if obs.enabled():
        # retroactive, obs-only: no profiler session covers a miss
        for stage, labels in (("trace", {}), ("lower", {}), ("backend", {
                "cache": cache, "retrieval_s": miss.retrieval_s})):
            if stages[stage]:
                obs.record("exec." + stage,
                           stages[stage][0][0] + miss.skew,
                           stages[stage][-1][1] + miss.skew,
                           seconds=seconds[stage], **labels)
        obs.record("exec.first_run", ran_from, at)
    resilience.observe_executor_step("compile", compile_s)
    resilience.observe_executor_step("execute", at - miss.t0 - compile_s)
    sp.phase("exec.writeback", at)
    return at, entry


def miss_log():
    """The process's last 32 step-cache misses, oldest first: where each
    one's time went. One plain dict a miss, kept whether obs is on or off
    (it is to a miss what ``Executor.cache_misses`` is to the count; the
    pipeline routes' misses are counted and not logged):

    ``entry`` ("run" / "run_steps" / "compiled": a CompiledProgram's
    step), ``program`` (its id) and ``version``; ``t0`` (the
    ``exec.compile`` boundary) and ``t1`` (the first call's return) on
    obs's clock; ``builder_s`` (verification, the closure, the
    ``jax.jit`` wrapper: the ``exec.compile`` phase); ``trace_s``,
    ``lower_s``, ``backend_s`` from JAX's own compile events on this
    thread between the two; ``cache`` ("hit" when the persistent compile
    cache answered every request, else "miss"; "off" when no compile
    asked it or no directory is placed), ``cache_requests``,
    ``cache_hits``, ``retrieval_s``; ``first_run_s`` (from the last
    backend compile's end to ``t1``: executable load, donation, the first
    dispatch); ``compile_s`` = builder + trace + lower + backend, what the
    ``executor_step_seconds{kind="compile"}`` histogram and the
    ``straggler`` event take for the miss."""
    return [dict(e) for e in _misses]


def _watched(what, call, n_steps=1):
    """A pipelined dispatch has no phases: its wall time a step goes to
    the straggler detector (return_numpy syncs the fetches), when one
    is armed."""
    if watchdog.straggler_detector() is None:
        return call()
    t0 = time.perf_counter()
    out = call()
    watchdog.observe_step_latency((time.perf_counter() - t0) / n_steps,
                                  what=what)
    return out


class Executor(object):
    def __init__(self, place=None):
        # Remember whether the caller chose the device. Only an EXPLICIT
        # place may pin jax.default_device during execution — a defaulted
        # Executor must respect an ambient jax.default_device(...) context
        # (e.g. the multichip dryrun pinning everything to CPU while a TPU
        # is attached); an unconditional inner pin would silently override
        # the caller's outer pin.
        self._explicit_place = place is not None
        self.place = place if place is not None else _current_expected_place()
        self._cache = {}
        # step-cache accounting: a miss is a fresh trace+compile, a hit
        # re-dispatches the cached executable
        self.cache_hits = 0
        self.cache_misses = 0
        # numeric_policy="skip" accounting: CONSECUTIVE steps discarded
        # by the in-graph revert; any clean step resets it, crossing
        # the strategy's numeric_skip_budget escalates
        self._numeric_skips = 0

    def _device_ctx(self):
        """default_device context for execution: pin only when the user
        picked a place; otherwise defer to the ambient default."""
        if self._explicit_place:
            return jax.default_device(self.place.jax_device())
        return contextlib.nullcontext()

    def close(self):
        self._cache.clear()

    # ------------------------------------------------------------------
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           steps_per_dispatch=1):
        """Run the whole dataset through the jitted train step (reference
        executor.py train_from_dataset / MultiTrainer). The device_worker
        thread pool maps to background batch prefetch + JAX async
        dispatch: the host stages batch N+1 while the chip runs batch N.
        steps_per_dispatch=W batches W steps into one fused lax.scan
        device program (run_steps) — the reference's in-C++ trainer loop.
        Returns (steps_run, last_fetch_values)."""
        from ..trainer_factory import TrainerFactory
        if dataset is None:
            raise ValueError("dataset is required")
        program = program if program is not None else default_main_program()
        trainer_cls = TrainerFactory()._create_trainer(
            getattr(program, "_fleet_opt", None))
        trainer = trainer_cls(self, program)
        return trainer.run(dataset, fetch_list=fetch_list,
                           fetch_info=fetch_info,
                           print_period=print_period, debug=debug,
                           scope=scope,
                           steps_per_dispatch=steps_per_dispatch)

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        """Same loop, but the program must be inference-only. The reference
        disables gradient push (python/paddle/fluid/executor.py:1061); a
        jitted step has no push to disable, so the equivalent safety is
        rejecting programs that would update parameters — otherwise
        "inference" on a training program silently trains."""
        program = program if program is not None else default_main_program()
        # lr_sched ops mutate persistable schedule counters — the same
        # "inference advances training state" trap clone(for_test=True)
        # strips them for (program.py clone).
        update_ops = sorted({
            op.type for blk in program.blocks for op in blk.ops
            if op.attrs.get("op_role") in ("optimize", "lr_sched")})
        if update_ops:
            raise ValueError(
                "infer_from_dataset got a program containing parameter-"
                "update ops %s; pass the inference program (e.g. "
                "program.clone(for_test=True) taken BEFORE minimize(), or "
                "use train_from_dataset to train)" % (update_ops,))
        return self.train_from_dataset(program, dataset, scope, thread,
                                       debug, fetch_list, fetch_info,
                                       print_period)

    # ------------------------------------------------------------------
    def run(self, program=None, feed=None, fetch_list=None, feed_var_name=None,
            fetch_var_name=None, scope=None, return_numpy=True,
            use_program_cache=True):
        from .compiler import CompiledProgram
        strategy = None
        if isinstance(program, CompiledProgram):
            strategy = program
            program = program._program
        if program is None:
            program = default_main_program()
        scope = scope if scope is not None else global_scope()
        if not fetch_list:
            self._run_eager(program, _reader_feed(program, feed), scope)
            return []
        planned = getattr(program, "_pp_plan", None) is not None
        if planned or (strategy is not None and strategy._pp_enabled()):
            feed = self._step_feed(program, feed, "Executor.run")
            fetch_names = _fetch_names(fetch_list)
            if planned:
                return _watched("Executor.run", lambda: self._run_pipeline(
                    program, feed, fetch_names, scope, return_numpy))
            return _watched("Executor.run", lambda: self._run_compiled_pp(
                strategy, program, feed, fetch_names, scope, return_numpy))
        # the jitted single-step path: exec.step covers the call, its
        # phases tile it (see _run_jitted)
        with obs.span("exec.step", entry="run") as sp:
            try:
                return self._run_jitted(program, feed, fetch_list, scope,
                                        return_numpy, use_program_cache,
                                        strategy, sp)
            except BaseException:
                _miss_tls.open = None   # a miss that raised leaves no mark
                raise

    @staticmethod
    def _step_feed(program, feed, what):
        """The feed a step dispatches: the readers' batches, then the
        chaos-harness injection points, one fire per jitted-step dispatch
        (startup/eager programs don't count). No-ops unless a
        FaultInjector is installed (resilience.inject / PADDLE_TPU_FAULTS)."""
        feed = _reader_feed(program, feed)
        resilience.fire("step", what=what)
        return _hit_step_feed(feed)

    def _run_jitted(self, program, feed, fetch_list, scope,
                    return_numpy, use_program_cache, strategy, sp):
        """One jitted step under its ``exec.step`` span ``sp``. The phases
        tile the span: prepare (the readers' pulls, fetch names, fault
        hooks), feed, prepare (state selection, cache key and lookup),
        compile on a miss, execute, writeback > fetch, release, and with
        obs on records; ``_boundary`` reads the clock for the always-on
        executor_step_seconds{kind=} histograms and the straggler
        detector, the obs layer's executor leg."""
        t_step = _boundary(sp, "exec.prepare")
        feed = self._step_feed(program, feed, "Executor.run")
        fetch_names = _fetch_names(fetch_list)
        sp.phase("exec.feed")
        feed_vals = self._convert_feed(program, feed)
        sp.phase("exec.prepare")
        state_names, uses_rng = self._prepare_state(program, feed, scope)
        check_numerics, policy, skip_budget = _numeric_config(
            program, strategy)
        key = (id(program), program._version,
               _feed_signature(feed_vals), tuple(fetch_names),
               tuple(state_names), check_numerics,
               None if strategy is None else strategy._cache_token())
        step_fn = self._cache.get(key) if use_program_cache else None
        state_vals = tuple(scope.find_var(n) for n in state_names)
        feed_tuple = tuple(feed_vals[k] for k in sorted(feed_vals))
        miss = None
        if step_fn is None:
            self.cache_misses += 1
            sp.set(cache="miss")
            miss = _open_miss("run" if strategy is None else "compiled",
                              program, _boundary(sp, "exec.compile"))
            step_fn = self._compile(program, feed_vals, fetch_names,
                                    state_names, uses_rng, strategy,
                                    check_numerics, policy)
            if use_program_cache:
                self._cache[key] = step_fn
            t_execute = _boundary(sp, "exec.execute")
        else:
            self.cache_hits += 1
            sp.set(cache="hit")
            t_execute = _boundary(sp, "exec.execute")
        if check_numerics:
            fetches, new_state, finite = step_fn(state_vals, feed_tuple)
            finite = np.asarray(finite)
            if not finite.all():
                self._numeric_fault(scope, state_names, new_state,
                                    finite, fetch_names, policy,
                                    skip_budget)
            elif policy == "skip":
                self._numeric_skips = 0   # clean step ends a streak
        else:
            fetches, new_state = step_fn(state_vals, feed_tuple)
        t_writeback, miss = _end_execute(sp, miss, t_execute)
        out = self._writeback(scope, state_names, new_state, fetches,
                              return_numpy)
        t_release = _boundary(sp, "exec.release", "writeback", t_writeback)
        # the step's references to the old state (~a handle a persistable
        # var), the feed and the new state die HERE, after the fetch has
        # returned: where the frame's exit dropped them, now under a name
        del state_vals, feed_vals, feed_tuple, new_state, fetches
        t_end = _boundary(sp, None, "total", t_step)
        self._end_step(program, scope, sp, "Executor.run", 1, t_step,
                       miss, t_execute, t_writeback, t_release, t_end)
        return out

    def _end_step(self, program, scope, sp, what, n_steps, t_step,
                  miss, t_execute, t_writeback, t_release, t_end):
        """What watches a finished step, each only where it is armed: the
        straggler detector gets the step's latency with its phases (the
        boundaries' readings: nothing is clocked again; on a miss
        ``compile_s`` is the builder and the three compile stages of its
        ``miss_log()`` entry, ``execute_s`` the rest of that call), and
        with obs on the layers' registered counters become spans under
        ``exec.records``."""
        if watchdog.straggler_detector() is not None:
            phases = {"feed_prepare_s": (t_execute if miss is None
                                         else miss["t0"]) - t_step,
                      "execute_s": t_writeback - t_execute,
                      "writeback_s": t_release - t_writeback,
                      "release_s": t_end - t_release}
            if miss is not None:
                phases["compile_s"] = miss["compile_s"]
                phases["execute_s"] = t_writeback - miss["t0"] \
                    - miss["compile_s"]
            watchdog.observe_step_latency((t_end - t_step) / n_steps,
                                          what=what, phases=phases)
        if obs.enabled() and getattr(program, "step_records", None):
            sp.phase("exec.records", t_end)
            self._record_step_state(program, scope)

    @staticmethod
    def _record_step_state(program, scope):
        """One obs span a step for every counter a layer registered
        (`Program.record_step_state`), from the state the step itself
        wrote. Reads scope arrays only, all of them in ONE `device_get`
        (one host round trip a step, not one a counter): no program runs
        and `cache_misses` stays."""
        records = [rec for rec in program.step_records
                   if scope.find_var(rec[1]) is not None]
        values = jax.device_get([scope.find_var(rec[1]) for rec in records])
        at = obs.now()
        for (span, _name, labels, summarize), value in zip(records, values):
            obs.record(span, at, at, **dict(
                labels, **(summarize(value) if summarize
                           else {"value": value.tolist()})))

    @staticmethod
    def _writeback(scope, state_names, new_state, fetches, return_numpy):
        """Shared run()/run_steps() tail: persist the new state, convert
        fetches. ``exec.fetch`` holds the wait for the device and the copy
        back; what is left of the caller's ``exec.writeback`` is the scope
        writes."""
        for n, v in zip(state_names, new_state):
            scope.set_var(n, v)
        if return_numpy:
            with obs.span("exec.fetch"):
                return [np.asarray(f) for f in fetches]
        return list(fetches)

    @staticmethod
    def _state_step_no(state_names, new_state):
        """The program's PRNG step counter value, when it carries one —
        names the step in numeric_fault events."""
        try:
            i = state_names.index(STEP_VAR)
        except ValueError:
            return None
        return int(np.asarray(new_state[i]))

    def _numeric_fault(self, scope, state_names, new_state, finite_row,
                       fetch_names, policy, skip_budget,
                       window_offset=0):
        """One step went non-finite: localize the first offending var,
        record the numeric_fault event, and apply the policy tail.

        "skip": the in-graph guard already reverted the state — count
        the consecutive discard (SkipBudgetExceededError past the
        budget) and RETURN so the caller commits the reverted state.
        "rewind"/"raise": write the state back first (the inputs were
        donated, so leaving the scope pointing at them would poison
        every later run for callers that catch this to inspect/resume)
        and raise — NumericFaultError for the trainer's
        rewind-and-skip-the-batch recovery, today's plain
        FloatingPointError otherwise."""
        culprit = _first_offender(finite_row, fetch_names, state_names)
        step_no = self._state_step_no(state_names, new_state)
        evt = {"policy": policy}
        if culprit is not None:
            evt["culprit"] = culprit
        if step_no is not None:
            evt["step"] = step_no
        resilience.record_event("numeric_fault", **evt)
        where = "var %r" % culprit if culprit is not None \
            else "fetches or updated state"
        if policy == "skip":
            self._numeric_skips += 1
            if self._numeric_skips > skip_budget:
                self._writeback(scope, state_names, new_state, (),
                                False)
                raise resilience.SkipBudgetExceededError(
                    "numeric_policy='skip' discarded %d consecutive "
                    "steps (budget %d); last offender: %s — the fault "
                    "is persistent, not a poison batch"
                    % (self._numeric_skips, skip_budget, where),
                    step=step_no, culprit=culprit,
                    window_offset=window_offset)
            return
        self._writeback(scope, state_names, new_state, (), False)
        if policy == "rewind":
            raise resilience.NumericFaultError(
                "numeric fault: non-finite value (NaN/Inf) in %s of "
                "this step — rewinding to the last checkpoint with the "
                "poison batch skipped on replay" % where,
                step=step_no, culprit=culprit,
                window_offset=window_offset)
        raise FloatingPointError(
            "check_numerics: non-finite value (NaN/Inf) detected in "
            "%s of this step (reference parity: check_nan_inf)" % where)

    # ------------------------------------------------------------------
    def run_steps(self, program=None, feed=None, fetch_list=None,
                  scope=None, return_numpy=True, use_program_cache=True):
        """Run N consecutive steps as ONE device program (lax.scan).

        ``feed`` maps each feed name to an array with a leading steps
        axis: step i consumes ``feed[name][i]``. The traced step function
        is scanned over the stacked feeds with the persistable state as
        the carry, so parameters/optimizer moments/PRNG counter thread
        through on-device and the host dispatches ONE computation for the
        whole window. This is the reference's C++ trainer loop
        (`framework/trainer.cc` runs many steps without returning to
        Python) done the XLA way — and it takes per-step host dispatch
        latency off the critical path entirely.

        Returns the fetches of every step, stacked on a leading axis of
        length N. Per-step semantics (dropout PRNG folding, state
        updates) are identical to N sequential ``run`` calls — pinned by
        tests/test_executor_scan.py. Accepts a CompiledProgram: the scan
        is then jitted over the strategy's mesh with the same state/feed
        shardings as run() (stacked feeds gain a replicated steps axis).
        """
        from .compiler import CompiledProgram
        strategy = None
        if isinstance(program, CompiledProgram):
            # sharded window: same scan, jitted over the strategy's mesh
            strategy = program
            program = program._program
        if program is None:
            program = default_main_program()
        if any(r._started for r in getattr(program, "_py_readers", ())):
            raise ValueError("run_steps needs explicit stacked feeds, not "
                             "started py_readers")
        scope = scope if scope is not None else global_scope()
        feed = dict(feed or {})
        fetch_names = _fetch_names(fetch_list or [])
        if not feed or not fetch_names:
            raise ValueError("run_steps requires stacked feeds and a "
                             "fetch_list")
        # .shape/np.shape never sync a device array to host
        lens = {k: (np.shape(v)[0] if np.ndim(v) else None)
                for k, v in feed.items()}
        if None in lens.values() or len(set(lens.values())) != 1:
            raise ValueError(
                "every run_steps feed needs the same leading steps axis; "
                "got %r" % lens)
        n_steps = next(iter(lens.values()))
        if n_steps == 0:
            raise ValueError("run_steps needs at least one step; the "
                             "stacked feeds have a leading axis of 0")
        planned = getattr(program, "_pp_plan", None) is not None
        if planned or (strategy is not None and strategy._pp_enabled()):
            feed = self._window_feed(feed)
            if planned:
                return _watched(
                    "Executor.run_steps", lambda: self._run_pipeline_steps(
                        program, feed, fetch_names, scope, return_numpy,
                        n_steps), n_steps)
            return _watched(
                "Executor.run_steps", lambda: self._run_compiled_pp(
                    strategy, program, feed, fetch_names, scope,
                    return_numpy, windowed=True), n_steps)
        # one exec.step parent per window — the run() path's grouping,
        # so the window's phases share one trace even when no ambient
        # span is open around the caller
        with obs.span("exec.step", entry="run_steps",
                      steps=n_steps) as sp:
            try:
                return self._run_steps_jitted(
                    program, strategy, feed, fetch_names, scope,
                    return_numpy, use_program_cache, n_steps, sp)
            except BaseException:
                _miss_tls.open = None   # a miss that raised leaves no mark
                raise

    @staticmethod
    def _window_feed(feed):
        """One fire per scanned WINDOW (a window is one device dispatch —
        the granularity at which a real preemption would kill the step)."""
        resilience.fire("step", what="Executor.run_steps")
        return _hit_step_feed(feed)

    def _run_steps_jitted(self, program, strategy, feed, fetch_names,
                          scope, return_numpy, use_program_cache,
                          n_steps, sp):
        """`_run_jitted`'s phases for a window; the straggler detector's
        per-step latency is the window's wall-clock / its length."""
        t_step = _boundary(sp, "exec.prepare")
        feed = self._window_feed(feed)
        sp.phase("exec.feed")
        staged = self._convert_feed(program, feed, steps_axis=True)
        sp.phase("exec.prepare")
        check_numerics, policy, skip_budget = _numeric_config(
            program, strategy)
        state_names, uses_rng = self._prepare_state(program, staged,
                                                    scope)
        key = (id(program), program._version,
               _feed_signature(staged), tuple(fetch_names),
               tuple(state_names), check_numerics, "scan",
               None if strategy is None else strategy._cache_token())
        fn = self._cache.get(key) if use_program_cache else None
        state_vals = tuple(scope.find_var(n) for n in state_names)
        feed_tuple = tuple(staged[k] for k in sorted(staged))
        miss = None
        if fn is not None:
            self.cache_hits += 1
            sp.set(cache="hit")
            t_execute = _boundary(sp, "exec.execute")
        else:
            self.cache_misses += 1
            sp.set(cache="miss")
            miss = _open_miss(
                "run_steps" if strategy is None else "compiled", program,
                _boundary(sp, "exec.compile"))
            from .compiler import verify_for_compile
            verify_for_compile(
                program,
                None if strategy is None else strategy._build_strategy,
                feeds={k: tuple(np.shape(v)[1:])
                       for k, v in staged.items()},
                fetch_names=fetch_names, source="compile")
            base_step = self._make_step(program, sorted(staged),
                                        fetch_names, state_names, uses_rng,
                                        check_numerics)
            if check_numerics and policy == "skip":
                # revert inside each scan iteration: a poisoned step's
                # state never reaches the next step of the window
                base_step = _skip_guard(base_step)

            def multi(state_tuple, feed_stack_tuple):
                def body(carry, xs):
                    out = base_step(carry, xs)
                    # (fetches[, finite_flag]) stacked per step
                    return out[1], (out[0],) + out[2:]
                final_state, ys = jax.lax.scan(
                    body, state_tuple, feed_stack_tuple)
                return ys, final_state

            if strategy is not None:
                fn = strategy._build_multi_step(multi, state_names,
                                                sorted(staged))
            else:
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")  # CPU: no donation
                    jitted = jax.jit(multi, donate_argnums=(0,))

                def fn(state_vals, feed_tuple):
                    with self._device_ctx():
                        return jitted(state_vals, feed_tuple)
            if use_program_cache:
                self._cache[key] = fn
            t_execute = _boundary(sp, "exec.execute")
        ys, new_state = fn(state_vals, feed_tuple)
        if check_numerics:
            finite = np.asarray(ys[1])
            # per-step verdicts: (n_steps, n_vars) mask rows, or the
            # legacy (n_steps,) scalar flags
            step_ok = finite.all(axis=1) if finite.ndim == 2 else finite
            if not step_ok.all():
                k = int(np.argmax(~step_ok))
                if policy == "skip":
                    # each bad step's state already reverted in-graph
                    # inside the scan; account every discard, honoring
                    # a streak carried in from previous windows
                    streak, worst, last = self._numeric_skips, 0, None
                    for i, ok_i in enumerate(step_ok):
                        if ok_i:
                            streak = 0
                            continue
                        streak += 1
                        worst = max(worst, streak)
                        last = i
                        c = _first_offender(finite[i], fetch_names,
                                            state_names)
                        resilience.record_event(
                            "numeric_fault", policy="skip", step=i,
                            **({} if c is None else {"culprit": c}))
                    self._numeric_skips = streak
                    if worst > skip_budget:
                        self._writeback(scope, state_names, new_state,
                                        (), False)
                        raise resilience.SkipBudgetExceededError(
                            "numeric_policy='skip' discarded %d "
                            "consecutive steps (budget %d) inside one "
                            "run_steps window" % (worst, skip_budget),
                            step=last, window_offset=last)
                else:
                    # write the post-window state back first — the
                    # input buffers were donated, so leaving the scope
                    # pointing at them would poison every later run.
                    # Unlike run(), detection lands after the scanned
                    # window completes (a scan cannot abort mid-flight)
                    # — the step index still names the first offender
                    self._writeback(scope, state_names, new_state, (),
                                    False)
                    culprit = _first_offender(
                        finite[k] if finite.ndim == 2 else finite[k],
                        fetch_names, state_names)
                    resilience.record_event(
                        "numeric_fault", policy=policy, step=k,
                        **({} if culprit is None
                           else {"culprit": culprit}))
                    tail = "" if culprit is None \
                        else " (first offender: %r)" % culprit
                    if policy == "rewind":
                        raise resilience.NumericFaultError(
                            "numeric fault: non-finite value first "
                            "detected at step %d of this run_steps "
                            "window%s — rewinding with the poison "
                            "batch skipped on replay" % (k, tail),
                            step=k, culprit=culprit, window_offset=k)
                    raise FloatingPointError(
                        "check_numerics: non-finite value (NaN/Inf) "
                        "first detected at step %d of this run_steps "
                        "window%s" % (k, tail))
            elif policy == "skip":
                self._numeric_skips = 0
        t_writeback, miss = _end_execute(sp, miss, t_execute)
        out = self._writeback(scope, state_names, new_state, ys[0],
                              return_numpy)
        t_release = _boundary(sp, "exec.release", "writeback", t_writeback)
        del state_vals, staged, feed_tuple, new_state, ys
        t_end = _boundary(sp, None, "total", t_step)
        self._end_step(program, scope, sp, "Executor.run_steps", n_steps,
                       t_step, miss, t_execute, t_writeback,
                       t_release, t_end)
        return out

    # ------------------------------------------------------------------
    def _convert_feed(self, program, feed, steps_axis=False):
        """Host-side dtype normalization + ONE batched device_put for all
        feeds (a single transfer keeps per-array latency off the step
        critical path).
        steps_axis=True (run_steps): each array carries a leading steps
        axis; shape validation applies to the per-step remainder."""
        out = {}
        blk = program.global_block()
        for name, val in feed.items():
            if isinstance(val, jax.Array):   # already device-resident
                out[name] = val
                continue
            var = blk._find_var_recursive(name)
            dtype = np.dtype(jax.dtypes.canonicalize_dtype(
                to_jax_dtype(var.dtype))) if var is not None else None
            arr = np.asarray(val)
            if dtype is not None and arr.dtype != dtype:
                arr = arr.astype(dtype)
            if var is not None and var.shape is not None:
                want = var.shape
                got = arr.shape[1:] if steps_axis else arr.shape
                kind = "per-step " if steps_axis else ""
                if len(want) != len(got):
                    # named error at the feed boundary (reference parity:
                    # DataFeeder's check), instead of a jax shape error
                    # deep inside the trace
                    raise ValueError(
                        "feed %r has %srank %d (shape %s) but the program "
                        "declares rank %d (shape %s)"
                        % (name, kind, len(got), tuple(got), len(want),
                           tuple(want)))
                for w, g in zip(want, got):
                    if w not in (-1, g):
                        raise ValueError(
                            "feed %r %sshape %s incompatible with declared "
                            "%s" % (name, kind, got, want))
            out[name] = arr
        host = [k for k, v in out.items() if not isinstance(v, jax.Array)]
        if host:
            staged = jax.device_put([out[k] for k in host])
            out.update(zip(host, staged))
        return out

    def _prepare_state(self, program, feed, scope):
        """Select the persistable vars that form the step's carried state
        (+ the implicit PRNG step counter when the program uses RNG)."""
        persistable = _persistable_names(program)
        state_names = sorted(n for n in persistable
                             if scope.find_var(n) is not None
                             and n not in feed)
        uses_rng = _uses_rng(program)
        if uses_rng:
            if scope.find_var(STEP_VAR) is None:
                scope.set_var(STEP_VAR, jnp.asarray(0, jnp.int32))
            if STEP_VAR not in state_names:
                state_names.append(STEP_VAR)
        return state_names, uses_rng

    def _make_step(self, program, feed_names_sorted, fetch_names,
                   state_names, uses_rng, check_numerics=False):
        """Build THE pure step function: forward + backward + optimizer ops
        of `program` traced as one jax computation (what gets jitted)."""
        want_vjp = _want_vjp_set(program)
        seed = program.random_seed

        def step(state_tuple, feed_tuple):
            env = dict(zip(state_names, state_tuple))
            env.update(zip(feed_names_sorted, feed_tuple))
            if uses_rng:
                step_no = env.get(STEP_VAR, jnp.asarray(0, jnp.int32))
                base_key = jax.random.fold_in(jax.random.PRNGKey(seed),
                                              step_no)
                env[STEP_VAR] = step_no + 1
            else:
                base_key = jax.random.PRNGKey(seed)
            ctx = TraceContext(program, base_key, want_vjp)
            trace_block(program.global_block(), env, ctx)
            fetches = tuple(
                trace_mod._lookup(env, n, _FetchOp) for n in fetch_names)
            new_state = tuple(env[n] for n in state_names)
            if check_numerics:
                # PER-VAR finite mask, index-aligned with fetch_names +
                # state_names so the host can NAME the first offender
                # (reference check_nan_inf names the op; we name the
                # tensor). Non-inexact vars hold a constant-folded True
                # placeholder purely to keep the indices aligned.
                flags = []
                for v in list(fetches) + list(new_state):
                    if jnp.issubdtype(jnp.result_type(v), jnp.inexact):
                        flags.append(jnp.all(jnp.isfinite(v)))
                    else:
                        flags.append(jnp.asarray(True))
                flag = jnp.stack(flags) if flags \
                    else jnp.ones((0,), jnp.bool_)
                return fetches, new_state, flag
            return fetches, new_state

        return step

    def _compile(self, program, feed_vals, fetch_names, state_names,
                 uses_rng, strategy, check_numerics=False,
                 numeric_policy="raise"):
        # Program verification at the compile seam (one walk per cache
        # miss): located diagnostics BEFORE the trace turns a malformed
        # program into a first-named-error or a jax traceback
        from .compiler import verify_for_compile
        verify_for_compile(
            program,
            None if strategy is None else strategy._build_strategy,
            feeds={k: np.shape(v) for k, v in feed_vals.items()},
            fetch_names=fetch_names, source="compile")
        step = self._make_step(program, sorted(feed_vals), fetch_names,
                               state_names, uses_rng, check_numerics)
        if check_numerics and numeric_policy == "skip":
            # wrap BEFORE any strategy lowering so the revert select is
            # part of the (globally-viewed) jitted computation
            step = _skip_guard(step)
        if strategy is not None:
            return strategy._build_step(self, step, program, state_names,
                                        sorted(feed_vals), feed_vals,
                                        check_numerics)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")  # CPU ignores donation; fine.
            jitted = jax.jit(step, donate_argnums=(0,))

        def run_step(state_vals, feed_tuple):
            with self._device_ctx():
                return jitted(state_vals, feed_tuple)
        return run_step

    # ------------------------------------------------------------------
    def _pipeline_build(self, program, fetch_names, windowed=False):
        """Build (or fetch the program-cached) fused pipeline step.

        Returns (plan, init_fn, fn) where fn is jitted:
          windowed=False: fn(params, opt_state, x_micro, ys_micro,
              ys_full) -> (fetch_tuple, params, opt_state)
          windowed=True:  same signature with a leading steps axis on the
              data args, scanned on-device (run_steps for pipelines).

        Fetches may be the loss (from the schedule) and/or any var the
        unstamped loss section computes — those are evaluated by one
        extra pipeline forward + the traced tail on the UN-microbatched
        batch with the PRE-update params, which is exactly what a serial
        Executor.run of the unpartitioned program fetches."""
        from ..distributed import pipeline_program as ppp
        from ..distributed.pipeline import (pipeline_loss_and_grads,
                                            pipeline_1f1b_step,
                                            pipeline_forward)
        from ..distributed.mesh import get_mesh
        plan = program._pp_plan
        mesh = get_mesh()
        if mesh is None or "pp" not in mesh.axis_names:
            raise ValueError(
                "pipeline program needs an installed mesh with a 'pp' "
                "axis — call fleet.init with mesh_axes containing 'pp'")
        if mesh.shape["pp"] != plan.n_stage:
            raise ValueError(
                "program has %d pipeline stages but the mesh 'pp' axis has "
                "%d devices — they must match" % (plan.n_stage,
                                                  mesh.shape["pp"]))
        tail_produced = set()
        for op in plan.tail_ops:
            tail_produced.update(op.output_names())
        aux_names = [n for n in fetch_names if n != plan.loss_name]
        unknown = [n for n in aux_names if n not in tail_produced]
        if unknown:
            raise ValueError(
                "pipeline fetch_list entries must be the loss or vars "
                "computed by the unstamped loss section; %r are not "
                "(stage outputs stay sharded on the pp ring)" % (unknown,))
        init_fn, update_fn = ppp.make_update_fn(program._pp_optimizer)
        dp_axis = "dp" if ("dp" in mesh.axis_names and
                           mesh.shape["dp"] > 1) else None
        step_key = (plan.schedule, mesh, dp_axis, tuple(fetch_names),
                    windowed, type(program._pp_optimizer).__name__)
        cache = getattr(program, "_pp_step_cache", None)
        if cache is None:
            cache = program._pp_step_cache = {}
        fn = cache.get(step_key)
        if fn is None:
            stage_fn = ppp.make_stage_fn(program, plan)
            loss_fn = ppp.make_loss_fn(program, plan)
            tail_fn = ppp.make_tail_fn(program, plan, aux_names) \
                if aux_names else None
            if plan.schedule == "gpipe":
                def pipeline_call(params, x, ys):
                    def global_loss(out, ym):
                        return jnp.mean(jax.vmap(loss_fn)(out, ym))
                    return pipeline_loss_and_grads(
                        stage_fn, global_loss, params, x, ys, mesh,
                        dp_axis=dp_axis)
            elif plan.schedule == "1f1b":
                def pipeline_call(params, x, ys):
                    return pipeline_1f1b_step(stage_fn, loss_fn, params,
                                              x, ys, mesh, dp_axis=dp_axis)
            else:
                raise ValueError("unknown pp_schedule %r" % plan.schedule)

            def _unmicro(a):
                # microbatch() is a plain reshape, so merging the first
                # two dims recovers the original batch order
                return a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])

            def _step(params, opt_state, x, ys):
                loss, grads = pipeline_call(params, x, ys)
                aux = ()
                if tail_fn is not None:
                    h = pipeline_forward(stage_fn, params, x, mesh,
                                         dp_axis=dp_axis)
                    aux = tail_fn(_unmicro(h),
                                  tuple(_unmicro(y) for y in ys))
                params, opt_state = update_fn(params, grads, opt_state)
                fetches = tuple(
                    loss if n == plan.loss_name
                    else aux[aux_names.index(n)] for n in fetch_names)
                return fetches, params, opt_state

            if windowed:
                def _multi(params, opt_state, xs, yss):
                    def body(carry, data):
                        p, s = carry
                        fetches, p, s = _step(p, s, *data)
                        return (p, s), fetches
                    (params, opt_state), stacked = jax.lax.scan(
                        body, (params, opt_state), (xs, yss))
                    return stacked, params, opt_state
                target = _multi
            else:
                target = _step
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")  # CPU ignores donation
                fn = jax.jit(target, donate_argnums=(0, 1))
            cache[step_key] = fn
        return plan, init_fn, fn

    def _run_pipeline(self, program, feed, fetch_names, scope,
                      return_numpy):
        """Execute a fleet-partitioned pipeline Program: one jitted step =
        GPipe/1F1B schedule over the mesh's pp axis (x dp when present) +
        the inner optimizer's functional update on the stacked stage
        params (distributed/pipeline_program.py)."""
        from ..distributed import pipeline_program as ppp
        plan, init_fn, step = self._pipeline_build(program,
                                                   tuple(fetch_names))
        params = ppp.stack_params_from_scope(plan, scope)
        opt_state = getattr(program, "_pp_opt_state", None)
        if opt_state is None:
            opt_state = init_fn(params)
        feed_vals = self._convert_feed(program, feed)
        x = ppp.microbatch(feed_vals[plan.x_feed], plan.n_micro)
        ys = tuple(ppp.microbatch(feed_vals[n], plan.n_micro)
                   for n in plan.y_feeds)
        fetches, params, opt_state = step(params, opt_state, x, ys)
        ppp.unstack_params_to_scope(plan, scope, params)
        program._pp_opt_state = opt_state
        if getattr(program, "_check_numerics", False):
            # parity with run(): a non-finite fetch raises instead of
            # silently training on
            for name, arr in zip(fetch_names, fetches):
                if not np.isfinite(np.asarray(arr)).all():
                    raise FloatingPointError(
                        "non-finite value in pipeline fetch %r" % (name,))
        if return_numpy:
            return [np.asarray(f) for f in fetches]
        return list(fetches)

    def _run_pipeline_steps(self, program, feed, fetch_names, scope,
                            return_numpy, n_steps):
        """run_steps for pipeline programs: the whole W-step window is
        ONE device program — lax.scan over the fused GPipe/1F1B step with
        (params, opt_state) as carry."""
        from ..distributed import pipeline_program as ppp
        plan, init_fn, fn = self._pipeline_build(program,
                                                 tuple(fetch_names),
                                                 windowed=True)
        params = ppp.stack_params_from_scope(plan, scope)
        opt_state = getattr(program, "_pp_opt_state", None)
        if opt_state is None:
            opt_state = init_fn(params)
        feed_vals = self._convert_feed(program, feed, steps_axis=True)

        def micro_steps(name):
            arr = jnp.asarray(feed_vals[name])
            if arr.shape[1] % plan.n_micro:
                raise ValueError(
                    "per-step batch %d not divisible by n_micro %d"
                    % (arr.shape[1], plan.n_micro))
            return arr.reshape((arr.shape[0], plan.n_micro,
                                arr.shape[1] // plan.n_micro)
                               + arr.shape[2:])

        xs = micro_steps(plan.x_feed)
        yss = tuple(micro_steps(n) for n in plan.y_feeds)
        stacked, params, opt_state = fn(params, opt_state, xs, yss)
        ppp.unstack_params_to_scope(plan, scope, params)
        program._pp_opt_state = opt_state
        if getattr(program, "_check_numerics", False):
            # the scan cannot abort mid-window; detect afterwards and
            # name the first offending step (loss is always fetched or
            # fetchable — check every fetched output)
            for name, arr in zip(fetch_names, stacked):
                bad = ~np.isfinite(np.asarray(arr))
                if bad.any():
                    step_idx = int(np.argwhere(
                        bad.reshape(bad.shape[0], -1).any(1))[0][0])
                    raise FloatingPointError(
                        "non-finite value in pipeline run_steps fetch %r "
                        "at window step %d" % (name, step_idx))
        if return_numpy:
            return [np.asarray(f) for f in stacked]
        return list(stacked)

    # ------------------------------------------------------------------
    def _run_compiled_pp(self, strategy, program, feed, fetch_names,
                         scope, return_numpy, windowed=False):
        """CompiledProgram pipeline path (BuildStrategy.pp_stages / a >1
        "pp" mesh axis): the strategy's CompilePlan cuts the minimized
        program (trace -> cut -> schedule -> jit) and the step lowers
        through the GPipe/1F1B schedule inside one shard_map over the
        pp x dp mesh — dp gradient sync (quantized included) and the
        program's own update section run unchanged on the other axes.
        Scope stays in per-stage var names (checkpoints/elastic
        machinery see the usual layout); state is stacked onto the pp
        axis per dispatch and unstacked on the way out."""
        from ..distributed import pipeline_program as ppp
        feed_vals = self._convert_feed(program, feed, steps_axis=windowed)
        # verify WITH the real feed shapes + fetch roots before the cut:
        # feed-dependent pp checks (micro-batch divisibility, dp batch
        # divisibility, dead ops) must fire on the actual pp seam, not
        # only in compile_plan's feed-less guard
        from .compiler import verify_for_compile
        verify_for_compile(
            program, strategy._build_strategy,
            feeds={k: (tuple(np.shape(v)[1:]) if windowed
                       else tuple(np.shape(v)))
                   for k, v in feed_vals.items()},
            fetch_names=fetch_names, source="compile")
        cplan = strategy.compile_plan()
        cut = cplan.cut
        plan = cut.plan
        expect = set([plan.x_feed] + list(plan.y_feeds))
        if set(feed_vals) != expect:
            raise ValueError(
                "pipeline program expects exactly the feeds %r; got %r"
                % (sorted(expect), sorted(feed_vals)))
        check_numerics = bool(
            getattr(program, "_check_numerics", False) or
            getattr(strategy._build_strategy, "check_numerics", False))

        def _micro(name):
            arr = jnp.asarray(feed_vals[name])
            if not windowed:
                return ppp.microbatch(arr, plan.n_micro)
            if arr.shape[1] % plan.n_micro:
                raise ValueError(
                    "per-step batch %d not divisible by pp_micro_batches "
                    "%d" % (arr.shape[1], plan.n_micro))
            return arr.reshape((arr.shape[0], plan.n_micro,
                                arr.shape[1] // plan.n_micro)
                               + arr.shape[2:])

        feed_order = [plan.x_feed] + list(plan.y_feeds)
        micro = {n: _micro(n) for n in feed_order}
        key = (id(program), program._version,
               tuple((n, tuple(micro[n].shape), str(micro[n].dtype))
                     for n in feed_order),
               tuple(fetch_names), check_numerics,
               "pp_scan" if windowed else "pp", cplan.token)
        entry = self._cache.get(key)
        if entry is None:
            self.cache_misses += 1
            entry = strategy._build_pp_step(
                program, cplan, tuple(fetch_names),
                {n: tuple(micro[n].shape) for n in feed_order},
                check_numerics, windowed)
            self._cache[key] = entry
        else:
            self.cache_hits += 1
        (stacked_names, stage_cols, shared_names, forder), step_fn = entry

        # flat state order = the step's external signature: per-stage
        # vars grouped by template (stage-major within), then shared.
        # Plain replicated scope arrays in, plain arrays out — the
        # pp-stacking happens INSIDE the jit (no eager multi-device op
        # may race another host thread's dispatch)
        flat_names = [nm for t in stacked_names for nm in stage_cols[t]]
        flat_names += list(shared_names)
        state_vals = []
        for nm in flat_names:
            v = scope.find_var(nm)
            if v is None:
                raise ValueError(
                    "pipeline state %r not initialized — run the "
                    "startup program first" % nm)
            state_vals.append(v)
        feed_tuple = tuple(micro[n] for n in forder)
        out = step_fn(tuple(state_vals), feed_tuple)

        def _writeback_pp(new_state):
            for nm, v in zip(flat_names, new_state):
                scope.set_var(nm, v)

        if windowed:
            ys, new_state = out
            fetch_out = ys[0]
            if check_numerics:
                finite = np.asarray(ys[1])
                if not finite.all():
                    # state back first: inputs were donated (run() parity)
                    _writeback_pp(new_state)
                    raise FloatingPointError(
                        "check_numerics: non-finite value (NaN/Inf) first "
                        "detected at step %d of this pipeline run_steps "
                        "window" % int(np.argmin(finite)))
        elif check_numerics:
            fetch_out, new_state, finite = out
            if not bool(np.asarray(finite)):
                _writeback_pp(new_state)
                raise FloatingPointError(
                    "check_numerics: non-finite value (NaN/Inf) detected "
                    "in fetches or updated state of this pipeline step")
        else:
            fetch_out, new_state = out
        _writeback_pp(new_state)
        if return_numpy:
            return [np.asarray(f) for f in fetch_out]
        return list(fetch_out)

    # ------------------------------------------------------------------
    def dump_hlo(self, program=None, feed=None, fetch_list=None,
                 scope=None, include_compiled=True):
        """Return the XLA text of the SINGLE jitted step for (program,
        feed, fetch_list): {"lowered": StableHLO, "compiled": optimized
        HLO}.

        The TPU-native debugger (ref python/paddle/fluid/debugger.py
        pprint_program / graphviz): one module containing forward, backward
        and optimizer ops — the fused-step design stated in SURVEY §1 —
        inspectable as text. Run the startup program first so parameters
        exist in the scope. Accepts a CompiledProgram too, in which case
        the module is lowered with the strategy's mesh shardings (the dump
        then shows the partitioned program with its collectives).
        """
        from .compiler import CompiledProgram
        strategy = None
        if isinstance(program, CompiledProgram):
            strategy = program
            program = program._program
        if program is None:
            program = default_main_program()
        scope = scope if scope is not None else global_scope()
        feed = dict(feed or {})
        fetch_names = _fetch_names(fetch_list or [])
        state_names, uses_rng = self._prepare_state(program, feed, scope)
        feed_vals = self._convert_feed(program, feed)
        step = self._make_step(program, sorted(feed_vals), fetch_names,
                               state_names, uses_rng)
        state_vals = tuple(scope.find_var(n) for n in state_names)
        feed_tuple = tuple(feed_vals[k] for k in sorted(feed_vals))
        if strategy is not None:
            mesh = strategy._mesh_obj()
            state_sh = tuple(strategy._var_sharding(n, mesh)
                             for n in state_names)
            feed_sh = tuple(strategy._feed_sharding(n, mesh)
                            for n in sorted(feed_vals))
            jitted = jax.jit(step, in_shardings=(state_sh, feed_sh),
                             out_shardings=(None, state_sh),
                             donate_argnums=(0,))
            with mesh:
                lowered = jitted.lower(state_vals, feed_tuple)
                out = {"lowered": lowered.as_text()}
                if include_compiled:
                    out["compiled"] = lowered.compile().as_text()
            return out
        with self._device_ctx():
            lowered = jax.jit(step, donate_argnums=(0,)).lower(
                state_vals, feed_tuple)
            out = {"lowered": lowered.as_text()}
            if include_compiled:
                out["compiled"] = lowered.compile().as_text()
        return out

    # ------------------------------------------------------------------
    def _run_eager(self, program, feed, scope):
        """Op-by-op eager execution (startup programs, init ops)."""
        env = {}
        persistable = _persistable_names(program)
        for n in persistable:
            v = scope.find_var(n)
            if v is not None:
                env[n] = v
        env.update(self._convert_feed(program, feed))
        salt = scope.find_var("@EAGER_SALT@") or 0
        scope.set_var("@EAGER_SALT@", salt + 1)
        base_key = jax.random.fold_in(
            jax.random.PRNGKey(program.random_seed), salt)
        ctx = TraceContext(program, base_key, _want_vjp_set(program))
        with self._device_ctx():
            trace_block(program.global_block(), env, ctx)
        for n in persistable:
            if n in env:
                scope.set_var(n, env[n])


class _FetchOp(object):
    type = "fetch"
