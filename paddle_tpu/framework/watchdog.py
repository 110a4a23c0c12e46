"""Collective/step timeout watchdog — halt & failure detection.

Reference parity: the reference's collective ops carry a timeout and the
trainer aborts on stuck NCCL rings (operators/collective/ +
check_nan_inf-style failure hooks). Under XLA a hung ICI/DCN collective
(straggler host, preempted chip) shows up as a step whose outputs never
become ready, so the TPU-native guard is a watchdog around
``block_until_ready``: the wait runs on a helper thread and a bounded join
turns a silent hang into a diagnosable CollectiveTimeoutError.
"""
import threading

import jax

__all__ = ["CollectiveTimeoutError", "wait_with_timeout", "bounded_call",
           "StragglerDetector", "enable_straggler_detection",
           "disable_straggler_detection", "straggler_detector",
           "observe_step_latency", "straggler_action_due"]


class CollectiveTimeoutError(RuntimeError):
    """A jitted step (and therefore some collective in it) failed to
    complete within the configured timeout."""


class StragglerDetector(object):
    """Per-step latency EWMA — flag a slow host BEFORE it hangs.

    The watchdog only knows "done within timeout_s"; a straggling host
    (thermal throttle, noisy neighbor, degrading ICI link) serves k
    warnings before it becomes a hard CollectiveTimeoutError. Each
    ``observe(seconds)`` updates ``ewma = alpha*x + (1-alpha)*ewma`` and
    records a ``straggler`` resilience event when a step exceeds
    ``k × ewma`` (after ``warmup`` samples, and only past
    ``min_latency_s`` so microsecond jitter never pages anyone).

    Straggler samples still update the EWMA: a PERSISTENT slowdown
    recalibrates the baseline instead of flagging every step forever —
    the signal is the transition, which is when rebalancing helps.

    MITIGATION, not just detection: ``action_k`` (> k) arms a second,
    critical threshold. A step past ``action_k × ewma`` is a host that
    is very probably about to become a hard CollectiveTimeoutError, so
    the detector latches an action flag (``straggler_critical`` event);
    the training loop polls :func:`straggler_action_due` at the next
    step boundary and takes a pre-emptive checkpoint (``straggler_ckpt``
    event) — the eventual hang then costs at most one step of replay.
    """

    def __init__(self, alpha=0.2, k=3.0, warmup=5, min_latency_s=0.0,
                 action_k=None):
        if not 0.0 < alpha <= 1.0:
            raise ValueError("alpha must be in (0, 1]")
        if k <= 1.0:
            raise ValueError("k must be > 1 (k*ewma is the flag line)")
        if action_k is not None and action_k < k:
            raise ValueError("action_k is the SECOND threshold — it must "
                             "be >= k (got action_k=%g < k=%g)"
                             % (action_k, k))
        self.alpha = float(alpha)
        self.k = float(k)
        self.warmup = int(warmup)
        self.min_latency_s = float(min_latency_s)
        self.action_k = None if action_k is None else float(action_k)
        self._action_due = False
        self._ewma = None
        self._n = 0
        self._lock = threading.Lock()

    @property
    def ewma_s(self):
        return self._ewma

    @property
    def count(self):
        return self._n

    def observe(self, seconds, what="step", phases=None):
        """Feed one step latency; True if it was flagged as a straggler.
        ``phases`` ({"execute_s": ...}: where the caller's own clock put
        the step's time) rides on the ``straggler`` event of a flagged
        step, so the event says which phase grew."""
        seconds = float(seconds)
        with self._lock:
            # ewma > 0: a zero baseline has no meaningful ratio (and
            # would flag every positive sample forever)
            flagged = (self._n >= self.warmup and self._ewma is not None
                       and self._ewma > 0.0
                       and seconds > self.k * self._ewma
                       and seconds > self.min_latency_s)
            critical = (flagged and self.action_k is not None
                        and seconds > self.action_k * self._ewma)
            if critical:
                self._action_due = True
            ewma = self._ewma
            self._ewma = seconds if self._ewma is None else (
                self.alpha * seconds + (1.0 - self.alpha) * self._ewma)
            self._n += 1
        if flagged:
            from . import resilience
            resilience.record_event("straggler", what=what,
                                    latency_s=seconds, ewma_s=ewma,
                                    ratio=seconds / ewma, **(phases or {}))
        if critical:
            from . import resilience
            resilience.record_event("straggler_critical", what=what,
                                    latency_s=seconds, ewma_s=ewma,
                                    ratio=seconds / ewma)
        return flagged

    def action_due(self):
        """Consume the latched critical flag: True once per critical
        straggler, then False until the next one. The trainer that polls
        this takes the pre-emptive checkpoint."""
        with self._lock:
            due = self._action_due
            self._action_due = False
            return due


# opt-in global detector: armed by ResilientTrainer/operators that want
# early warning; a no-op by default so unrelated runs never pay for it
_detector = [None]


def enable_straggler_detection(alpha=0.2, k=3.0, warmup=5,
                               min_latency_s=0.0, action_k=None):
    """Install (and return) the process-global StragglerDetector fed by
    Executor.run/run_steps and armed wait_with_timeout calls.
    ``action_k`` arms the second (mitigation) threshold — see
    StragglerDetector."""
    _detector[0] = StragglerDetector(alpha=alpha, k=k, warmup=warmup,
                                     min_latency_s=min_latency_s,
                                     action_k=action_k)
    return _detector[0]


def disable_straggler_detection():
    _detector[0] = None


def straggler_detector():
    return _detector[0]


def observe_step_latency(seconds, what="step", phases=None):
    """Feed the global detector (no-op when detection is disabled)."""
    det = _detector[0]
    if det is None:
        return False
    return det.observe(seconds, what=what, phases=phases)


def straggler_action_due():
    """Consume the global detector's critical-straggler flag (False when
    detection is disabled or no critical straggler was seen). Trainers
    poll this at step boundaries to take the pre-emptive checkpoint."""
    det = _detector[0]
    if det is None:
        return False
    return det.action_due()


def bounded_call(fn, timeout_s, name="paddle_tpu-bounded-call"):
    """Run ``fn()`` on a daemon helper thread with a bounded join.

    Returns ``(done, value, error)``; ``done`` False means the join
    timed out and the orphaned thread keeps running in the background.
    The one detect-the-hang mechanism shared by wait_with_timeout and
    resilience.run_with_deadline."""
    box = {}
    done = threading.Event()

    def _worker():
        try:
            box["value"] = fn()
        except BaseException as e:      # surface errors to the caller
            box["error"] = e
        finally:
            done.set()

    t = threading.Thread(target=_worker, daemon=True, name=name)
    t.start()
    if not done.wait(float(timeout_s)):
        return False, None, None
    return True, box.get("value"), box.get("error")


def wait_with_timeout(outputs, timeout_s, what="jitted step"):
    """Block until every array in ``outputs`` is ready, or raise
    CollectiveTimeoutError after ``timeout_s`` seconds.

    The computation itself cannot be cancelled (XLA owns the device), but
    raising lets the trainer log, checkpoint-abort, or tear down the mesh
    instead of hanging forever — the reference's collective-timeout
    semantics. Returns ``outputs`` for call-through style.
    """
    if timeout_s is None:
        return outputs
    leaves = jax.tree_util.tree_leaves(outputs)

    def _wait_all():
        for leaf in leaves:
            ready = getattr(leaf, "block_until_ready", None)
            if ready is not None:
                ready()

    done, _, err = bounded_call(_wait_all, timeout_s,
                                name="paddle_tpu-collective-watchdog")
    # NOTE: an armed wait does NOT feed the straggler detector —
    # Executor.run/run_steps already observe the full dispatch latency,
    # and the compiled path's one-behind wait is near-zero when fetches
    # were synced, which would halve the EWMA baseline (double-count).
    if not done:
        # observability: every watchdog trip lands in the resilience
        # event log (lazy import — resilience imports this module)
        from . import resilience
        resilience.record_event("watchdog_timeout", what=what,
                                timeout_s=float(timeout_s))
        raise CollectiveTimeoutError(
            "%s did not complete within %.1fs (process %d/%d, %d local "
            "devices) — likely a hung collective: straggler or failed "
            "host, or a mismatched mesh/sharding across processes"
            % (what, float(timeout_s), jax.process_index(),
               jax.process_count(), jax.local_device_count()))
    if err is not None:
        raise err
    return outputs
