"""watchdog.wait_with_timeout coverage (resilience PR satellite):
timeout path, device-error propagation, timeout_s=None passthrough, and
pytree (non-array leaf) inputs — plus the straggler-detection EWMA
(pod-recovery PR satellite): flag a slow step BEFORE it becomes a hard
CollectiveTimeoutError."""
import time

import pytest

import jax.numpy as jnp

from paddle_tpu.framework import resilience, watchdog
from paddle_tpu.framework.watchdog import (CollectiveTimeoutError,
                                           StragglerDetector,
                                           disable_straggler_detection,
                                           enable_straggler_detection,
                                           observe_step_latency,
                                           straggler_detector,
                                           wait_with_timeout)


class _SlowLeaf(object):
    """Array stand-in whose readiness wait hangs (a stuck collective)."""

    def __init__(self, delay_s):
        self._delay_s = delay_s

    def block_until_ready(self):
        time.sleep(self._delay_s)


class _FailingLeaf(object):
    """Array stand-in whose wait dies like a device error."""

    def block_until_ready(self):
        raise RuntimeError("device says no")


def test_timeout_raises_and_logs_event():
    resilience.clear_events()
    t0 = time.time()
    with pytest.raises(CollectiveTimeoutError, match="did not complete"):
        wait_with_timeout([_SlowLeaf(1.0)], 0.05, what="unit-test step")
    assert time.time() - t0 < 0.9   # raised at the timeout, not the hang
    evs = resilience.events("watchdog_timeout")
    assert evs and evs[-1]["what"] == "unit-test step"


def test_device_error_propagates_not_timeout():
    # the waiter thread's exception reaches the caller (bounded_call
    # hands it back), not a timeout
    with pytest.raises(RuntimeError, match="device says no"):
        wait_with_timeout([_FailingLeaf()], 5.0)


def test_none_timeout_is_passthrough():
    # no watchdog thread, no wait — even a would-hang leaf returns now
    outputs = {"a": _SlowLeaf(60.0)}
    t0 = time.time()
    assert wait_with_timeout(outputs, None) is outputs
    assert time.time() - t0 < 0.5


def test_pytree_with_non_array_leaves():
    # ints/strings have no block_until_ready and must be skipped; None
    # is not a pytree leaf; jnp arrays are genuinely waited on
    tree = {"arr": jnp.arange(3), "n": 3,
            "nested": [None, "tag", jnp.ones(2)]}
    assert wait_with_timeout(tree, 5.0, what="pytree wait") is tree


def test_returns_outputs_for_call_through_style():
    x = jnp.arange(4) * 2
    assert wait_with_timeout(x, 1.0) is x


# ---------------------------------------------------------------------------
# straggler detection (per-step latency EWMA)
# ---------------------------------------------------------------------------

@pytest.fixture(autouse=True)
def _clean_straggler_state():
    """The detector and the event log are process-global: isolate."""
    disable_straggler_detection()
    resilience.clear_events()
    yield
    disable_straggler_detection()
    resilience.clear_events()


def test_straggler_flagged_after_warmup_with_event():
    det = StragglerDetector(alpha=0.5, k=3.0, warmup=3)
    # warmup samples establish the baseline without ever flagging
    for _ in range(3):
        assert not det.observe(0.1)
    assert det.count == 3 and det.ewma_s == pytest.approx(0.1)
    # 10x the EWMA: well past k=3 — flagged, and the event carries the
    # diagnosis (latency, baseline, ratio)
    assert det.observe(1.0, what="unit step")
    evs = resilience.events("straggler")
    assert len(evs) == 1
    ev = evs[-1]
    assert ev["what"] == "unit step"
    assert ev["latency_s"] == pytest.approx(1.0)
    assert ev["ewma_s"] == pytest.approx(0.1)
    assert ev["ratio"] == pytest.approx(10.0)


def test_straggler_event_carries_the_phases_it_was_fed():
    """Executor.run feeds the step's phase durations with its latency:
    the event of a flagged step says which phase grew."""
    det = StragglerDetector(alpha=0.5, k=3.0, warmup=1)
    det.observe(0.1, phases={"execute_s": 0.01, "writeback_s": 0.08})
    assert resilience.events("straggler") == []
    assert det.observe(1.0, what="Executor.run",
                       phases={"execute_s": 0.02, "writeback_s": 0.97})
    ev = resilience.events("straggler")[-1]
    assert (ev["execute_s"], ev["writeback_s"]) == (0.02, 0.97)
    assert ev["latency_s"] == pytest.approx(1.0)
    assert det.observe(50.0)                 # fed no phases: none reported
    assert "writeback_s" not in resilience.events("straggler")[-1]


def test_straggler_persistent_slowdown_recalibrates():
    """Straggler samples still feed the EWMA: a host that becomes slow
    and STAYS slow flags the transition, then stops paging — the new
    latency is the new baseline."""
    det = StragglerDetector(alpha=0.5, k=3.0, warmup=2)
    for _ in range(4):
        det.observe(0.1)
    flags = [det.observe(1.0) for _ in range(6)]
    assert flags[0] is True          # the transition
    assert flags[-1] is False        # recalibrated: no flag storm
    assert not any(flags[3:])


def test_straggler_min_latency_floor_and_warmup_gate():
    # microsecond jitter below the floor never flags, whatever the ratio
    det = StragglerDetector(alpha=0.5, k=2.0, warmup=1,
                            min_latency_s=0.5)
    det.observe(1e-5)
    assert not det.observe(1e-3)     # 100x the EWMA but under the floor
    assert det.observe(1.0)          # past the floor AND past k*ewma
    # warmup: the first sample can never flag (no baseline yet)
    det2 = StragglerDetector(warmup=0)
    assert not det2.observe(5.0)


def test_straggler_constructor_validation():
    with pytest.raises(ValueError, match="alpha"):
        StragglerDetector(alpha=0.0)
    with pytest.raises(ValueError, match="k must be > 1"):
        StragglerDetector(k=1.0)
    with pytest.raises(ValueError, match="action_k"):
        StragglerDetector(k=3.0, action_k=2.0)


def test_straggler_second_threshold_latches_action():
    """Mitigation threshold: past k*ewma flags; past action_k*ewma
    ADDITIONALLY latches the action flag (straggler_critical event) that
    the trainer consumes to take a pre-emptive checkpoint. The flag is
    consume-once."""
    det = StragglerDetector(alpha=0.2, k=2.0, warmup=2, action_k=5.0)
    for _ in range(3):
        det.observe(0.1)
    assert det.observe(0.3)              # straggler, but not critical
    assert not det.action_due()
    assert resilience.events("straggler_critical") == []
    # recalibrate, then blow way past the second threshold
    for _ in range(5):
        det.observe(0.1)
    assert det.observe(2.0)
    assert resilience.events("straggler_critical")
    assert det.action_due() is True      # latched...
    assert det.action_due() is False     # ...and consume-once


def test_global_straggler_action_due_wiring():
    from paddle_tpu.framework.watchdog import straggler_action_due
    assert straggler_action_due() is False          # disabled: no-op
    det = enable_straggler_detection(alpha=0.5, k=2.0, warmup=1,
                                     action_k=3.0)
    det.observe(0.1)
    det.observe(0.1)
    assert det.observe(5.0)
    assert straggler_action_due() is True
    assert straggler_action_due() is False
    disable_straggler_detection()


def test_global_detector_enable_disable_and_observe():
    assert straggler_detector() is None
    assert observe_step_latency(99.0) is False     # disabled: no-op
    det = enable_straggler_detection(alpha=0.5, k=3.0, warmup=1)
    assert straggler_detector() is det
    observe_step_latency(0.1)
    assert observe_step_latency(5.0) is True
    disable_straggler_detection()
    assert straggler_detector() is None


def test_executor_feeds_global_detector():
    """Executor.run / run_steps report their dispatch latency to the
    armed detector (the wiring, not the flagging, is under test)."""
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import layers
    main, startup = pt.Program(), pt.Program()
    with pt.program_guard(main, startup):
        x = layers.data("sd_x", [3], dtype="float32")
        y = layers.fc(x, size=2)
    exe = pt.Executor()
    exe.run(startup)
    det = enable_straggler_detection(warmup=1000)   # observe-only
    xv = np.ones((2, 3), np.float32)
    exe.run(main, feed={"sd_x": xv}, fetch_list=[y])
    assert det.count == 1
    stacked = {"sd_x": np.ones((4, 2, 3), np.float32)}
    exe.run_steps(main, feed=stacked, fetch_list=[y])
    assert det.count == 2


def test_armed_wait_does_not_double_feed_detector():
    """The compiled path's one-behind wait must NOT feed the detector:
    Executor.run/run_steps already observe the full dispatch latency,
    and the wait's near-zero sample would halve the EWMA baseline."""
    det = enable_straggler_detection(warmup=1000)
    wait_with_timeout([_SlowLeaf(0.01)], 5.0, what="armed wait")
    with pytest.raises(CollectiveTimeoutError):
        wait_with_timeout([_SlowLeaf(1.0)], 0.05)
    assert det.count == 0


def test_straggler_zero_baseline_never_flags_or_crashes():
    """An all-zero warmup (clock granularity) must not make every later
    positive sample a straggler — and must never divide by the zero
    EWMA when recording the event."""
    det = StragglerDetector(alpha=0.5, k=3.0, warmup=1)
    det.observe(0.0)
    det.observe(0.0)
    assert not det.observe(0.1)      # no baseline ratio: not flagged
    assert resilience.events("straggler") == []
    for _ in range(8):               # a real baseline forms...
        det.observe(0.1)
    assert det.observe(10.0)         # ...and flagging works again
