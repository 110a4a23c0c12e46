"""bench_micro perf gates: the CPU-measurable host/compiler verdict
every PR gets without a chip.

Runs the microbench suite in-process and checks every metric against
the per-metric regression budgets declared in bench_micro.BUDGETS —
an order-of-magnitude regression (trace blowup, cache-key churn, a
codec that stopped compressing, a feed hot-loop slowdown) fails tier-1
instead of waiting for a chip run."""
import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import bench_micro  # noqa: E402

pytestmark = pytest.mark.quant


def test_run_all_meets_regression_budgets():
    report = bench_micro.run_all()
    # the output contract: one JSON-serializable dict, headline fields
    line = json.dumps(report)
    parsed = json.loads(line)
    assert parsed["metric"] == "bench_micro"
    assert parsed["platform"] == ["cpu"]
    m = parsed["metrics"]
    for key in bench_micro.BUDGETS:
        assert key in m, "missing metric %r" % key
    assert report.get("errors") is None or not report["errors"], \
        report.get("errors")
    assert report["budgets_ok"], report.get("budget_violations")
    # the headline compression assertion, independent of the budget
    # table: quantized collectives move <= 30% of the raw bytes
    assert m["collective_wire_ratio"] <= 0.30
    assert m["collective_wire_bytes"] < m["collective_raw_bytes"]


def test_check_budgets_flags_violations():
    good = {name: (budget if kind == "max" else budget)
            for name, (kind, budget) in bench_micro.BUDGETS.items()}
    assert bench_micro.check_budgets(good) == []
    bad = dict(good)
    bad["trace_lower_s"] = 1e9            # max exceeded
    bad["cache_hit_rate"] = 0.0           # min violated
    bad.pop("feed_samples_per_s")         # missing metric
    bad["collective_wire_ratio"] = "nope"  # non-numeric
    violations = bench_micro.check_budgets(bad)
    assert len(violations) == 4
    joined = "\n".join(violations)
    for frag in ("trace_lower_s", "cache_hit_rate", "feed_samples_per_s",
                 "collective_wire_ratio"):
        assert frag in joined


def test_budget_table_covers_the_contract():
    """The ISSUE-6 contract metrics are all gated (trace+lower, cache
    hit rate, quantized-vs-exact step wall time, byte ratio, feed
    throughput) plus the ISSUE-8 transport/serving sections (round
    latency, router p50/p99 + shed rate — the last two ROADMAP item 4
    slices)."""
    assert set(bench_micro.BUDGETS) == {
        "trace_lower_s", "cache_hit_rate", "exact_step_s",
        "quant_step_s", "collective_wire_ratio", "feed_samples_per_s",
        "transport_roundtrip_ms", "transport_gather_ms",
        "transport_failover_ms",
        "serving_p50_ms", "serving_p99_ms", "serving_shed_rate",
        "serving_error_rate", "router_failover_ms",
        # ISSUE-16 multi-tenant QoS slice of the serving section:
        # highest-class p99 behind the WFQ cutter + Jain's fairness
        # index over per-class success ratios
        "serving_gold_p99_ms", "serving_fairness",
        "pp_step_s", "pp_bubble_frac", "pp_cache_hit_rate",
        "obs_step_overhead_ratio", "obs_router_overhead_ratio",
        "obs_span_record_us",
        # ISSUE-15 program-verifier section: one walk of the BERT-base
        # pretrain program, the verify/trace+lower overhead ratio, and
        # the zero-false-positive gate on the clean headline program
        "analysis_verify_s", "analysis_overhead_ratio",
        "analysis_bert_errors",
        # ISSUE-17 numeric-fault plane: the in-graph finite-mask cost
        # vs the plain dp step and the wall of one failpoint-poisoned
        # skip-policy recovery
        "numerics_overhead_frac", "fault_recovery_ms",
        # ISSUE-18 elastic pp re-cut: decision commit -> first
        # completed post-re-cut step on the in-process pp=2 pod
        "pp_recut_ms",
        # ISSUE-19 in-memory buddy checkpointing: the per-window
        # snapshot encode+send tax, the buddy restore wall, and the
        # disk load_checkpoint wall it front-runs
        "buddy_snapshot_ms", "buddy_restore_ms",
        "buddy_disk_restore_ms",
        # ISSUE-20 p2p buddy mailboxes + delta snapshots: one dual
        # deposit (own + buddy mailbox + metadata commit) and the
        # delta-wire fraction on the churn-skewed reference scope
        "buddy_p2p_send_ms", "buddy_delta_bytes_ratio"}


def test_analysis_section_measures_the_verifier():
    """ISSUE-15 satellite: the analysis section walks the BERT-base
    pretrain program (clean: zero errors — the bench-side
    no-false-positive gate) and the verifier stays well under the
    trace+lower wall it fronts, so warn-by-default is free to keep
    on."""
    m = bench_micro.bench_analysis()
    assert 0 < m["analysis_verify_s"] < 10.0
    assert 0 < m["analysis_overhead_ratio"] < 0.5
    assert m["analysis_bert_errors"] == 0


def test_pipeline_section_measures_the_pp_path():
    """ISSUE-10 satellite: the pipeline section reports the pp=2 x dp=4
    step wall, a bubble fraction in [0, 1] alongside the (M+K-1)/M
    model value, and a cache-hit rate whose misses equal the number of
    distinct schedule configs (toggle re-lowers, repeats hit)."""
    m = bench_micro.bench_pipeline(steps=2)
    assert 0 < m["pp_step_s"] < 30.0
    assert 0.0 <= m["pp_bubble_frac"] <= 1.0
    assert 0.0 < m["pp_bubble_frac_ideal"] < 1.0
    # 4 toggle runs over 2 distinct schedule configs on one fresh
    # executor: exactly two lowerings, both repeats hit
    assert m["pp_cache_compiles"] == 2
    assert m["pp_cache_hit_rate"] == 0.5


def test_pp_recut_section_measures_the_recut_wall():
    """ISSUE-18 satellite: the pp_recut section kills one host of the
    in-process pp=2 pod and reports the wall from the re-cut decision
    committing to the first completed post-re-cut step, plus the
    re-placed state leaf count (the re-cut moves state, it never
    rewrites it)."""
    m = bench_micro.bench_pp_recut()
    assert 0 < m["pp_recut_ms"] < 30000.0
    assert m["pp_recut_resharded"] > 0


def test_buddy_section_measures_both_restore_paths():
    """ISSUE-19 satellite: the buddy section reports the per-window
    snapshot encode+send tax and both recovery walls — the buddy
    mailbox restore and the disk load_checkpoint it front-runs — all
    inside their budgets (the section itself asserts the restored
    state is bitwise, so a green wall is a CORRECT wall)."""
    m = bench_micro.bench_buddy(windows=3)
    assert 0 < m["buddy_snapshot_ms"] < 5000.0
    assert 0 < m["buddy_restore_ms"] < 5000.0
    assert 0 < m["buddy_disk_restore_ms"] < 10000.0
    # ISSUE-20: the p2p dual deposit stays in the same class as the
    # legacy put, and on the churn-skewed scope (one large static leaf
    # + small churning leaves) the delta wire moves under HALF the
    # full-scope wire — the section asserts the chain reconstructs
    # bitwise, so a green ratio is a CORRECT ratio
    assert 0 < m["buddy_p2p_send_ms"] < 5000.0
    assert 0 < m["buddy_delta_bytes_ratio"] < 0.5


def test_transport_section_measures_latency():
    m = bench_micro.bench_transport(roundtrips=50, gathers=5)
    assert 0 < m["transport_roundtrip_ms"] < 25.0
    assert 0 < m["transport_gather_ms"] < 250.0


def test_failover_section_measures_promotion_round_trip():
    """The HA headline metric: primary killed → gather completes on
    the promoted standby, timed end to end and inside its budget —
    and the standby really did promote (term bumped)."""
    m = bench_micro.bench_failover(hb_deadline_s=0.4)
    assert 0 < m["transport_failover_ms"] < 15000.0
    assert m["transport_failover_term"] >= 1


def test_router_failover_section_measures_client_outage():
    """ISSUE-11 satellite: one of two in-process routers is severed
    mid-load and the pinned FleetClient's first successful request on
    the survivor lands inside the budget — the router tier's outage
    metric, gated in tier-1 like every other budget."""
    m = bench_micro.bench_router_failover(hb_deadline_s=0.5)
    assert 0 < m["router_failover_ms"] < 15000.0


def test_fail_on_drift_is_default_on(tmp_path, capsys):
    """ROADMAP item 4, final slice: with the noise floor calibrated
    (>= MIN_DRIFT_GATE_ROUNDS prior rounds), a drift flag exits
    non-zero by DEFAULT; thinner history keeps it informational, and
    --no-fail-on-drift opts out entirely. (Budgets stay green
    throughout — this is purely the drift gate.)"""
    rd = str(tmp_path / "rounds")
    hist = _good_metrics()
    hist["trace_lower_s"] = 2.0
    for i in range(1, bench_micro.MIN_DRIFT_GATE_ROUNDS + 1):
        _fake_round(rd, i, hist)
    current = dict(hist)
    current["trace_lower_s"] = 10.0      # 5x the median, inside budget
    flags = bench_micro.check_drift(current, rd)
    assert flags and "trace_lower_s" in "\n".join(flags)
    # the gate itself, without re-running the whole suite: drive main()
    # through a stub run_all so only the flag plumbing is under test
    real_run_all = bench_micro.run_all

    def fake_run_all(rounds_dir=None):
        report = {"metric": "bench_micro", "metrics": dict(current),
                  "budgets_ok": True}
        fl = bench_micro.check_drift(current, rounds_dir)
        report["drift_ok"] = not fl
        if fl:
            report["drift_flags"] = fl
        report["drift_gating"] = len(bench_micro._round_files(
            rounds_dir)) >= bench_micro.MIN_DRIFT_GATE_ROUNDS
        return report

    bench_micro.run_all = fake_run_all
    try:
        assert bench_micro.main(["--rounds-dir", rd]) == 1
        assert bench_micro.main(["--rounds-dir", rd,
                                 "--no-fail-on-drift"]) == 0
        # thin history (below the calibration threshold): the same
        # drift flag stays INFORMATIONAL — no gate, exit 0
        thin = str(tmp_path / "thin")
        for i in (1, 2, 3):
            _fake_round(thin, i, hist)
        assert bench_micro.main(["--rounds-dir", thin]) == 0
    finally:
        bench_micro.run_all = real_run_all
    capsys.readouterr()


def _fake_round(rounds_dir, idx, metrics):
    import json
    os.makedirs(rounds_dir, exist_ok=True)
    with open(os.path.join(rounds_dir, "round_%04d.json" % idx),
              "w") as f:
        json.dump({"metric": "bench_micro", "metrics": metrics}, f)


def _good_metrics():
    return {name: budget for name, (kind, budget)
            in bench_micro.BUDGETS.items()}


def test_drift_flags_metric_slide_within_budget(tmp_path):
    """A metric can be well inside its loose absolute budget and still
    have drifted vs its own history — that is exactly what the rounds
    comparison exists to flag."""
    rd = str(tmp_path / "rounds")
    hist = _good_metrics()
    hist["trace_lower_s"] = 2.0          # history: ~2s (budget is 60)
    hist["feed_samples_per_s"] = 9000.0
    for i in (1, 2, 3):
        _fake_round(rd, i, hist)
    current = dict(hist)
    current["trace_lower_s"] = 10.0      # 5x the median, still < 60
    current["feed_samples_per_s"] = 2000.0   # 4.5x slower, still > 1000
    assert bench_micro.check_budgets(current) == []
    flags = bench_micro.check_drift(current, rd)
    joined = "\n".join(flags)
    assert "trace_lower_s" in joined and "feed_samples_per_s" in joined
    # an in-family round raises no flags
    assert bench_micro.check_drift(dict(hist), rd) == []
    # <2 rounds of history: nothing to compare
    assert bench_micro.check_drift(current, str(tmp_path / "empty")) == []


def test_save_round_numbers_sequentially(tmp_path):
    rd = str(tmp_path / "rounds")
    p1 = bench_micro.save_round({"metrics": {}}, rd)
    p2 = bench_micro.save_round({"metrics": {}}, rd)
    assert os.path.basename(p1) == "round_0001.json"
    assert os.path.basename(p2) == "round_0002.json"


def test_run_all_with_rounds_dir_persists_and_reports(tmp_path):
    rd = str(tmp_path / "rounds")
    for i in (1, 2):
        _fake_round(rd, i, _good_metrics())
    report = bench_micro.run_all(rounds_dir=rd)
    assert "drift_ok" in report
    assert os.path.basename(report["round_file"]) == "round_0003.json"
    assert len(os.listdir(rd)) == 3


@pytest.mark.slow
def test_bench_micro_cli_emits_json():
    """End-to-end: `python bench_micro.py` prints one JSON line and
    exits 0. Subprocess = a fresh jax import, so this rides the slow
    marker."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "bench_micro.py")],
        text=True, timeout=420, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stdout[-500:]
    report = json.loads(proc.stdout.strip().splitlines()[-1])
    assert report["metric"] == "bench_micro" and report["budgets_ok"]
