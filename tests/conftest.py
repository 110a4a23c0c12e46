"""Test config: force CPU backend with 8 virtual devices BEFORE jax import,
so sharding/collective tests run anywhere (mirrors how the driver validates
multi-chip via xla_force_host_platform_device_count)."""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# the Program verifier (framework/analysis.py) runs STRICT across the
# whole suite: every program any test compiles must verify clean (or
# carry an explicit analysis.allowlist) — the acceptance bar for the
# verifier's no-false-positive contract. Respect an explicit override
# so `PADDLE_TPU_VERIFY=off pytest` can bisect verifier-vs-product
# failures.
os.environ.setdefault("PADDLE_TPU_VERIFY", "strict")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# the helper modules' asserts read as a test file's do
pytest.register_assert_rewrite("_flash_cases", "_moe_cases")


#: The files a whole tier-1 run is longest in, longest first (the seconds a
#: file of `/tmp/_t1.xml`, PR 46). Under `--dist loadfile` a file is one
#: worker's, so a long file that starts late is what five workers wait for
#: at the end: these are handed out first, in this order, and the rest as
#: collected. A stale tuple costs balance, never a test.
LONGEST_FIRST = (
    "benchmark_suite/test_phi4flash.py",
    "benchmark_suite/test_nemotronh_family.py",
    "benchmark_suite/test_smallthinker_family.py",
    "benchmark_suite/test_lfm2moe_family.py",
    "benchmark_suite/test_compile_kimivl.py",
    "benchmark_suite/test_compile_fullsize.py",
    "benchmark_suite/test_kimivl_family.py",
    "test_moe_ops.py",
    "test_kimi_vl.py",
    "test_moe_layer.py",
    "benchmark_suite/test_kimilinear_family.py",
    "test_flash_fused_backward_bf16.py",
    "test_flash_fused_backward.py",
    "test_pod_transport.py",
    "benchmark_suite/test_reference.py",
    "test_flash_attention.py",
    "benchmark_suite/test_compile_smallthinker.py",
    "test_model_zoo_extra.py",
    "benchmark_suite/test_harness.py",
    "benchmark_suite/test_compile_kimilinear.py",
    "test_nemotron_h.py",
    "test_flash_modes.py",
    "test_sequence_parallel.py",
    "benchmark_suite/test_compile_lfm2moe.py",
    "test_distributed.py",
    "test_smallthinker.py",
    "test_kda_op.py",
    "test_lfm2moe.py",
    "test_kda_kernels.py",
    "test_detection_ops.py",
    "test_ssd_kernels.py",
)


def pytest_collection_modifyitems(items):
    here = os.path.dirname(os.path.abspath(__file__))
    rank = {name: at for at, name in enumerate(LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(      # stable: files stay whole
        os.path.relpath(str(item.path), here).replace(os.sep, "/"),
        len(rank)))


def pytest_configure(config):
    # pytest-xdist hands files out by their number of tests, most first,
    # unless told to keep the collection's order, which is the one above
    config.option.loadscopereorder = False
    # registered markers so tier-1 (-m 'not slow') runs warning-free:
    # fast chaos tests carry `faultinject`; long soaks hide behind `slow`
    config.addinivalue_line(
        "markers", "slow: long soak/perf tests excluded from tier-1 runs")
    config.addinivalue_line(
        "markers",
        "faultinject: fast chaos tests driven by framework.resilience")
    config.addinivalue_line(
        "markers",
        "pod: pod-level coordinated-recovery tests (threaded "
        "LocalCoordinator only — tier-1-safe)")
    config.addinivalue_line(
        "markers",
        "data: elastic data plane tests (ShardedFeed cursors, "
        "membership re-balancing, exact-batch resume)")
    config.addinivalue_line(
        "markers",
        "procpod: REAL-process pod-transport tests (subprocesses over "
        "SocketCoordinator, SIGKILL chaos) — wall-bounded, tier-1-safe")
    config.addinivalue_line(
        "markers",
        "quant: quantized-collective / compressed-state-movement tests "
        "(block codec, quantize_collectives guardrails, compressed "
        "checkpoints)")
    config.addinivalue_line(
        "markers",
        "pallas: Pallas kernel batteries (the kernels' names in the "
        "traced step, the fused head op's wiring) — interpret mode on "
        "CPU, tier-1-safe")
    config.addinivalue_line(
        "markers",
        "fleet: serving-fleet batteries (micro-batching router + "
        "replica members over CoordServer; SIGKILL chaos under "
        "sustained load) — wall-bounded, tier-1-safe")
    config.addinivalue_line(
        "markers",
        "pp: pipeline-parallel CompiledProgram batteries (pp x dp mesh "
        "cut/lowering, GPipe/1F1B parity, elastic pp rewind) — CPU "
        "8-device mesh, tier-1-safe")
    config.addinivalue_line(
        "markers",
        "obs: distributed-tracing / step-phase-profiler batteries "
        "(obs spans engine, trace-context propagation across the "
        "fleet, traceview merge, tracing-overhead gate) — "
        "tier-1-safe")
    config.addinivalue_line(
        "markers",
        "analysis: Program IR verifier batteries (analysis-pass "
        "framework, adversarial broken-program corpus, progcheck/"
        "codelint tools, strict-mode model sweep) — tier-1-safe")


@pytest.fixture(autouse=True)
def disarmed_failpoints():
    """No test leaks an armed fault schedule (or stale hit counters)
    into the next — the fault-injection plane starts and ends cold."""
    from paddle_tpu.framework import faultinject
    faultinject.disarm()
    faultinject.reset_counters()
    yield
    faultinject.disarm()
    faultinject.reset_counters()


@pytest.fixture(autouse=True)
def mesh_put_back():
    """No test leaves its mesh to the next one of its worker: the global
    mesh (and the axes a re-init rebuilds it from) is what it was."""
    from paddle_tpu.distributed import mesh
    old = mesh._mesh, mesh._mesh_axes
    yield
    mesh._mesh, mesh._mesh_axes = old


@pytest.fixture(autouse=True)
def fresh_programs():
    """Give every test fresh default programs + scope + name generator."""
    import paddle_tpu as pt
    from paddle_tpu.framework.program import (switch_main_program,
                                              switch_startup_program,
                                              Program)
    from paddle_tpu.framework.scope import Scope, _global_scope
    import paddle_tpu.framework.scope as scope_mod
    from paddle_tpu.framework import unique_name

    old_main = switch_main_program(Program())
    old_startup = switch_startup_program(Program())
    old_scope = scope_mod._global_scope
    scope_mod._global_scope = Scope()
    old_gen = unique_name.switch()
    yield
    switch_main_program(old_main)
    switch_startup_program(old_startup)
    scope_mod._global_scope = old_scope
    unique_name.switch(old_gen)


@pytest.fixture
def rng():
    return np.random.RandomState(42)
