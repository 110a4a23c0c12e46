"""The dry run is CPU-only whatever the parent's environment: a chip
belongs to one process at a time, and the caller may hold it.

dryrun_multichip self-execs in a fresh subprocess with a guaranteed
CPU-only jax env; these tests pin that contract, including under the TPU
env vars a chip host's shell carries.
"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as graft  # noqa: E402


def test_dryrun_multichip_subprocess_hostile_env(monkeypatch):
    # a chip host's env: TPU forced on, its library unloadable — the
    # subprocess must drop every one of these and still go green
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setenv("TPU_LIBRARY_PATH", "/nonexistent")
    monkeypatch.setenv("PJRT_DEVICE", "TPU")
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0")
    graft.dryrun_multichip(8, timeout=480)


def test_dryrun_env_filter_drops_tpu_keys():
    hostile = ["JAX_PLATFORMS", "TPU_LIBRARY_PATH", "PJRT_DEVICE",
               "TPU_VISIBLE_CHIPS", "LIBTPU_INIT_ARGS",
               "MEGASCALE_COORDINATOR", "XLA_FLAGS", "CLOUD_TPU_TASK_ID"]
    for k in hostile:
        assert any(p in k.upper() for p in graft._TPU_ENV_PAT), k
    # benign keys survive the filter
    for k in ["PATH", "HOME", "PYTHONHASHSEED"]:
        assert not any(p in k.upper() for p in graft._TPU_ENV_PAT), k


def test_dryrun_failure_surfaces_child_tail():
    # a broken child must raise, not hang silently past the driver budget
    with pytest.raises(RuntimeError, match="dryrun_multichip"):
        graft.dryrun_multichip(8, timeout=0.001)
