"""Nothing on the chip path may pass without the chip: chip_smoke.py fails
off-TPU, TPUPlace does not resolve to a CPU device, the auto attention
path does not swallow a kernel error, and the compile cache has one
placement rule."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.framework import compile_cache
from paddle_tpu.framework.place import PlaceUnavailableError
from paddle_tpu.ops.registry import get_op

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_fails_without_a_tpu_and_names_the_platform():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")], text=True,
        timeout=300, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert "JAX found 'cpu'" in proc.stderr
    # no result line: nothing on stdout claims success
    assert '"ok": true' not in proc.stdout


@pytest.fixture
def restored_cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defers_to_the_jax_variable(monkeypatch, tmp_path,
                                                 restored_cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.place_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_path_in_the_checkout(
        monkeypatch, tmp_path, restored_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    seen = []
    for cwd in (tmp_path, ROOT):
        monkeypatch.chdir(cwd)
        seen.append(compile_cache.place_compile_cache())
        assert jax.config.jax_compilation_cache_dir == seen[-1]
    assert seen[0] == seen[1] == os.path.join(ROOT, ".jax_compile_cache")


def test_tpu_place_raises_in_a_cpu_only_process():
    with pytest.raises(PlaceUnavailableError, match="tpu"):
        pt.TPUPlace(0).jax_device()
    # the README quick start's Executor(TPUPlace()) must not run on CPU
    with pytest.raises(PlaceUnavailableError):
        pt.Executor(pt.TPUPlace()).run(pt.default_startup_program())
    # the default stays usable: it is a default, not a fallback
    assert isinstance(pt.Executor().place, pt.CPUPlace)


def test_auto_attention_propagates_a_kernel_error(monkeypatch):
    from paddle_tpu.ops.pallas import flash_attention as fa

    def broken(*a, **kw):
        raise RuntimeError("injected kernel failure")
    monkeypatch.setattr(fa, "flash_attention", broken)
    # 512 x 512 > 256 x 256: the shape rule picks the flash kernel
    q = jnp.asarray(np.ones((1, 2, 512, 64), np.float32))
    with pytest.raises(RuntimeError, match="injected kernel failure"):
        get_op("scaled_dot_product_attention").fn(
            None, {"Q": [q], "K": [q], "V": [q]}, {"impl": "auto"})
