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
    in_key = jax.config.jax_compilation_cache_include_metadata_in_key
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      in_key)


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


def test_a_cached_step_keeps_the_names_it_was_lowered_under(tmp_path):
    """The same arithmetic lowered under other scope names must not load
    the first one's executable from the persistent cache: the names are
    what a device trace is read by (framework/trace.py's role/op scopes),
    so `place_compile_cache` puts the metadata into the key."""
    code = (
        "import re, sys\n"
        "sys.path.insert(0, %r)\n"
        "from paddle_tpu.framework.compile_cache import "
        "place_compile_cache\n"
        "place_compile_cache()\n"
        "import jax, jax.numpy as jnp\n"
        "jax.config.update("
        "'jax_persistent_cache_min_compile_time_secs', 0)\n"
        "jax.config.update("
        "'jax_persistent_cache_min_entry_size_bytes', -1)\n"
        "def make(scope):\n"
        "    def f(x):\n"
        "        with jax.named_scope(scope):\n"
        "            return jnp.sin(x) @ x\n"
        "    return f\n"
        "x = jnp.ones((32, 32))\n"
        "for scope in ('forward/mul', 'backward/mul'):\n"
        "    text = jax.jit(make(scope)).lower(x).compile().as_text()\n"
        "    names = set(re.findall(r'op_name=\"([^\"]+)\"', text))\n"
        "    assert any(scope in n for n in names), (scope, names)\n"
        "print('ok')\n" % ROOT)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().endswith("ok")
    assert os.listdir(str(tmp_path))        # the cache was in use


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
