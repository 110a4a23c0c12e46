"""Where JAX's persistent compilation cache lives.

The cache directory is part of every entry's key, so a directory that
moves never hits. Whoever runs the program may place the cache from
outside with ``JAX_COMPILATION_CACHE_DIR`` (JAX reads that variable
itself); otherwise it goes to ONE fixed, git-ignored directory inside
the checkout, the same whatever the working directory, process id or
time.
"""
import os

import jax

# <checkout>/.jax_compile_cache — this file is paddle_tpu/framework/
_IN_CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_compile_cache")


def place_compile_cache():
    """Call before the first compilation. Leaves JAX's configuration
    alone when ``JAX_COMPILATION_CACHE_DIR`` is set; otherwise points
    ``jax_compilation_cache_dir`` at the in-checkout directory. Returns
    the directory in effect."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", _IN_CHECKOUT_DIR)
    return _IN_CHECKOUT_DIR
