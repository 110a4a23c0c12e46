"""Where JAX's persistent compilation cache lives.

The cache directory is part of every entry's key, so a directory that
moves never hits. Whoever runs the program may place the cache from
outside with ``JAX_COMPILATION_CACHE_DIR`` (JAX reads that variable
itself); otherwise it goes to ONE fixed, git-ignored directory inside
the checkout, the same whatever the working directory, process id or
time.

An entry's key includes the operations' metadata
(``jax_compilation_cache_include_metadata_in_key``): the names the program
lowers its ops under (``framework/trace.py``: ``<role>/<op type>``) are
what a device trace is read by, and without this a cached executable
compiled from the same arithmetic under OTHER names (an older checkout
sharing the directory) would be loaded in its place, names and all.
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
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", _IN_CHECKOUT_DIR)
    return _IN_CHECKOUT_DIR
