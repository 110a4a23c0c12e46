"""Compile-only: cells 1-3's step program at full size for a described
v5e:2x2 topology, no chip attached. A pass says the chip's compiler takes
the program (the flash kernels included) and that the one program fits a
chip's 16 GB; it says nothing about results or times. All such compiles
live in this one file, inside fixtures (on-chip-measurement guide, section
2): only the worker that is given this file loads the TPU's library.
"""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

HBM_BYTES = 16 * 10 ** 9
CELLS = ["bert-base.s128-b256", "gpt2.t4096-b4", "gpt2.t1024-b16"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def lower_step(cell_name, devices, mesh_axes=None):
    """The cell's step program, traced as Executor traces it, lowered and
    compiled for `devices` (described, not attached). The startup program
    runs here on the CPU only to learn the state's shapes."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding
    import paddle_tpu as pt
    from paddle_tpu import optimizer
    from paddle_tpu.framework.scope import Scope
    from benchmark import cells
    cell = cells.Cell(cell_name)
    opt = cell.config["optimizer"]
    adam = optimizer.Adam(learning_rate=opt["learning_rate"])
    main, startup, loss = cell.family.build(cell.config, cell.traffic,
                                            adam.minimize)
    scope = Scope()
    exe = pt.Executor(pt.CPUPlace())
    exe.run(startup, scope=scope)
    rng = np.random.default_rng(0)
    feed = exe._convert_feed(main, cell.family.make_batch(
        cell.config, cell.traffic, rng))
    state_names, uses_rng = exe._prepare_state(main, feed, scope)
    step = exe._make_step(main, sorted(feed), [loss.name], state_names,
                          uses_rng)
    if mesh_axes:
        mesh = Mesh(np.array(devices).reshape(list(mesh_axes.values())),
                    tuple(mesh_axes))
        state_sh = NamedSharding(mesh, P())
        feed_sh = NamedSharding(mesh, P("dp"))
    else:
        state_sh = feed_sh = SingleDeviceSharding(devices[0])

    def struct(x, sharding):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    state = tuple(struct(scope.find_var(n), state_sh) for n in state_names)
    feeds = tuple(struct(feed[k], feed_sh) for k in sorted(feed))
    del scope
    return jax.jit(step, donate_argnums=(0,)).lower(state, feeds).compile()


def device_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("cell_name", CELLS)
def test_step_compiles_for_v5e_and_fits(topo, no_compile_cache, monkeypatch,
                                        cell_name):
    # the process's backend is the CPU, where the kernels' entry would
    # choose interpret mode: steer it to the real Mosaic lowering here
    monkeypatch.setenv("PADDLE_TPU_PALLAS_INTERPRET", "0")
    compiled = lower_step(cell_name, topo.devices[:1])
    need = device_bytes(compiled)
    print("%s: %.2f GiB on the chip by memory_analysis()"
          % (cell_name, need / 2.0 ** 30))
    assert need < HBM_BYTES
    text = compiled.as_text()
    if cell_name.startswith("gpt2"):
        assert "tpu_custom_call" in text    # the flash kernels are in it
    else:
        assert "tpu_custom_call" not in text    # T=128 takes the XLA path
