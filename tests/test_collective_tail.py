"""Collective kernel tail: c_allreduce_{max,min,prod}, c_broadcast,
c_reducescatter, ppermute inside shard_map on the 8-device mesh —
values checked against the closed-form results."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.registry import get_op

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


class _Ctx:
    bound_axes = ("dp",)

    def rng(self):
        return jax.random.PRNGKey(0)


def _mesh():
    return Mesh(np.array(jax.devices()[:8]), ("dp",))


def _run_collective(op_name, x, attrs, out_spec=P("dp")):
    def body(xs):
        out = get_op(op_name).fn(_Ctx(), {"X": [xs]},
                                 dict(attrs, axis_name="dp"))
        return out["Out"]

    f = shard_map(body, mesh=_mesh(), in_specs=P("dp"),
                  out_specs=out_spec)
    return np.asarray(f(jnp.asarray(x)))


def test_allreduce_max_min_prod():
    x = np.arange(1.0, 9.0, dtype=np.float32)      # one scalar per chip
    np.testing.assert_allclose(
        _run_collective("c_allreduce_max", x, {}), np.full(8, 8.0))
    np.testing.assert_allclose(
        _run_collective("c_allreduce_min", x, {}), np.full(8, 1.0))
    np.testing.assert_allclose(
        _run_collective("c_allreduce_prod", x, {}),
        np.full(8, float(np.prod(x))), rtol=1e-5)


def test_broadcast_from_root():
    x = np.arange(8.0, dtype=np.float32) + 100.0
    got = _run_collective("c_broadcast", x, {"root": 3})
    np.testing.assert_allclose(got, np.full(8, 103.0))


def test_reducescatter():
    # per-chip input of length 8; psum_scatter leaves each chip the
    # sum of its own slot across chips
    x = np.tile(np.arange(8.0, dtype=np.float32), 8)   # (64,) sharded
    got = _run_collective("c_reducescatter", x, {}, out_spec=P("dp"))
    # every chip's local slice held [0..7]; chip i ends with sum over
    # chips of element i = 8*i
    np.testing.assert_allclose(got, 8.0 * np.arange(8.0))


def test_ppermute_ring_shift():
    x = np.arange(8.0, dtype=np.float32)
    got = _run_collective("ppermute", x, {"shift": 1})
    # ring shift by one: chip i receives chip (i-1)'s value
    np.testing.assert_allclose(got, np.roll(x, 1))


def test_collectives_identity_off_mesh():
    """Outside shard_map (no bound axis) every collective is identity —
    the single-device degeneration the kernels promise."""
    class NoCtx:
        bound_axes = ()

        def rng(self):
            return jax.random.PRNGKey(0)

    x = jnp.arange(4.0)
    for name in ("c_allreduce_max", "c_allreduce_min",
                 "c_allreduce_prod", "c_broadcast", "c_reducescatter",
                 "ppermute"):
        out = get_op(name).fn(NoCtx(), {"X": [x]}, {"axis_name": "dp"})
        np.testing.assert_allclose(np.asarray(out["Out"]),
                                   np.asarray(x), err_msg=name)
