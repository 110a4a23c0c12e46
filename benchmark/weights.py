"""Seeded weights, made on the device in one jitted call, in the type the
program holds them in. The program and the plain reference both start from
these values: the reference from their float32 copy (`as_float32`).
"""
import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed, stream):
    """A threefry key from any whole-number seed (the driver's pass 2**31)
    and a stream number, through numpy's SeedSequence."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def host_rng(seed, stream):
    return np.random.default_rng(np.random.SeedSequence([int(seed),
                                                         int(stream)]))


def weight_maker(specs, init_range, sharding=None):
    """make(seed) -> {name: array} for `specs` ({name: (shape, dtype,
    kind)}): truncated normal (two sigma) of `init_range`, ones or zeros.
    `sharding` places every leaf (replicated over a mesh for the multi-chip
    cells)."""
    names = sorted(specs)

    def make(key):
        out = {}
        for i, name in enumerate(names):
            shape, dtype, kind = specs[name]
            if kind == "normal":
                leaf = init_range * jax.random.truncated_normal(
                    jax.random.fold_in(key, i), -2.0, 2.0, shape,
                    jnp.float32)
            elif kind == "ones":
                leaf = jnp.ones(shape, jnp.float32)
            elif kind == "zeros":
                leaf = jnp.zeros(shape, jnp.float32)
            else:
                raise ValueError("unknown init %r for %s" % (kind, name))
            out[name] = leaf.astype(dtype)
        return out

    fn = jax.jit(make) if sharding is None else jax.jit(
        make, out_shardings={n: sharding for n in names})
    return lambda seed: fn(seed_key(seed, 0))


@jax.jit
def as_float32(weights):
    return {k: v.astype(jnp.float32) for k, v in weights.items()}
