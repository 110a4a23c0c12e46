"""Sharded embedding tables — the TPU-native parameter-server replacement.

Reference parity: operators/distributed/* + distribute_transpiler's pserver
path, whose job is ONE thing — keep an embedding table too big for one
device and serve sparse lookup/update. On a TPU pod there are no parameter
server processes: the table is row-sharded over a mesh axis, lookups are a
local masked gather + psum over that axis (each id's row lives on exactly
one shard, so the psum sums one hit and zeros), and the backward is the
transposed scatter-add into the local shard — XLA keeps every update local
to the owner shard. Pair with Adam(lazy_mode=True) for row-sparse moments.
"""
import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P


def sharded_embedding_lookup(table, ids, mesh, axis="mp"):
    """Lookup rows of a row-sharded table.

    table: (V, D) sharded on rows over `axis` (V divisible by axis size)
    ids:   int array, any shape, replicated
    Returns ids.shape + (D,), replicated. Differentiable w.r.t. table; the
    cotangent is the dense scatter-add restricted to each owner shard.
    """
    n_shard = mesh.shape[axis]
    v, d = table.shape
    rows_per = v // n_shard

    def local_fn(tbl, ids_local):
        shard = lax.axis_index(axis)
        lo = shard * rows_per
        local = ids_local - lo
        hit = (local >= 0) & (local < rows_per)
        safe = jnp.clip(local, 0, rows_per - 1)
        vals = tbl[0][safe]                       # (..., D) local gather
        vals = jnp.where(hit[..., None], vals, 0)
        return lax.psum(vals, axis)

    fn = shard_map(local_fn, mesh=mesh,
                   in_specs=(P(axis), P()), out_specs=P())
    return fn(table.reshape(n_shard, rows_per, d), ids)


class ShardedEmbedding(object):
    """Big-table embedding living row-sharded on the mesh (pserver-table
    equivalent). Keeps the table as a device array with a NamedSharding so
    optimizer updates stay shard-local under jit."""

    def __init__(self, num_embeddings, dim, mesh, axis="mp", scale=0.01,
                 seed=0, dtype=jnp.float32):
        if num_embeddings % mesh.shape[axis]:
            raise ValueError("num_embeddings must divide the %r axis size"
                             % axis)
        self.mesh = mesh
        self.axis = axis
        self.num_embeddings = num_embeddings
        self.dim = dim
        key = jax.random.PRNGKey(seed)
        host = jax.random.normal(key, (num_embeddings, dim), dtype) * scale
        self.table = jax.device_put(
            host, NamedSharding(mesh, P(axis, None)))

    def __call__(self, ids):
        return sharded_embedding_lookup(self.table, ids, self.mesh,
                                        self.axis)

    def apply_row_sparse_grad(self, grad, lr):
        """SGD row update; grad is the dense cotangent (zero rows for
        untouched ids). Sharded identically to the table, so the update
        is local per shard."""
        self.table = self.table - lr * grad


def distributed_embedding_attr(name=None, axis="mp", **kw):
    """ParamAttr annotating a static-graph embedding table as row-sharded
    (the is_distributed=True path of layers.embedding): CompiledProgram
    places it with NamedSharding(mesh, (axis, None)) and XLA partitions
    lookups/updates across shards."""
    from ..param_attr import ParamAttr
    return ParamAttr(name=name, sharding=(axis, None), **kw)
