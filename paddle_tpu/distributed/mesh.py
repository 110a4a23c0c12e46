"""Device-mesh management.

Reference parity: the reference builds NCCL communicators per ring
(c_comm_init / gen_nccl_id over brpc); TPU-native: a single jax.sharding.Mesh
over all devices. Axes convention:

  dp — data parallel (batch)          mp — tensor/model parallel
  pp — pipeline stages                sp — sequence/context parallel

Multi-host: jax.distributed.initialize() enrolls every host in the same
mesh; XLA routes collectives over ICI within a pod slice and DCN across
slices — no parameter server processes needed.
"""
import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_mesh = None
_mesh_axes = None      # last init_mesh axes — what a re-init rebuilds from
_reinit_hooks = []     # fns(lost_hosts, live_hosts, mesh) run after re-init
_lost_hosts = set()    # hosts currently out of the mesh (cumulative)
_total_hosts = None    # pod size the loss/absorb fractions scale against


class DistributedStrategy(object):
    """Reference parity: fleet DistributedStrategy. Fields map reference
    knobs onto mesh/sharding decisions."""

    def __init__(self):
        self.mesh_axes = {"dp": 1}
        self.amp = False
        self.recompute = False
        self.gradient_merge_steps = 1
        self.sharding_optimizer_state = False  # ZeRO-1 style
        self.collective_timeout_s = 600.0
        # pipeline parallelism (fleet path; distributed/pipeline_program.py)
        self.pipeline = False
        self.pp_schedule = "1f1b"      # "1f1b" | "gpipe"
        self.pp_num_micro = 1


def shard_map_unchecked(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with varying-axes (VMA) checking off — the one
    convention for bodies VMA typing cannot describe: a Pallas call's
    outputs carry no varying-axes annotation, and the compiled step
    bodies replay cached per-op vjps whose cotangents were never marked
    varying. Bodies that are differentiated THROUGH the shard_map from
    outside (distributed/pipeline.py, sharded_embedding.py) need the
    checked ``jax.shard_map``: its psum transposes are only right with
    VMA on."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def init_mesh(mesh_axes=None, devices=None, multihost=False):
    """Create and install the global mesh. mesh_axes e.g. {"dp":2,"mp":4}."""
    global _mesh, _mesh_axes
    if multihost and jax.process_count() == 1:
        try:
            jax.distributed.initialize()
        except Exception:
            pass
    devices = devices if devices is not None else jax.devices()
    mesh_axes = mesh_axes or {"dp": len(devices)}
    sizes = list(mesh_axes.values())
    n = int(np.prod(sizes))
    dev = np.array(devices[:n]).reshape(sizes)
    _mesh = Mesh(dev, tuple(mesh_axes.keys()))
    _mesh_axes = dict(mesh_axes)
    return _mesh


def reset_mesh():
    """Uninstall the global mesh (tests / reconfiguration)."""
    global _mesh, _mesh_axes, _total_hosts
    _mesh = None
    _mesh_axes = None
    _lost_hosts.clear()
    _total_hosts = None


def add_reinit_hook(fn):
    """Register ``fn(lost_hosts, live_hosts, mesh)`` to run after the
    mesh is rebuilt on a host loss (recompile caches, re-place state,
    notify data loaders). Returns fn for decorator use."""
    _reinit_hooks.append(fn)
    return fn


def clear_reinit_hooks():
    del _reinit_hooks[:]


def handle_host_loss(lost_hosts, live_hosts):
    """Coordinator host-loss hook: rebuild the global mesh over the
    surviving topology and fan out to :func:`add_reinit_hook` hooks.

    The reference restarts NCCL rings (gen_nccl_id + c_comm_init) when a
    trainer drops; the XLA equivalent is re-making the Mesh so the next
    jit re-partitions over the survivors. Data-parallel capacity shrinks
    with the hosts, so the ``dp`` axis is scaled by the survivor
    fraction (model axes describe the MODEL — they must survive intact
    or the job cannot run at all and a NoQuorum/cold-start escalation is
    the right move). On a real pod, jax.distributed re-initialization
    (coordinator-led) replaces the device list; in the single-process
    simulation the visible devices are unchanged and only the shape
    scales. Returns the new mesh (or None when none was installed)."""
    global _mesh, _mesh_axes, _total_hosts
    from ..framework import resilience
    lost, live = sorted(lost_hosts), sorted(live_hosts)
    _lost_hosts.clear()
    _lost_hosts.update(lost)
    _total_hosts = len(lost) + len(live)
    resilience.record_event("mesh_reinit", lost=lost, live=live)
    if _mesh is not None and _mesh_axes:
        # scale from the ORIGINAL axes: lost_hosts is cumulative, so a
        # second loss must not compound a shrink already applied
        base = dict(_mesh_axes)
        axes = dict(base)
        total = len(lost) + len(live)
        if lost and total and "dp" in axes and axes["dp"] > 1:
            axes["dp"] = max(1, axes["dp"] * len(live) // total)
        init_mesh(axes)
        _mesh_axes = base
    for fn in list(_reinit_hooks):
        fn(lost, live, _mesh)
    return _mesh


def absorb_hosts(joined, live_hosts):
    """Inverse of :func:`handle_host_loss`: hosts rejoined the pod —
    re-grow the mesh over the restored topology and fan out to the same
    :func:`add_reinit_hook` hooks (state must be re-sharded back onto
    the larger mesh, step functions recompiled, loaders re-balanced).

    ``joined`` are the hosts being re-absorbed; ``live_hosts`` is the
    live set INCLUDING them. The axes scale from the ORIGINAL topology
    by the new live fraction — when every host is back, the mesh is
    bitwise the full one again, so an Executor/compiler cache keyed on
    the axes (CompiledProgram._cache_token) re-uses the pre-shrink
    executables. Returns the new mesh (or None when none is installed).
    """
    global _mesh, _mesh_axes, _total_hosts
    from ..framework import resilience
    joined, live = sorted(joined), sorted(live_hosts)
    _lost_hosts.difference_update(joined)
    if _total_hosts is None:
        _total_hosts = len(_lost_hosts) + len(live)
    total = _total_hosts
    resilience.record_event("mesh_absorb", joined=joined, live=live,
                            capacity="%d/%d" % (len(live), total))
    if _mesh is not None and _mesh_axes:
        base = dict(_mesh_axes)
        axes = dict(base)
        if _lost_hosts and total and "dp" in axes and axes["dp"] > 1:
            axes["dp"] = max(1, axes["dp"] * len(live) // total)
        init_mesh(axes)
        _mesh_axes = base
    for fn in list(_reinit_hooks):
        fn(sorted(_lost_hosts), live, _mesh)
    return _mesh


def _remap_spec(spec, new_mesh, shape):
    """Filter a PartitionSpec for ``new_mesh``: drop axes the mesh does
    not have and axes whose dim no longer divides the (resized) mesh
    axis — those dims fall back to replicated, mirroring
    CompiledProgram._var_sharding's divisibility rule."""
    axes = set(new_mesh.axis_names)
    out = []
    for i, entry in enumerate(tuple(spec)):
        names = entry if isinstance(entry, tuple) else (entry,)
        keep = []
        for a in names:
            if a is None or a not in axes:
                continue
            keep.append(a)
        if not keep:
            out.append(None)
            continue
        factor = int(np.prod([new_mesh.shape[a] for a in keep]))
        if i < len(shape) and shape[i] is not None \
                and shape[i] % factor != 0:
            out.append(None)
        elif len(keep) == 1:
            out.append(keep[0])
        else:
            out.append(tuple(keep))
    return P(*out)


def reshard_state(state, old_mesh, new_mesh):
    """Remap every NamedSharding-annotated leaf of ``state`` (a
    ``{name: array}`` mapping — e.g. ``dict(scope.items())``) from
    ``old_mesh`` onto ``new_mesh``. Returns a new dict; non-device and
    already-resident leaves pass through untouched.

    The common case — a ``dp`` axis resize where every dim still
    divides — is ONE sharded ``jax.device_put`` per leaf (XLA moves
    only the bytes that change owner). Anything device_put cannot
    express (changed device sets across processes, exotic layouts)
    falls back to gather-then-reshard: materialize on host, then place
    with the new sharding. Specs are filtered per ``new_mesh`` exactly
    like CompiledProgram._var_sharding (missing axes and non-dividing
    dims go replicated), so a shrunk mesh never produces an invalid
    NamedSharding."""
    from ..framework import resilience
    out, moved, gathered = {}, 0, 0
    for name, val in state.items():
        if not isinstance(val, jax.Array):
            out[name] = val
            continue
        sh = getattr(val, "sharding", None)
        if not isinstance(sh, NamedSharding):
            out[name] = val
            continue
        target = NamedSharding(new_mesh,
                               _remap_spec(sh.spec, new_mesh, val.shape))
        if sh == target:
            out[name] = val
            continue
        try:
            out[name] = jax.device_put(val, target)
            moved += 1
        except Exception:
            # gather-then-reshard: the general fallback when a direct
            # cross-sharding transfer is not expressible
            out[name] = jax.device_put(np.asarray(val), target)
            gathered += 1
    resilience.record_event(
        "reshard", moved=moved, gathered=gathered,
        old=None if old_mesh is None else
        {a: int(s) for a, s in old_mesh.shape.items()},
        new={a: int(s) for a, s in new_mesh.shape.items()})
    return out


def get_mesh():
    return _mesh


def mesh_axes():
    return tuple(_mesh.axis_names) if _mesh is not None else ()


def shard_parameter(param, spec):
    """Annotate a Parameter's sharding, e.g. shard_parameter(w, ("mp", None))."""
    param.sharding = tuple(spec)
    return param


def column_parallel_attr(name=None, **kw):
    """ParamAttr for a column-parallel fc weight (out-dim sharded on mp):
    matmul is local; XLA all-gathers activations only when needed."""
    from ..param_attr import ParamAttr
    return ParamAttr(name=name, sharding=(None, "mp"), **kw)


def row_parallel_attr(name=None, **kw):
    """ParamAttr for a row-parallel fc weight (in-dim sharded on mp);
    XLA inserts the psum on the output."""
    from ..param_attr import ParamAttr
    return ParamAttr(name=name, sharding=("mp", None), **kw)
