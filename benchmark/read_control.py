"""python3 benchmark/read_control.py --workload <name> --seeds a,b,c
[--bfloat16]: the control's reading for a cell whose output check fills the
chip. `read_limits.py` keeps the program's state on the chip beside the
reference's five float32 copies of the parameters; at 697M parameters that
is 6.6 + 13 GiB. Here no program is built: for each seed the plain reference
in float8 (and with --bfloat16 in bfloat16) takes the program's place, and
its gaps against the float32 reference are read as `harness.compare` reads
the program's. The program's own readings come from the cell's runs
(`run.py` prints each number beside its limit).

The steps are `reference.follow`'s (same loss, same Adam, same norms), but
the start weights wait on the host: the float8 matmul keeps its rounded
operands for the backward, its temporaries are larger than the float32
run's, and with a fifth copy of the parameters on the chip it does not fit.
Writes what it read to chiprun_out/control.<workload>.json as well."""
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def follow_lean(family, config, traffic, start, batches, precision,
                compare_with=None):
    """`reference.follow` for one chip with `start` ({leaf: float32 numpy
    array}) on the host. Returns its dict, with "first_gradient" on the
    host."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark import reference
    mm = reference.matmul_at(precision)
    rows = family.batch_rows(traffic)
    block = int(traffic["reference_block_rows"])
    value_and_grad = jax.jit(jax.value_and_grad(
        lambda p, blk: family.reference_loss(p, blk, config, traffic, mm)))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=(0,))
    update = jax.jit(functools.partial(reference.adam_update,
                                       opt=config["optimizer"]),
                     donate_argnums=(0, 2, 3), static_argnums=(4,))
    params = {k: jnp.asarray(v) for k, v in start.items()}
    m1 = jax.tree_util.tree_map(jnp.zeros_like, params)
    m2 = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms, diffs, first = [], None, {}, None
    with jax.default_matmul_precision("highest"):
        for step in range(reference.CHECK_STEPS):
            total, grads = 0.0, None
            for lo in range(0, rows, block):
                part, g = value_and_grad(params, family.block_of(
                    batches[step], lo, lo + block))
                grads = g if grads is None else add(grads, g)
                total = total + part
            losses.append(float(total))
            if step == 0:
                grad_norms = reference.leaf_norms(grads)
                for who, theirs in (compare_with or {}).items():
                    diffs[who] = {k: float(reference._diff_norm(g, theirs[k]))
                                  for k, g in grads.items()}
                first = {k: np.asarray(g) for k, g in grads.items()}
            params, m1, m2 = update(params, grads, m1, m2, step + 1)
            del grads
        del m1, m2
        deltas = {k: float(reference._diff_norm(params[k], start[k]))
                  for k in start}
    return {"losses": losses,
            "grad_norms": {k: float(v) for k, v in grad_norms.items()},
            "delta_norms": deltas, "grad_diff_norms": diffs,
            "first_gradient": first}


def read(workload, seeds, platform="tpu", root=None, say=print,
         bfloat16=False):
    import numpy as np
    from benchmark import cells, harness, weights
    cell = cells.Cell(workload, root or cells.ROOT)
    if cell.chips != 1:
        raise ValueError("read_control.py reads one-chip cells")
    devices, _ = harness.attach(platform, cell.chips)
    specs = cell.family.param_specs(cell.config, cell.traffic)
    make = weights.weight_maker(specs, cell.config["initializer_range"])
    no_limit = dict.fromkeys(harness.GAPS, float("inf"))
    kinds = {"control_float8": "float8"}
    if bfloat16:
        kinds["bfloat16"] = "bfloat16"
    out = {"workload": workload, "device": devices[0].device_kind,
           "seconds": {}}
    out.update({kind: {} for kind in kinds})
    for seed in seeds:
        start = {k: np.asarray(v) for k, v in
                 weights.as_float32(make(seed)).items()}
        pool = harness.make_pool(cell, seed)
        rows = {}
        for kind, precision in kinds.items():
            t0 = time.perf_counter()
            rows[kind] = follow_lean(cell.family, cell.config, cell.traffic,
                                     start, pool, precision)
            out["seconds"]["%s %d" % (kind, seed)] = time.perf_counter() - t0
        t0 = time.perf_counter()
        ref = follow_lean(
            cell.family, cell.config, cell.traffic, start, pool, "float32",
            compare_with={k: v["first_gradient"] for k, v in rows.items()})
        out["seconds"]["float32 %d" % seed] = time.perf_counter() - t0
        for kind, numbers in rows.items():
            gaps = {name: (value, note) for name, value, _l, _ok, note
                    in harness.compare(numbers, ref, no_limit, kind)}
            out[kind][str(seed)] = {k: v[0] for k, v in gaps.items()}
            say("%s seed=%d loss_gap=%.3g grad_diff=%.3g (%s) "
                "grad_norm_gap=%.3g (%s) delta_norm_gap=%.3g (%s); "
                "reference %.1f s, control %.1f s"
                % (kind, seed, gaps["loss_gap"][0], gaps["grad_diff"][0],
                   gaps["grad_diff"][1], gaps["grad_norm_gap"][0],
                   gaps["grad_norm_gap"][1], gaps["delta_norm_gap"][0],
                   gaps["delta_norm_gap"][1],
                   out["seconds"]["float32 %d" % seed],
                   out["seconds"]["%s %d" % (kind, seed)]))
        stats = devices[0].memory_stats() or {}
        say("memory after seed %d: peak_bytes_in_use %s peak_bytes_reserved "
            "%s" % (seed, stats.get("peak_bytes_in_use"),
                    stats.get("peak_bytes_reserved")))
    for kind in kinds:
        for gap in sorted(no_limit):
            values = [r[gap] for r in out[kind].values()]
            say("summary %s %s min=%.4g max=%.4g over %d seeds"
                % (kind, gap, min(values), max(values), len(values)))
    return out


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--bfloat16", action="store_true")
    args = ap.parse_args()
    result = read(args.workload, [int(x) for x in args.seeds.split(",") if x],
                  bfloat16=args.bfloat16)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/control.%s.json" % args.workload, "w") as f:
        json.dump(result, f, indent=1)
