"""python3 benchmark/read_limits.py --workload <name> --seeds a,b,c
[--control-seeds a,b,c]: the two readings every limit of `correct` is set
from, in one process on the chip, at the cell's own size. For each seed the
program's gaps against the plain reference (the sound runs); for each control
seed the gaps of the control, the reference computed in float8 in the
program's place and, with --bfloat16, of the reference in bfloat16 (what
the program's own precision should read). No measured window: training's readings need none.
Writes what it read to chiprun_out/limits.<workload>.json as well."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def read(workload, seeds, control_seeds, platform="tpu", root=None,
         say=print, bfloat16=False):
    from benchmark import cells, harness
    cell = cells.Cell(workload, root or cells.ROOT)
    devices, _ = harness.attach(platform, cell.chips)
    no_limit = dict.fromkeys(harness.GAPS, float("inf"))
    runner = harness.Runner(cell, devices)
    out = {"workload": workload, "device": devices[0].device_kind,
           "program": {}, "control_float8": {}, "bfloat16": {}, "raw": {}}
    try:
        for seed in sorted(set(seeds) | set(control_seeds)):
            pool = harness.make_pool(cell, seed)
            rows = {}
            if seed in seeds:
                runner.reset(seed)
                rows["program"] = runner.check_steps(seed, pool)
            if seed in control_seeds:
                lower = ["float8"] + (["bfloat16"] if bfloat16 else [])
                for precision in lower:
                    kind = "control_float8" if precision == "float8" \
                        else precision
                    rows[kind] = harness.reference_numbers(
                        cell, runner, seed, pool, precision,
                        keep_first_gradient=True)
            t0 = time.perf_counter()
            ref = harness.reference_numbers(
                cell, runner, seed, pool, compare_with={
                    k: v["first_gradient"] for k, v in rows.items()})
            t_ref = time.perf_counter() - t0
            out["raw"][str(seed)] = {"reference": ref}
            for kind, numbers in rows.items():
                numbers.pop("first_gradient")
                out["raw"][str(seed)][kind] = numbers
                gaps = {name: (value, note) for name, value, _l, _ok, note
                        in harness.compare(numbers, ref, no_limit, kind)}
                out[kind][str(seed)] = {k: v[0] for k, v in gaps.items()}
                say("%s seed=%d loss_gap=%.3g grad_diff=%.3g (%s) "
                    "grad_norm_gap=%.3g (%s) delta_norm_gap=%.3g (%s) "
                    "reference %.1f s"
                    % (kind, seed, gaps["loss_gap"][0],
                       gaps["grad_diff"][0], gaps["grad_diff"][1],
                       gaps["grad_norm_gap"][0], gaps["grad_norm_gap"][1],
                       gaps["delta_norm_gap"][0],
                       gaps["delta_norm_gap"][1], t_ref))
    finally:
        runner.close()
    for kind in ("program", "control_float8", "bfloat16"):
        for gap in sorted(no_limit):
            values = [r[gap] for r in out[kind].values()]
            if values:
                say("summary %s %s min=%.4g max=%.4g over %d seeds"
                    % (kind, gap, min(values), max(values), len(values)))
    return out


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--bfloat16", action="store_true")
    args = ap.parse_args()

    def ints(s):
        return [int(x) for x in s.split(",") if x]

    result = read(args.workload, ints(args.seeds), ints(args.control_seeds),
                  bfloat16=args.bfloat16)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/limits.%s.json" % args.workload, "w") as f:
        json.dump(result, f, indent=1)
