#!/usr/bin/env python
"""Every dot_general of a step's StableHLO, by operand dtypes and FLOPs.

  python tools/dot_inventory.py step.hlo

`step.hlo` holds the "lowered" text of `Executor.dump_hlo`. Prints the
table, then the rows as one JSON line.
"""
import json
import re
import sys

import numpy as np


def dot_inventory(hlo_text, top_k=20):
    """Classify every dot_general in the fused step's HLO by operand
    dtypes and analytic FLOPs — the r4 bf16 audit (which found the f32
    vocab-decode backward) as one command. Non-bf16 rows at the top of
    this table are the MFU attack surface: on TPU a DEFAULT-precision
    f32 dot runs the MXU at half rate (or worse, f32 passes)."""
    dots = []
    # the executor dumps StableHLO ("lowered" section):
    #   %54 = stablehlo.dot_general %a, %b, contracting_dims = [1] x [0],
    #     precision = [...] : (tensor<512x256xbf16>, tensor<256x256xbf16>)
    #     -> tensor<512x256xbf16>
    line_pat = re.compile(
        r"stablehlo\.dot_general([^:]*)contracting_dims = \[([\d, ]*)\]"
        r"[^:]*:\s*\(tensor<([^>]*)>,\s*tensor<([^>]*)>\)\s*->\s*"
        r"tensor<([^>]*)>", re.DOTALL)
    prec_pat = re.compile(r"precision = \[(\w+)")

    def parse_tensor(spec):
        parts = spec.split("x")
        return [int(p) for p in parts[:-1]], parts[-1]

    for m in line_pat.finditer(hlo_text):
        head, cdims, a_spec, b_spec, out_spec = m.groups()
        a, a_dt = parse_tensor(a_spec)
        b, b_dt = parse_tensor(b_spec)
        out, out_dt = parse_tensor(out_spec)
        pm = prec_pat.search(m.group(0))
        precision = pm.group(1) if pm else "DEFAULT"
        contract = 1
        for i in [int(x) for x in cdims.replace(" ", "").split(",") if x]:
            contract *= a[i] if i < len(a) else 1
        flops = 2.0 * float(np.prod(out or [1])) * contract
        dots.append({"out": "%sx%s" % ("x".join(map(str, out)), out_dt),
                     "lhs": "%sx%s" % ("x".join(map(str, a)), a_dt),
                     "rhs": "%sx%s" % ("x".join(map(str, b)), b_dt),
                     "bf16_operands": a_dt == "bf16" and b_dt == "bf16",
                     "precision": precision,
                     "gflops": round(flops / 1e9, 3)})
    if not dots:
        print("dot inventory: no dot() lines parsed (check HLO format)")
        return dots
    dots.sort(key=lambda d: -d["gflops"])
    total = sum(d["gflops"] for d in dots)
    nonbf = sum(d["gflops"] for d in dots if not d["bf16_operands"])
    print("\ndot_general inventory: %d dots, %.1f GFLOP total, "
          "%.1f GFLOP (%.1f%%) with non-bf16 operands"
          % (len(dots), total, nonbf, 100.0 * nonbf / max(total, 1e-9)))
    for d in dots[:top_k]:
        note = "" if d["bf16_operands"] else "   <-- NOT bf16"
        if d["precision"] != "DEFAULT":
            note += "  [precision=%s]" % d["precision"]
        print("  %8.2f GF  %s  %s x %s%s"
              % (d["gflops"], d["out"], d["lhs"], d["rhs"], note))
    return dots


if __name__ == "__main__":
    print(json.dumps(dot_inventory(open(sys.argv[1]).read())))
