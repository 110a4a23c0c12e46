"""python3 benchmark/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>: one run of one cell on the TPU this process finds. The last
line of standard output is the result object. There is no fallback: without
a TPU, or with fewer chips than the cell asks for, it prints no result and
exits non-zero."""
import time
T_START = time.perf_counter()

import os      # noqa: E402
import sys     # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

if __name__ == "__main__":
    from benchmark import harness
    try:
        sys.exit(harness.main(sys.argv[1:], "tpu", T_START))
    except harness.Refused as e:
        print("refused: %s" % e, file=sys.stderr)
        sys.exit(4)
