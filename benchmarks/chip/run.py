"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout, on a machine with the chips the cell asks
for. The last line of standard output is the result as one JSON object;
the last lines of standard error give each number compared beside its
limit. With ``--trace 0`` the metrics are the cell's end-to-end ones,
with ``--trace 1`` its per-layer ones, read from a profiler trace of the
window. Without a TPU, or with fewer chips than the cell asks for, it
exits 2 and prints no result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    from chipbench import spec
    spec.configure_cache(ROOT)
    from chipbench import harness
    cell = spec.load_cell(args.workload, ROOT)
    try:
        line = harness.run_cell(cell, args.seed, args.seconds,
                                bool(args.trace), t_start=T_START, root=ROOT)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    print(json.dumps(line), flush=True)
    harness.print_checks(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
