"""Readings for the limits of ``correct``: the program's mean and widest
logit gaps and the control's, over many seeds in one process.

    python3 benchmarks/chip/control.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...]

Each seed is a whole run of the cell as ``run.py`` makes it (set-up, the
window at the cell's own load, the comparison), and the control reads,
at each position of the same prompts and served tokens, the gap of the
token that the reference in bfloat16 puts first. One JSON line per seed,
then a summary: the largest program reading and the smallest control
reading, which ``limits/<cell>.json`` is set between. The benchmark's
own runs never run the control.
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


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    from chipbench import spec
    spec.configure_cache(ROOT)
    from chipbench import harness
    cell = spec.load_cell(args.workload, ROOT)
    rows = []
    for seed in args.seeds:
        t0 = time.time() if rows else T_START
        try:
            line = harness.run_cell(cell, seed, args.seconds, False,
                                    t_start=t0, root=ROOT, control=True)
        except harness.NoChip as e:
            print(f"control.py: {e}", file=sys.stderr)
            return 2
        row = {"seed": seed, "program": line["program"],
               "control": line["control"],
               "control_correct": line["control_correct"],
               "tokens": line["checks"]["compared_tokens"]["value"],
               "correct": line["correct"], "metrics": line["metrics"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "workload": args.workload, "seeds": len(rows),
        "program_max": {g: max(r["program"][g] for r in rows)
                        for g in rows[0]["program"]},
        "control_min": {g: min(r["control"][g] for r in rows)
                        for g in rows[0]["control"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
