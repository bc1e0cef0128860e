"""The knee of a cell: the highest offered rate at which the backlog does
not grow over the window. Run once when a cell is defined; the cell's
traffic file then fixes its rate at about four fifths of the knee.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates <req/s> [<req/s> ...]

One set-up, then one window per rate over the same engine, each a fresh
agent on a fresh log. Per rate one JSON line: requests due and admitted
by the window's close, the time to first token of the first and last
quarters of the requests (a growing backlog makes the last quarter
wait longer), the tails, and tokens per second.
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
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    args = p.parse_args(argv)
    from chipbench import spec
    spec.configure_cache(ROOT)
    from chipbench import harness, measures, serve
    from chipbench import traffic as T
    cell = spec.load_cell(args.workload, ROOT)
    try:
        bench = harness.Bench(cell, args.seed)
    except harness.NoChip as e:
        print(f"sweep.py: {e}", file=sys.stderr)
        return 2
    print(f"set-up {time.time() - T_START} s", flush=True)
    for rate in args.rates:
        reqs = T.generate(cell.traffic, args.seed, args.seconds,
                          cell.config["vocab"], rate=rate)
        with serve.CompileCounter() as counter:
            counter.phase = "before"
            run = bench.serve(reqs, args.seconds, counter)
        for rid in list(bench.engine.seqs):  # what the stop left in lanes
            bench.engine._retire(rid)
        ttft = measures.ttft_s(run)
        q = max(1, len(ttft) // 4)
        admitted_by_close = sum(
            1 for r in run.requests if r["admit_step"] is not None
            and run.steps[r["admit_step"]]["result_ts"] <= run.t1)
        print(json.dumps({
            "rate": rate, "due": len(run.requests),
            "admitted_by_close": admitted_by_close,
            "ttft_first_quarter_ms": measures.mean(ttft[:q]) * 1e3,
            "ttft_last_quarter_ms": measures.mean(ttft[-q:]) * 1e3,
            "ttft_p50_ms": measures.percentile(ttft, 50) * 1e3,
            "ttft_p90_ms": measures.percentile(ttft, 90) * 1e3,
            "itl_p50_ms": measures.percentile(measures.itl_gaps_s(run), 50)
            * 1e3,
            "itl_p95_ms": measures.percentile(measures.itl_gaps_s(run), 95)
            * 1e3,
            "tokens_per_s": measures.tokens_in_window(run) / run.seconds,
            "prefill_ms": measures.mean(measures.span_ms(run, "prefill")),
            "decode_ms": measures.mean(measures.span_ms(run, "decode")),
            "compiles_in_window": run.compiles_in_window}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
