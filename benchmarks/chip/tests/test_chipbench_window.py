"""The window's end-to-end numbers from a log: one stall in the served
path, three seconds in which every step takes four times as long, moves
the time-to-first-token tail and the inter-token tail."""
import pytest

import chipbench_tiny  # noqa: F401  (paths)
from chipbench import measures
from chipbench.serve import Run
from chipbench.spec import BENCH_DIR, load_module

STEP = 0.05  # s between Results


def synthetic_run(stall_at=None, stall_s=3.0):
    """20 requests due every 0.5 s into a 10 s window, each admitted at
    the first step after it is due and served 8 tokens (two with the
    admitting Result, as prefill and the step's decode give them, then
    one a step), one step every 50 ms; with ``stall_at``, the steps that start in the ``stall_s``
    seconds after it take four times as long."""
    n_steps = 240
    ts = []
    t = 0.0
    for i in range(n_steps):
        slow = stall_at is not None and stall_at <= t < stall_at + stall_s
        t += 4 * STEP if slow else STEP
        ts.append(t)
    steps = [{"intent_id": f"i{i}", "step": i + 1, "intent_ts": x - 0.01,
              "result_ts": x, "trigger_ts": x - STEP, "ok": True,
              "committed": True, "admitted": [], "finished": {},
              "decoded": 0} for i, x in enumerate(ts)]
    requests = []
    for k in range(20):
        due = 0.5 * k + STEP / 2
        a = next(i for i, x in enumerate(ts) if x > due)
        f = a + 6
        steps[a]["admitted"].append(f"r{k}")
        steps[f]["finished"][f"r{k}"] = [1] * 8
        requests.append({"req_id": f"r{k}", "due": due, "n_prompt": 30,
                         "max_new": 8, "admit_step": a, "finish_step": f,
                         "tokens": [1] * 8, "rejected": False})
    inflight = 0
    for s in steps:
        s["decoded"] = inflight + len(s["admitted"])
        inflight += len(s["admitted"]) - len(s["finished"])
    return Run(seconds=10.0, t0=0.0, t1=10.0, t_stop=ts[-1],
               requests=requests, steps=steps, spans={}, lateness_s=[],
               compiles_in_window=0, failed_results=[])


def reader(name):
    return load_module(BENCH_DIR / "metrics" / f"{name}.py").read


def test_steady_window():
    run = synthetic_run()
    assert reader("itl_p95_ms")(run) == pytest.approx(STEP * 1e3)
    assert reader("ttft_p90_ms")(run) <= STEP * 1e3
    # 20 requests x 8 tokens, every step of them inside the window
    assert reader("tokens_per_s")(run) == pytest.approx(160 / 10.0)


@pytest.mark.parametrize("stall_at", [2.0, 6.0])
def test_one_stall_moves_both_tails(stall_at):
    calm = synthetic_run()
    stalled = synthetic_run(stall_at=stall_at)
    for name in ("ttft_p90_ms", "itl_p95_ms"):
        assert reader(name)(stalled) > reader(name)(calm) + 25, name
    assert max(measures.itl_gaps_s(stalled)) == pytest.approx(4 * STEP)


def test_a_request_never_admitted_counts_to_the_stop():
    run = synthetic_run()
    q = run.requests[-1]
    q["admit_step"] = q["finish_step"] = None
    assert max(measures.ttft_s(run)) == pytest.approx(run.t_stop - q["due"])
