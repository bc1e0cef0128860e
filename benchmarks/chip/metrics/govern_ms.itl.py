"""Log and governance (core/driver.py, core/voter.py, core/decider.py,
core/bus.py MemoryBus, core/executor.py up to the handler): per step,
from what set it off (the previous Result, or the Mail that woke an idle
planner) to the start of its handler; summed over the steps whose Result
falls in the window and divided by their number."""
from chipbench import measures


def read(run):
    start = {s["step"]: s["t0"] for s in run.spans.get("handler", ())}
    xs = [(start[s["step"]] - s["trigger_ts"]) * 1e3 for s in run.steps
          if s["ok"] and measures.in_window(run, s["result_ts"])
          and s["trigger_ts"] is not None and s["step"] in start]
    return measures.mean(xs)
