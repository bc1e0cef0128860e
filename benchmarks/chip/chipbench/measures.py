"""Reductions from a window's log, spans and trace to numbers, shared by
the metric readers in ``metrics/``.

Definitions:
- a request is due in the window when its due time lies in it: every
  generated request is;
- its time to first token runs from its due time to the ``realtime_ts``
  of the Result whose ``admitted`` lists it (prefill yields the first
  token); a request never admitted counts up to the agent's stop;
- its inter-token gaps are the differences between consecutive
  ``serve_step`` Results from its admitting Result to its finishing one
  (or to the last before the stop): one token each. A gap belongs to the
  window when its later Result does;
- the tokens of a step are one per lane it decoded plus one per request
  it prefilled.
"""
from __future__ import annotations

import bisect
from typing import Any, Dict, List, Optional, Tuple

import numpy as np


def percentile(xs: List[float], q: float) -> Optional[float]:
    return float(np.percentile(xs, q)) if xs else None


def mean(xs: List[float]) -> Optional[float]:
    return float(np.mean(xs)) if xs else None


def in_window(run: Any, ts: Optional[float]) -> bool:
    return ts is not None and run.t0 <= ts <= run.t1


def ttft_s(run: Any) -> List[float]:
    out = []
    for q in run.requests:
        i = q["admit_step"]
        end = run.steps[i]["result_ts"] if i is not None else run.t_stop
        out.append(end - q["due"])
    return out


def itl_gaps_s(run: Any) -> List[float]:
    out = []
    last = len(run.steps) - 1
    for q in run.requests:
        a = q["admit_step"]
        if a is None:
            continue
        f = q["finish_step"] if q["finish_step"] is not None else last
        ts = [run.steps[i]["result_ts"] for i in range(a, f + 1)]
        out.extend(b - a_ for a_, b in zip(ts, ts[1:])
                   if b is not None and a_ is not None and in_window(run, b))
    return out


def step_tokens(step: Dict[str, Any]) -> int:
    return step["decoded"] + len(step["admitted"])


def tokens_in_window(run: Any) -> int:
    return sum(step_tokens(s) for s in run.steps
               if s["ok"] and in_window(run, s["result_ts"]))


def spans_in_window(run: Any, name: str) -> List[Dict[str, Any]]:
    return [s for s in run.spans.get(name, ())
            if run.t0 <= s["t0"] and s["t1"] <= run.t1]


def span_ms(run: Any, name: str) -> List[float]:
    return [(s["t1"] - s["t0"]) * 1e3 for s in spans_in_window(run, name)]


def traced(run: Any, name: str) -> List[Tuple[Dict[str, Any], float, float]]:
    """(record, start_ns, end_ns) of each ``name`` span that the trace
    holds inside its window. The trace holds the last calls: its k-th
    ``name`` span from the end is the k-th record from the end."""
    if run.trace is None:
        return []
    w0 = w1 = None
    found = []
    for n, s, d in run.trace["spans"]:
        if n == "window":
            w0, w1 = s, s + d
        elif n == name:
            found.append((s, s + d))
    found.sort()
    recs = run.spans.get(name, [])[-len(found):] if found else []
    return [(r, s, e) for r, (s, e) in zip(recs, found[-len(recs):])
            if w0 is not None and w0 <= s and e <= w1]


def device_time_in(run: Any, pattern: Any,
                   pairs: List[Tuple[Dict[str, Any], float, float]]
                   ) -> List[float]:
    """Seconds of device operations whose name matches ``pattern`` that
    start inside each span of ``pairs``."""
    ev = sorted((s, d) for _, n, s, d in run.trace["device"]
                if pattern.search(n))
    starts = [s for s, _ in ev]
    out = []
    for _, s, e in pairs:
        i, j = bisect.bisect_left(starts, s), bisect.bisect_right(starts, e)
        out.append(sum(d for _, d in ev[i:j]) * 1e-9)
    return out
