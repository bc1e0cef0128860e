"""Reduction of a JAX profiler trace to device busy time, idle gaps and
operation times.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
keeps two lists, both on the profiler's one clock: every operation on
each chip's ``XLA Ops`` line, and the benchmark's own host spans
(``TraceAnnotation`` names that start with ``bench.``). ``reduce`` works
on those lists alone, so the tests check it on a small recorded copy.

Busy time is the union of a chip's operation intervals inside the
``bench.window`` span, averaged over the chips; idle is the rest of the
window. Each idle gap is labelled by the benchmark span that covers its
middle on the host: ``prefill`` (the engine's admit), ``decode`` (the
engine's step), ``loadgen`` (a request appended), and otherwise
``govern``, the log and governance path between two steps.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from pathlib import Path
from typing import Any, Dict, List, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
GAP_LABELS = ("prefill", "decode", "loadgen")  # first match wins
OTHER_LABEL = "govern"


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def extract(path: Path) -> Dict[str, Any]:
    """{"device": [[chip, op name, start_ns, dur_ns], ...],
    "spans": [[span name, start_ns, dur_ns], ...]}"""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    device, spans, chips = [], [], []
    for plane in pd.planes:
        name = plane.name
        if name.startswith(DEVICE_PREFIX) and name[len(DEVICE_PREFIX):].isdigit():
            chip = len(chips)
            chips.append(name)
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device.extend([chip, e.name, e.start_ns, e.duration_ns]
                                  for e in line.events)
        elif name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name[len(SPAN_PREFIX):], e.start_ns,
                              e.duration_ns]
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return {"chips": chips, "device": device, "spans": spans}


def union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def window_of(ex: Dict[str, Any]) -> Tuple[float, float]:
    w = [s for s in ex["spans"] if s[0] == "window"]
    if len(w) != 1:
        raise ValueError(f"expected one bench.window span, found {len(w)}")
    return w[0][1], w[0][1] + w[0][2]


def _label(mid: float, spans: Dict[str, Tuple[List[float], List[float]]]
           ) -> str:
    for name in GAP_LABELS:
        starts, ends = spans.get(name, ([], []))
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and ends[i] >= mid:
            return name
    return OTHER_LABEL


def device_events_in(ex: Dict[str, Any], w0: float, w1: float):
    """(chip, name, start, end) of each operation, clipped to [w0, w1]."""
    for chip, name, s, d in ex["device"]:
        e = s + d
        if e > w0 and s < w1:
            yield chip, name, max(s, w0), min(e, w1)


def short_name(hlo: str) -> str:
    """``%copy.19 = f32[9,2561,8,16,128]{...} copy(...)`` -> ``%copy.19 =
    f32[9,2561,8,16,128] copy``: the op, its result shape and its kind."""
    lhs, _, rhs = hlo.partition(" = ")
    m = re.search(r" ([\w-]+)\(", rhs)
    if m is None:
        return hlo[:120]
    shape = re.sub(r"\{[^}]*\}", "", rhs[:m.start()])
    return f"{lhs} = {shape[:60]} {m.group(1)}"


def self_times(events: List[Tuple[float, float, str]]
               ) -> Dict[str, float]:
    """Self time by short name: an operation's duration less that of the
    operations nested in it (a loop holds the operations of its body)."""
    out: Dict[str, float] = defaultdict(float)
    stack: List[List[Any]] = []  # [end, name, self]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, n, own = stack.pop()
            out[n] += own
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, short_name(name), e - s])
    for end, n, own in stack:
        out[n] += own
    return out


def reduce(ex: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    """Busy and idle seconds of the traced window, the operations that
    took most device time (self time) and the longest idle gaps,
    labelled."""
    w0, w1 = window_of(ex)
    n_chips = max(1, len(ex["chips"]))
    per_chip: Dict[int, List[Tuple[float, float, str]]] = defaultdict(list)
    for chip, name, s, e in device_events_in(ex, w0, w1):
        per_chip[chip].append((s, e, name))
    op_ns: Dict[str, float] = defaultdict(float)
    for events in per_chip.values():
        for n, v in self_times(events).items():
            op_ns[n] += v
    busy_ns = 0.0
    gaps: List[Tuple[float, str]] = []
    by_name: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for name, s, d in ex["spans"]:
        by_name[name].append((s, s + d))
    spans = {}
    for name, iv in by_name.items():
        merged = union(iv)
        spans[name] = ([m[0] for m in merged], [m[1] for m in merged])
    gap_ns: Dict[str, float] = defaultdict(float)
    for chip in range(n_chips):
        busy = union([(s, e) for s, e, _ in per_chip.get(chip, [])])
        busy_ns += sum(e - s for s, e in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                label = _label((a + b) / 2, spans)
                gaps.append((b - a, label))
                gap_ns[label] += (b - a) / n_chips
    window_ns = w1 - w0
    busy_ns /= n_chips
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -g[0])
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "idle_share": 1.0 - busy_ns / window_ns if window_ns > 0 else None,
        "device_ops": [[n, v * 1e-9 / n_chips] for n, v in ops],
        "idle_gaps": [[label, g * 1e-9] for g, label in gaps[:top]],
        "idle_by_label": {k: v * 1e-9 for k, v in sorted(gap_ns.items())},
    }
