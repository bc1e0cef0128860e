"""The trace reduction: busy union, idle share, kernel time by name and
the labels on idle gaps, on a hand-made trace with known answers and on
a small slice recorded from a traced run of ``qwen3_4b.chat`` on one
v5e (``data/trace_slice.json``)."""
import json
import re
from pathlib import Path

import pytest

import chipbench_tiny  # noqa: F401  (paths)
from chipbench import measures, xtrace
from chipbench.serve import Run
from chipbench.spec import BENCH_DIR, load_module

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns


def hand_made():
    """Window 0-100 ms. Device: a loop 10-40 ms holding two ops (12-20,
    25-35), a kernel 50-60 and 60-70 ms, one op running over the
    window's end. Host: decode span 45-75 ms, prefill 5-42 ms, loadgen
    80-82 ms."""
    return {
        "chips": ["/device:TPU:0"],
        "device": [[0, "%while.1 = (f32[4]{0}) while(%x)", 10 * MS, 30 * MS],
                   [0, "%fusion.1 = f32[4]{0:T(8)} fusion(%a)", 12 * MS,
                    8 * MS],
                   [0, "%fusion.2 = f32[4]{0:T(8)} fusion(%b)", 25 * MS,
                    10 * MS],
                   [0, "paged_attention", 50 * MS, 10 * MS],
                   [0, "paged_attention", 60 * MS, 10 * MS],
                   [0, "copy.3", 95 * MS, 10 * MS]],
        "spans": [["window", 0, 100 * MS],
                  ["decode", 45 * MS, 30 * MS],
                  ["prefill", 5 * MS, 37 * MS],
                  ["loadgen", 80 * MS, 2 * MS]]}


def test_busy_is_the_union_clipped_to_the_window():
    r = xtrace.reduce(hand_made())
    # 10-40, 50-70, 95-100 ms
    assert r["busy_s"] == pytest.approx(0.055)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["idle_share"] == pytest.approx(0.45)


def test_idle_gaps_are_labelled_by_the_span_over_them():
    r = xtrace.reduce(hand_made())
    # gaps: 0-10 (prefill covers 5 ms mid), 40-50 (mid 45: decode),
    # 70-95 (mid 82.5: no span -> govern)
    assert sorted(r["idle_gaps"], key=lambda g: -g[1]) == [
        ["govern", pytest.approx(0.025)], ["prefill", pytest.approx(0.01)],
        ["decode", pytest.approx(0.01)]]
    assert r["idle_by_label"] == {"decode": pytest.approx(0.01),
                                  "govern": pytest.approx(0.025),
                                  "prefill": pytest.approx(0.01)}


def test_device_ops_rank_by_self_time():
    r = xtrace.reduce(hand_made())
    # the loop's own time excludes the two ops inside it
    assert r["device_ops"] == [
        ["paged_attention", pytest.approx(0.02)],
        ["%while.1 = (f32[4]) while", pytest.approx(0.012)],
        ["%fusion.2 = f32[4] fusion", pytest.approx(0.01)],
        ["%fusion.1 = f32[4] fusion", pytest.approx(0.008)],
        ["copy.3", pytest.approx(0.005)]]


def test_kernel_time_by_name_inside_matched_spans():
    ex = hand_made()
    run = Run(seconds=0.1, t0=0, t1=0.1, t_stop=0.1, requests=[], steps=[],
              spans={"decode": [{"t0": 0.045, "t1": 0.075,
                                 "attn_lens": [5]}]},
              lateness_s=[], compiles_in_window=0, failed_results=[],
              trace=ex)
    pairs = measures.traced(run, "decode")
    assert [(p[1], p[2]) for p in pairs] == [(45 * MS, 75 * MS)]
    got = measures.device_time_in(run, re.compile("paged"), pairs)
    assert got == [pytest.approx(0.02)]


def sweep_union_ns(intervals):
    """Busy time by a sweep over start/end events: an independent check
    of ``xtrace.union``."""
    events = sorted([(s, 1) for s, _ in intervals]
                    + [(e, -1) for _, e in intervals])
    busy, depth, last = 0, 0, None
    for t, d in events:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_recorded_slice_from_the_chip():
    ex = json.loads((DATA / "trace_slice.json").read_text())
    r = xtrace.reduce(ex)
    w0, w1 = xtrace.window_of(ex)
    iv = [(s, e) for _, _, s, e in xtrace.device_events_in(ex, w0, w1)]
    assert r["busy_s"] == pytest.approx(sweep_union_ns(iv) * 1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["idle_share"] == pytest.approx(1 - r["busy_s"] / r["window_s"])
    assert sum(r["idle_by_label"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert set(r["idle_by_label"]) <= {"prefill", "decode", "loadgen",
                                       "govern"}
    assert r["idle_by_label"].get("decode", 0) > 0
    kernel = load_module(BENCH_DIR / "metrics"
                         / "paged_attention_roofline.py").KERNEL
    found = [d for _, n, _, d in ex["device"] if kernel.search(n)]
    # one kernel call per layer of each decode step: 9 layers, 2 ms each
    assert len(found) >= 9
    assert all(1e6 < d < 1e7 for d in found)
