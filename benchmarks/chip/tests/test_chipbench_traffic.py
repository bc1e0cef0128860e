"""The traffic generator: the same requests for a seed, the same work for
every seed, within the clips and page buckets, at the offered rate."""
import json

import numpy as np
import pytest

import chipbench_tiny  # noqa: F401  (paths)
from chipbench import traffic as T
from chipbench.spec import BENCH_DIR

MIXES = sorted(p.stem for p in (BENCH_DIR / "traffic").glob("*.json"))


def load(mix):
    return json.loads((BENCH_DIR / "traffic" / f"{mix}.json").read_text())


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    tr = load(mix)
    a = T.generate(tr, 2 ** 31 + 11, 30, 1000)
    b = T.generate(tr, 2 ** 31 + 11, 30, 1000)
    assert [(r.at, r.max_new) for r in a] == [(r.at, r.max_new) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = T.generate(tr, 2 ** 31 + 12, 30, 1000)
    assert not all(np.array_equal(x.prompt[:8], y.prompt[:8])
                   for x, y in zip(a, c))


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_replays_the_schedule(mix):
    """Every seed sends the same prompt buckets and answer lengths at the
    same times: the spread between seeds is the system's. Another
    schedule seed is another schedule."""
    tr = load(mix)
    page = tr["page_size"]

    def schedule(rs):
        return [(r.at, T.page_of(len(r.prompt), page), r.max_new)
                for r in rs]

    runs = [schedule(T.generate(tr, s, 30, 1000))
            for s in (1, 2, 3 * 10 ** 9)]
    assert runs[0] == runs[1] == runs[2]
    other = schedule(T.generate(dict(tr, schedule_seed=1), 1, 30, 1000))
    assert other != runs[0]


def test_arrivals_are_poisson():
    """Exponential gaps: their spread equals their mean (bursts and lulls,
    not an even beat), and counts in disjoint windows are independent."""
    rng = np.random.default_rng(3)
    at = T.arrivals(rng, 2.0, 20000.0)
    gaps = np.diff(at)
    assert abs(gaps.mean() * 2.0 - 1) < 0.03
    assert abs(gaps.std() / gaps.mean() - 1) < 0.05
    counts = np.histogram(at, bins=np.arange(0, 20001, 5.0))[0]
    assert abs(counts.var() / counts.mean() - 1) < 0.1


@pytest.mark.parametrize("mix", MIXES)
def test_within_clips_and_buckets(mix):
    tr = load(mix)
    page, pr, out = tr["page_size"], tr["prompt"], tr["output"]
    reqs = T.generate(tr, 5, 60, 1000)
    for r in reqs:
        assert T.page_of(len(r.prompt), page) in pr["page_buckets"]
        assert pr["min"] - page < len(r.prompt) <= pr["max"]
        assert out["min"] <= r.max_new <= out["max"]
        assert r.prompt.min() >= 1 and r.prompt.max() < 1000
        assert (len(r.prompt) + r.max_new
                <= T.max_pages_per_seq(tr) * page)
    assert len(pr["page_buckets"]) <= 6


@pytest.mark.parametrize("mix", MIXES)
@pytest.mark.parametrize("seconds", [20, 40, 51])
def test_at_the_offered_rate(mix, seconds):
    tr = load(mix)
    reqs = T.generate(tr, 9, seconds, 1000)
    at = [r.at for r in reqs]
    assert at == sorted(at) and at[0] >= 0.0 and at[-1] < seconds
    # over many schedules, the mean count is the offered rate's
    counts = [len(T.generate(dict(tr, schedule_seed=k), 9, seconds, 10))
              for k in range(300)]
    want = tr["rate_rps"] * seconds
    assert abs(np.mean(counts) / want - 1) < 4 / np.sqrt(300 * want)


def test_rate_override_scales_the_same_arrivals():
    """A knee sweep's rates send the mix's arrivals faster or slower,
    with the same lengths."""
    tr = load(MIXES[0])
    slow = T.generate(tr, 9, 20, 1000, rate=1.0)
    fast = T.generate(tr, 9, 20, 1000, rate=3.5)
    assert len(fast) > len(slow)
    for a, b in zip(slow, fast):
        assert a.at == pytest.approx(b.at * 3.5)
        assert a.max_new == b.max_new and len(a.prompt) == len(b.prompt)
