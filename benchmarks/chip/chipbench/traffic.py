"""The one traffic generator: a traffic file's parameters and a seed in,
timed requests out.

Arrivals are an open-loop Poisson process at the file's rate: the gaps
between due times are exponential, so requests come in bursts and lulls
as independent users send them. Prompt and output lengths are lognormal,
clipped to the file's bounds. The schedule (due times and lengths) is
the mix's, drawn from the file's ``schedule_seed``; the run's seed draws
the prompt tokens and the position of each prompt's end inside its last
page (and, elsewhere, the weights). So every seed sends the same work at
the same times, a replayed trace with fresh data. A rate other than the
file's (a knee sweep) scales the same arrivals in time.

Prompt lengths are snapped up to one of the file's page buckets and then
shortened by 0 to ``page_size - 1`` tokens inside the last page: the
engine compiles one prefill per page bucket, and set-up must not grow
with the tail of the length distribution. Output lengths are not
snapped.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np


@dataclass
class Request:
    req_id: str
    at: float              # seconds after the window opens
    prompt: np.ndarray     # int32 token ids
    max_new: int


def _lognormal(rng: np.random.Generator, spec: Dict[str, Any],
               n: int) -> np.ndarray:
    x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    return np.clip(x, spec["min"], spec["max"])


def arrivals(rng: np.random.Generator, rate: float,
             seconds: float) -> np.ndarray:
    """Due times in ``[0, seconds)`` of a Poisson process at ``rate``:
    the arrivals of one at rate 1, divided by ``rate``."""
    at: List[float] = []
    t = rng.exponential()
    while t < seconds * rate:
        at.append(t / rate)
        t += rng.exponential()
    return np.array(at)


def page_of(n_tokens: int, page_size: int) -> int:
    return -(-int(n_tokens) // page_size)


def generate(traffic: Dict[str, Any], seed: int, seconds: float, vocab: int,
             rate: Optional[float] = None) -> List[Request]:
    """Requests due in ``[0, seconds)`` at ``rate`` (default: the file's),
    ordered by due time."""
    rate = rate or traffic["rate_rps"]
    page = traffic["page_size"]
    buckets = sorted(traffic["prompt"]["page_buckets"])
    if buckets[-1] * page < traffic["prompt"]["max"]:
        raise ValueError("the largest page bucket is shorter than the "
                         "longest prompt")
    # one stream each for arrivals, prompt and output lengths: a longer
    # window or another rate keeps the lengths of the requests it shares
    gap_rng, prompt_rng, out_rng = (
        np.random.default_rng(s) for s in
        np.random.SeedSequence(traffic["schedule_seed"]).spawn(3))
    at = arrivals(gap_rng, rate, seconds)
    n = len(at)
    drawn = _lognormal(prompt_rng, traffic["prompt"], n)
    outs = np.rint(_lognormal(out_rng, traffic["output"], n))
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        b = next(b for b in buckets if b * page >= drawn[i])
        plen = b * page - int(rng.integers(0, page))
        reqs.append(Request(
            req_id=f"r{i}", at=float(at[i]),
            prompt=rng.integers(1, vocab, plen, dtype=np.int32),
            max_new=int(outs[i])))
    return reqs


def max_pages_per_seq(traffic: Dict[str, Any]) -> int:
    """The widest block table a request of this mix can hold."""
    return page_of(traffic["prompt"]["max"] + traffic["output"]["max"],
                   traffic["page_size"])


def pool_pages(traffic: Dict[str, Any]) -> int:
    """Every lane can hold the longest request at once, plus the null
    page. The engine reserves a request's prompt and answer pages when it
    admits it, so this is the smallest pool in which admission waits only
    for a lane, never for pages."""
    return traffic["lanes"] * max_pages_per_seq(traffic) + 1


def describe(reqs: List[Request]) -> str:
    plen = np.array([len(r.prompt) for r in reqs])
    outs = np.array([r.max_new for r in reqs])
    return (f"{len(reqs)} requests; prompt tokens median "
            f"{int(np.median(plen))} max {plen.max()}; output tokens median "
            f"{int(np.median(outs))} max {outs.max()}; last due at "
            f"{reqs[-1].at:.3f} s" if reqs else "no requests")
