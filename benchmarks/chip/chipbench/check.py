"""The comparison that decides ``correct``.

Of the log: no ``serve_step`` Result failed; every ``serve_step`` Intent
was committed and carries a Result; every request due was admitted and
none was refused; every served token lies in the vocabulary.

Of the tokens: once the window has closed, a sample of the finished
requests, drawn from the seed and holding the longest, goes through the
plain reference once each, prompt and served tokens together. At each
served token the reference's logits give the gap by which that token's
logit lies below the best, in units of the logits' standard deviation
at that position. The engine returns greedy tokens, so a sound engine
leaves only rounding between its token and the reference's best. Three
numbers are read over the sample: the mean gap, the widest gap, and the
share of tokens flipped (in %: those whose logit lies below the
reference's best at all); a cell's limits file says which of them it
compares. With random weights and long contexts the widest gap is a
maximum over a few hundred near ties, set by how far rounding moves the
logits; the mean counts every token that rounding flipped and by how
much, and the flipped share counts them alone.

The reference runs at the precision the configuration states (float32,
dots at its ``dot_precision``). The control puts the reference in the
program's place in bfloat16, the precision below that: at each position
of the same prompts and tokens it reads the gap of the token that
bfloat16 puts first.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def log_problems(run: Any, vocab: int) -> Dict[str, int]:
    """Counts of what the log must not show, each with limit 0."""
    # the driver is held and every step settled before the stop, so no
    # Intent is cut by it
    cut = [i for i, s in enumerate(run.steps)
           if s["result_ts"] is None or not s["committed"]]
    bad_tokens = sum(1 for q in run.requests if q["tokens"] is not None
                     for t in q["tokens"] if not 0 <= t < vocab)
    return {
        "failed_results": len(run.failed_results),
        "steps_without_result": len(cut),
        "requests_not_admitted": sum(1 for q in run.requests
                                     if q["admit_step"] is None),
        "requests_refused": sum(1 for q in run.requests if q["rejected"]),
        "tokens_outside_vocab": bad_tokens,
    }


def sample(run: Any, seed: int, target_tokens: int,
           max_requests: int) -> List[int]:
    """Indices of finished requests to compare: the one with the most
    served tokens, then others in an order drawn from the seed, until
    ``target_tokens`` served tokens or ``max_requests`` requests."""
    done = [i for i, q in enumerate(run.requests) if q["tokens"]]
    if not done:
        return []
    longest = max(done, key=lambda i: (len(run.requests[i]["tokens"]), -i))
    rest = [i for i in done if i != longest]
    rest = [rest[j] for j in
            np.random.default_rng(seed % (1 << 128)).permutation(len(rest))]
    out, n = [longest], len(run.requests[longest]["tokens"])
    for i in rest:
        if n >= target_tokens or len(out) >= max_requests:
            break
        out.append(i)
        n += len(run.requests[i]["tokens"])
    return out


def pad_sizes(traffic: Dict[str, Any]) -> Tuple[int, int]:
    """One (sequence, positions) shape per cell, so the reference
    compiles once: the longest prompt and output, rounded up."""
    t = traffic["prompt"]["max"] + traffic["output"]["max"]
    step = 512 if t > 512 else 16
    return -(-t // step) * step, traffic["output"]["max"]


GAPS = ("mean_logit_gap", "widest_logit_gap", "flipped_pct")


def make_gap_fn(ref_logits, control: bool):
    def gaps(w, tokens, positions, served):
        lg = ref_logits(w, tokens, positions)
        best, sd = lg.max(-1), lg.std(-1)
        got = jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
        out = {"program": (best - got) / sd}
        if control:
            low = ref_logits(w, tokens, positions, dtype=jnp.bfloat16)
            first = jnp.argmax(low, -1)
            out["control"] = (best - jnp.take_along_axis(
                lg, first[:, None], -1)[:, 0]) / sd
        return out
    return jax.jit(gaps)


def compare(reference: Any, weights: Any, cfg: Dict[str, Any],
            traffic: Dict[str, Any], prompts: Sequence[np.ndarray],
            served: Sequence[Sequence[int]], precision: str,
            control: bool = False) -> Dict[str, Any]:
    """Mean and widest gap (in logit standard deviations) and flipped
    share of the served tokens, and with ``control`` those of the
    bfloat16 reference's first tokens, each as
    ``{name: {"program": x, "control": y}}``.
    ``precision`` is the configuration's ``dot_precision``."""
    t_pad, p_pad = pad_sizes(traffic)
    fn = make_gap_fn(partial(reference.logits_at, cfg=cfg,
                             precision=precision), control)
    per_token: Dict[str, List[np.ndarray]] = {}
    for prompt, toks in zip(prompts, served):
        seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
        tokens = np.zeros(t_pad, np.int32)
        tokens[:len(seq)] = seq
        n = len(toks)
        positions = np.zeros(p_pad, np.int32)
        positions[:n] = len(prompt) - 1 + np.arange(n)
        got = np.zeros(p_pad, np.int32)
        got[:n] = np.clip(toks, 0, cfg["vocab"] - 1)
        out = fn(weights, jnp.asarray(tokens), jnp.asarray(positions),
                 jnp.asarray(got))
        for k, v in out.items():
            per_token.setdefault(k, []).append(np.asarray(v)[:n])
    result: Dict[str, Any] = {g: {} for g in GAPS}
    for k, parts in per_token.items():
        g = np.concatenate(parts)
        result["mean_logit_gap"][k] = float(g.mean())
        result["widest_logit_gap"][k] = float(g.max())
        result["flipped_pct"][k] = float((g > 0).mean() * 100)
    for g in GAPS:   # nothing compared reads as no gap
        result[g].setdefault("program", 0.0)
    result["compared_tokens"] = sum(len(t) for t in served)
    result["compared_requests"] = len(served)
    return result
