"""Bring-up check of the governed paged-serving path on one TPU chip.

    python chip_smoke.py

One process holds the chip. It builds the continuous-batching serving
agent through ``build_continuous_serving_agent`` on a ``MemoryBus`` at
qwen3_4b's published widths (d_model 2560, 32 query / 8 kv heads, d_head
128, d_ff 9728, vocab 151936) with random weights from a seed, adds the
admission ``RuleVoter`` and the ``first_voter`` decider, and sends seeded
requests as Mail. Every decode step is a governed ``serve_step``: InfIn,
InfOut and Intent, then Votes, a Commit, the step on the TPU and a
Result.

Reduction: 8 of qwen3_4b's 36 layers, in the engine's fp32. All 36
layers in fp32 are 16.1 GB of weights, more than the v5e's 16 GB of
HBM; 8 layers and a 1024-page pool take about 6 GB.

It exits nonzero, without the JSON line, unless JAX's first device is a
TPU and all of these hold: every request finished with its full token
budget, none was rejected, no Result on the log failed, every
``serve_step`` Intent was committed and has a Result, the served decode
step contains the Pallas kernel, and the compiled kernel agrees with
``paged_attention_ref`` on a decode batch drawn from the live KV arenas.
The other lines it prints are bring-up facts, not benchmark numbers. Its
last line is ``{"ok": true, "device": {...}}`` with the device as JAX
reports it.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
from functools import partial
from pathlib import Path
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs.base import ArchConfig, get_config  # noqa: E402
from repro.core.acl import BusClient  # noqa: E402
from repro.core.entries import PayloadType  # noqa: E402
from repro.core.introspect import TRACE_TYPES, trace_intents  # noqa: E402
from repro.core.voter import RuleVoter  # noqa: E402
from repro.kernels.paged_attention import (  # noqa: E402
    paged_attention, paged_attention_ref)
from repro.launch.mesh import configure_compile_cache  # noqa: E402
from repro.serving.engine import PagedEngine  # noqa: E402
from repro.serving.server import (  # noqa: E402
    SERVE_ADMISSION_RULES, build_continuous_serving_agent)

SEED = 0
N_LAYERS = 8            # the reduction: see the module docstring
MAX_BATCH = 8
NUM_PAGES = 1024
PAGE_SIZE = 16
MAX_PAGES_PER_SEQ = 64
MAX_NEW_TOKENS = 32
N_REQUESTS = 16
# prompt sizes in pages (about 100, 200, 400 and 800 tokens): each page
# bucket is one prefill compile, so the run keeps to four
PROMPT_PAGES = (7, 13, 25, 50)
# Bound on ||kernel - ref|| / ||ref||. The kernel's fp32 dots run on the
# v5e MXU with bf16 operands (8 bits of mantissa, rounding error 2^-9 ~
# 0.2%); on random pages at these widths the chip measured 0.2-0.3%. A
# wrong page, lane or kv head moves the error to O(1).
KERNEL_TOL = 1e-2


def chip_config() -> ArchConfig:
    return dataclasses.replace(get_config("qwen3_4b"), n_layers=N_LAYERS)


def make_requests(vocab: int, n: int, prompt_pages: Sequence[int],
                  page_size: int, seed: int) -> List[Dict[str, Any]]:
    """Seeded prompts whose lengths fall in the given page buckets."""
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = (prompt_pages[i % len(prompt_pages)] * page_size
                - int(rng.integers(0, page_size)))
        reqs.append({"req_id": f"r{i}",
                     "prompt_tokens": rng.integers(1, vocab, plen).tolist()})
    return reqs


class CompileCounter:
    """Backend compile seconds and persistent-cache hits, from JAX's
    monitoring events, while the context is open."""

    def __init__(self) -> None:
        self.seconds = 0.0
        self.events: Dict[str, int] = {}

    def _duration(self, event: str, secs: float, **_: Any) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_: Any) -> None:
        if event.startswith("/jax/compilation_cache/cache_"):
            key = event.rsplit("/", 1)[1]
            self.events[key] = self.events.get(key, 0) + 1

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc: Any) -> None:
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def check_log(agent, requests: Sequence[Dict[str, Any]],
              max_new_tokens: int, vocab: int) -> List[str]:
    """What the log and the scheduler must show after a clean run."""
    problems = []
    pl = agent.driver.planner
    for r in requests:
        toks = pl.outputs.get(r["req_id"])
        if toks is None or len(toks) != max_new_tokens:
            problems.append(f"{r['req_id']}: {0 if toks is None else len(toks)}"
                            f" of {max_new_tokens} tokens")
        elif not all(0 <= t < vocab for t in toks):
            problems.append(f"{r['req_id']}: token outside the vocabulary")
    if pl.rejected:
        problems.append(f"rejected: {pl.rejected}")
    for e in agent.bus.read(0, types=(PayloadType.RESULT,)):
        if not e.body.get("ok"):
            err = e.body.get("value", {}).get("traceback") or e.body
            problems.append(f"{e.body.get('intent_id')}: failed Result\n{err}")
    steps = [t for t in trace_intents(agent.bus.read(0, types=TRACE_TYPES))
             if t.kind == "serve_step"]
    if not steps:
        problems.append("no serve_step Intent on the log")
    for t in steps:
        if t.decision != "commit" or t.result is None:
            problems.append(f"{t.intent_id}: decision {t.decision}, "
                            f"Result {'present' if t.result else 'missing'}")
    return problems


def decode_step_uses_kernel(engine: PagedEngine) -> bool:
    """Whether the engine's decode step, lowered as served, calls the
    Pallas TPU kernel."""
    b, n = engine.max_batch, engine.max_pages_per_seq
    lane = jnp.zeros(b, jnp.int32)
    text = engine._decode_jit.lower(
        engine.params, engine.pool.k, engine.pool.v, lane, lane,
        jnp.zeros((b, n), jnp.int32), lane, lane, lane).as_text()
    return "tpu_custom_call" in text


def check_kernel(engine: PagedEngine, seed: int, *,
                 interpret: bool = False) -> Tuple[float, float, bool]:
    """The kernel against ``paged_attention_ref`` on one decode-shaped
    batch over the last layer of the live arenas: block tables drawn from
    the pages the run wrote, ragged context lengths and one idle lane.
    Returns (relative error norm, max abs difference, ok): ok when the
    output is finite, the relative error is within KERNEL_TOL and the
    idle lane is exactly zero."""
    cfg, pool = engine.cfg, engine.pool
    rng = np.random.default_rng(seed)
    b, n = engine.max_batch, engine.max_pages_per_seq
    written = np.arange(1, max(pool.pages_in_use_hwm, 1) + 1)
    bt = rng.choice(written, size=(b, n))
    ctx = rng.integers(1, n * pool.page_size + 1, b)
    ctx[-1] = 0
    q = jax.random.normal(jax.random.PRNGKey(seed),
                          (b, cfg.n_heads, cfg.head_dim), jnp.float32)
    args = (q, pool.k[-1], pool.v[-1], jnp.asarray(bt, jnp.int32),
            jnp.asarray(ctx, jnp.int32))
    kw = dict(scale=cfg.attn_logit_scale, softcap=cfg.attn_softcap)
    got = jax.jit(partial(paged_attention, interpret=interpret, **kw))(*args)
    want = jax.jit(partial(paged_attention_ref, **kw))(*args)
    got, want = np.asarray(got), np.asarray(want)
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    ok = bool(np.all(np.isfinite(got)) and rel <= KERNEL_TOL
              and not np.any(got[-1]))
    return rel, float(np.max(np.abs(got - want))), ok


def run(cfg: ArchConfig, *, on_chip: bool, n_requests: int = N_REQUESTS,
        max_new_tokens: int = MAX_NEW_TOKENS, max_batch: int = MAX_BATCH,
        num_pages: int = NUM_PAGES, page_size: int = PAGE_SIZE,
        max_pages_per_seq: int = MAX_PAGES_PER_SEQ,
        prompt_pages: Sequence[int] = PROMPT_PAGES, seed: int = SEED
        ) -> Tuple[List[str], Dict[str, Any]]:
    """Every phase after the device check. ``on_chip`` compiles the
    kernel check for the device and requires the kernel in the decode
    step; off the chip the kernel check runs in interpret mode.
    Returns (problems, facts)."""
    requests = make_requests(cfg.vocab, n_requests, prompt_pages,
                             page_size, seed)
    agent = build_continuous_serving_agent(
        cfg, max_batch=max_batch, num_pages=num_pages, page_size=page_size,
        max_new_tokens=max_new_tokens, max_pages_per_seq=max_pages_per_seq)
    agent.add_voter(RuleVoter(BusClient(agent.bus, "admission", "voter"),
                              rules=SERVE_ADMISSION_RULES), from_tail=False)
    agent.set_policy("decider", {"mode": "first_voter"})
    facts: Dict[str, Any] = {}
    with CompileCounter() as cc:
        t0 = time.perf_counter()
        agent.executor.env.ensure_initialized()
        engine = agent.executor.env.engine
        jax.block_until_ready(engine.params)
        facts["init_s"] = time.perf_counter() - t0
        for r in requests:
            agent.send_mail(f"request {r['req_id']}", req_id=r["req_id"],
                            prompt_tokens=r["prompt_tokens"],
                            max_new_tokens=max_new_tokens)
        t0 = time.perf_counter()
        agent.run_until_idle(max_rounds=10 ** 6)
        facts["serve_wall_s"] = time.perf_counter() - t0
    facts["compile_s"] = cc.seconds
    facts["persistent_cache"] = cc.events
    problems = check_log(agent, requests, max_new_tokens, cfg.vocab)
    facts["requests"] = len(requests)
    facts["decode_steps"] = engine.n_steps
    facts["governed_steps"] = agent.driver.planner.step
    facts["tokens"] = sum(len(t) for t in
                          agent.driver.planner.outputs.values())
    facts["kernel_in_decode_step"] = decode_step_uses_kernel(engine)
    if on_chip and not facts["kernel_in_decode_step"]:
        problems.append("the served decode step does not call the kernel")
    rel, err, ok = check_kernel(engine, seed, interpret=not on_chip)
    facts["kernel_vs_ref_rel_err"] = rel
    facts["kernel_vs_ref_max_abs"] = err
    if not ok:
        problems.append(f"kernel vs paged_attention_ref: relative error "
                        f"{rel} (tolerance {KERNEL_TOL}), max abs {err}")
    return problems, facts


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform}); nothing was run", file=sys.stderr)
        return 1
    cache = configure_compile_cache()
    label = f"[{dev.platform} {dev.device_kind} x{len(jax.devices())}]"
    cfg = chip_config()
    print(f"{label} config qwen3_4b cut to {cfg.n_layers} of 36 layers, "
          f"fp32: d_model {cfg.d_model}, heads {cfg.n_heads}/"
          f"{cfg.n_kv_heads}, d_head {cfg.head_dim}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab}; pool {NUM_PAGES} pages x {PAGE_SIZE} tokens, "
          f"max_batch {MAX_BATCH}; compile cache {cache}", flush=True)
    problems, facts = run(cfg, on_chip=True)
    stats = dev.memory_stats() or {}
    facts["peak_bytes_in_use"] = stats.get("peak_bytes_in_use",
                                           "not reported")
    for k, v in facts.items():
        print(f"{label} {k}: {v}")
    if problems:
        print("chip_smoke FAILED:\n" + "\n".join(problems), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
