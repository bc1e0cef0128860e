"""The served window: the governed serving agent as users get it, driven
by an open-loop generator and timed from the log.

The agent is ``build_continuous_serving_agent`` on a ``MemoryBus`` in
threaded mode, with the admission ``RuleVoter`` (``SERVE_ADMISSION_RULES``)
and the ``first_voter`` decider. Every decode token is a governed
``serve_step``: InfIn, InfOut and Intent, a Vote, a Commit, the handler
(``PagedEngine.admit`` for each admission, then ``PagedEngine.step``) and
a Result. The generator thread appends each request's Mail at its due
time. Latencies run from the due time to the ``realtime_ts`` of Result
entries, the clock the log already keeps.

The spans are the benchmark's own, around calls into the program: the
handler, the engine's ``admit`` and ``step``, each Mail append, and the
window. Each is kept in memory and written as a ``TraceAnnotation`` so
that a device trace can say what the host was doing in each idle gap.
"""
from __future__ import annotations

import gc
import threading
import time
from collections import defaultdict
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional

import jax

from repro.core import entries as E
from repro.core.acl import BusClient
from repro.core.entries import PayloadType
from repro.core.voter import RuleVoter
from repro.serving.server import (SERVE_ADMISSION_RULES,
                                  build_continuous_serving_agent)

from . import traffic as T

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Backend compiles (cache loads included), their seconds, and
    persistent-cache hits and misses, from JAX's monitoring events, by
    phase: ``setup``, then ``window`` while the window is open, then
    ``after``."""

    def __init__(self) -> None:
        self.phase = "setup"
        self.compiles: Dict[str, int] = defaultdict(int)
        self.seconds: Dict[str, float] = defaultdict(float)
        self.events: Dict[str, int] = defaultdict(int)

    def _duration(self, event: str, secs: float, **_: Any) -> None:
        if event == COMPILE_EVENT:
            self.compiles[self.phase] += 1
            self.seconds[self.phase] += secs

    def _event(self, event: str, **_: Any) -> None:
        if event.startswith("/jax/compilation_cache/cache_"):
            self.events[f"{self.phase}.{event.rsplit('/', 1)[1]}"] += 1

    def __enter__(self) -> "CompileCounter":
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc: Any) -> None:
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


class FullCollections:
    """Seconds of each full (generation 2) garbage collection while
    ``open``: the collector holds the interpreter lock, so each is a stall
    of every thread of the served path."""

    def __init__(self) -> None:
        self.open = False
        self.seconds: List[float] = []
        self._start: Optional[float] = None

    def __call__(self, phase: str, info: Dict[str, Any]) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._start = time.perf_counter()
        elif self.open and self._start is not None:
            self.seconds.append(time.perf_counter() - self._start)

    def __enter__(self) -> "FullCollections":
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc: Any) -> None:
        gc.callbacks.remove(self)


class Spans:
    """Host spans by name: each record holds ``t0``/``t1`` (``time.time``,
    the log's clock) and what the caller added."""

    def __init__(self) -> None:
        self.records: Dict[str, List[Dict[str, Any]]] = defaultdict(list)

    @contextmanager
    def span(self, name: str, **info: Any) -> Iterator[Dict[str, Any]]:
        with jax.profiler.TraceAnnotation("bench." + name):
            info["t0"] = time.time()
            try:
                yield info
            finally:
                info["t1"] = time.time()
                self.records[name].append(info)


def instrument(engine: Any, spans: Spans) -> None:
    """Wrap the engine instance's ``admit`` and ``step`` in spans. The
    decode span records each decoded lane's attention length (its context
    and the new token), which the kernel's needed bytes are counted from,
    and the pool's pages in use."""
    admit, step = engine.admit, engine.step

    def timed_admit(req_id, prompt_tokens, max_new_tokens, **kw):
        with spans.span("prefill", req_id=req_id,
                        n_tokens=len(prompt_tokens)):
            return admit(req_id, prompt_tokens, max_new_tokens, **kw)

    def timed_step():
        lens = [engine.pool.seq(r).n_tokens + 1 for r in engine.lanes
                if r is not None and not engine.seqs[r].finished]
        with spans.span("decode", attn_lens=lens,
                        pages=engine.pool.n_pages_in_use):
            return step()

    engine.admit, engine.step = timed_admit, timed_step


def build_agent(arch: Any, engine: Any, traffic: Dict[str, Any],
                spans: Spans) -> Any:
    agent = build_continuous_serving_agent(
        arch, max_batch=traffic["lanes"], num_pages=T.pool_pages(traffic),
        page_size=traffic["page_size"],
        max_new_tokens=traffic["output"]["max"],
        max_pages_per_seq=T.max_pages_per_seq(traffic))
    agent.executor.env.engine = engine
    agent.add_voter(RuleVoter(BusClient(agent.bus, "admission", "voter"),
                              rules=SERVE_ADMISSION_RULES), from_tail=False)
    agent.set_policy("decider", {"mode": "first_voter"})
    handler = agent.executor.handlers["serve_step"]

    def timed_handler(args, env):
        with spans.span("handler", step=args.get("step")):
            return handler(args, env)

    agent.executor.handlers["serve_step"] = timed_handler
    return agent


@dataclass
class Run:
    """What one window left on the log and in the spans."""

    seconds: float
    t0: float                       # window opens (time.time)
    t1: float                       # window closes
    t_stop: float                   # agent stopped
    requests: List[Dict[str, Any]]  # one per request due in the window
    steps: List[Dict[str, Any]]     # serve_step Intents, in log order
    spans: Dict[str, List[Dict[str, Any]]]
    lateness_s: List[float]
    compiles_in_window: int
    failed_results: List[str]
    gc_pauses_s: List[float] = field(default_factory=list)
    setup_s: float = 0.0
    cfg: Dict[str, Any] = field(default_factory=dict)
    traffic: Dict[str, Any] = field(default_factory=dict)
    peaks: Optional[Dict[str, Any]] = None
    trace: Optional[Dict[str, Any]] = None   # xtrace.extract + reduce


def serve_window(agent: Any, reqs: List[T.Request], seconds: float,
                 spans: Spans, counter: CompileCounter, drain_s: float,
                 on_open: Optional[Callable[[], None]] = None) -> Run:
    """Open the window, send every request at its due time, close the
    window after ``seconds``, then keep serving until every request has
    been admitted (at most ``drain_s`` more). Then hold the driver, let
    the steps it proposed settle, stop the agent and read the log."""
    payloads = [dict(req_id=r.req_id, prompt_tokens=r.prompt.tolist(),
                     max_new_tokens=r.max_new) for r in reqs]
    client = agent.external_client("loadgen")
    lateness: List[float] = []
    hold, in_play = threading.Event(), threading.Lock()
    play = agent.driver.play_available

    def held_play() -> int:
        with in_play:
            return 0 if hold.is_set() else play()

    agent.driver.play_available = held_play
    agent.start()
    threads = list(agent._threads)
    if on_open is not None:
        on_open()
    with FullCollections() as collections:
        counter.phase = "window"
        collections.open = True
        mono0, t0 = time.monotonic(), time.time()

        def loadgen() -> None:
            for r, p in zip(reqs, payloads):
                delay = mono0 + r.at - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                with spans.span("loadgen", req_id=r.req_id) as s:
                    client.append(E.mail("request", "loadgen", **p))
                lateness.append(s["t0"] - (t0 + r.at))

        gen = threading.Thread(target=loadgen, name="loadgen")
        with spans.span("window"):
            gen.start()
            time.sleep(max(0.0, mono0 + seconds - time.monotonic()))
        gen.join()
        collections.open = False
        counter.phase = "after"
    t1 = t0 + seconds
    due = {r.req_id for r in reqs}
    settled: set = set()
    cursor = 0
    deadline = time.monotonic() + drain_s
    while time.monotonic() < deadline:
        for e in agent.bus.read(cursor, types=(PayloadType.RESULT,)):
            settled.update((e.body.get("value") or {}).get("admitted", ()))
            cursor = e.position + 1
        settled.update(agent.driver.planner.rejected)
        if due <= settled:
            break
        time.sleep(0.01)
    hold.set()
    with in_play:   # the driver's last round has ended: no Intent follows
        pass
    settle(agent, time.monotonic() + drain_s)
    agent.stop()
    for t in threads:
        t.join()
    t_stop = time.time()
    run = read_log(agent, reqs, seconds, t0, t1, t_stop, spans, lateness,
                   counter.compiles["window"])
    run.gc_pauses_s = collections.seconds
    return run


def settle(agent: Any, deadline: float) -> None:
    """Wait, until ``deadline`` (``time.monotonic``), for a Result or an
    Abort of every ``serve_step`` Intent on the log."""
    while time.monotonic() < deadline:
        pending = set()
        for e in agent.bus.read(0, types=(PayloadType.INTENT,
                                          PayloadType.ABORT,
                                          PayloadType.RESULT)):
            if e.type == PayloadType.INTENT:
                if e.body["kind"] == "serve_step":
                    pending.add(e.body["intent_id"])
            else:
                pending.discard(e.body["intent_id"])
        if not pending:
            return
        time.sleep(0.01)


def read_log(agent: Any, reqs: List[T.Request], seconds: float, t0: float,
             t1: float, t_stop: float, spans: Spans, lateness: List[float],
             compiles: int) -> Run:
    intents: Dict[str, Dict[str, Any]] = {}
    order: List[str] = []
    committed, results, failed = set(), {}, []
    idle_ts: List[float] = []   # the planner had nothing to do
    mail_ts: List[float] = []
    for e in agent.bus.read(0, types=(PayloadType.INTENT, PayloadType.COMMIT,
                                      PayloadType.RESULT, PayloadType.MAIL,
                                      PayloadType.INF_OUT)):
        b = e.body
        if e.type == PayloadType.INTENT and b["kind"] == "serve_step":
            intents[b["intent_id"]] = {"ts": e.realtime_ts, "args": b["args"]}
            order.append(b["intent_id"])
        elif e.type == PayloadType.COMMIT:
            committed.add(b["intent_id"])
        elif e.type == PayloadType.RESULT and not b.get("recovered"):
            results[b["intent_id"]] = (e.realtime_ts, b)
            if not b.get("ok"):
                v = b.get("value") or {}
                failed.append(f"{b['intent_id']}: "
                              f"{v.get('traceback') or v.get('error') or b}")
        elif e.type == PayloadType.INF_OUT and b["plan"].get("done"):
            idle_ts.append(e.realtime_ts)
        elif e.type == PayloadType.MAIL:
            mail_ts.append(e.realtime_ts)
    by_id = {r.req_id: r for r in reqs}
    steps = []
    inflight, prev_result = 0, None
    for iid in order:
        it = intents[iid]
        ts, body = results.get(iid, (None, None))
        v = (body or {}).get("value") or {}
        ok = bool(body and body.get("ok"))
        admitted = list(v.get("admitted", ())) if ok else []
        decoded = inflight + sum(1 for a in admitted
                                 if by_id[a].max_new > 1) if ok else 0
        steps.append({
            "intent_id": iid, "step": it["args"].get("step"),
            "intent_ts": it["ts"], "result_ts": ts,
            "trigger_ts": _trigger(prev_result, it["ts"], idle_ts, mail_ts),
            "ok": ok, "committed": iid in committed,
            "admitted": admitted,
            "finished": {f["req_id"]: f["generated"]
                         for f in v.get("finished", ())} if ok else {},
            "decoded": decoded})
        if ok:
            inflight = int(v.get("n_inflight", inflight))
        prev_result = ts
    requests = []
    for r in reqs:
        requests.append({"req_id": r.req_id, "due": t0 + r.at,
                         "n_prompt": len(r.prompt), "max_new": r.max_new,
                         "admit_step": None, "finish_step": None,
                         "tokens": None,
                         "rejected": r.req_id in agent.driver.planner.rejected})
    idx = {q["req_id"]: q for q in requests}
    for i, s in enumerate(steps):
        for a in s["admitted"]:
            idx[a]["admit_step"] = i
        for rid, toks in s["finished"].items():
            idx[rid]["finish_step"] = i
            idx[rid]["tokens"] = toks
    return Run(seconds=seconds, t0=t0, t1=t1, t_stop=t_stop,
               requests=requests, steps=steps, spans=dict(spans.records),
               lateness_s=lateness, compiles_in_window=compiles,
               failed_results=failed)


def _trigger(prev_result: Optional[float], intent_ts: float,
             idle_ts: List[float], mail_ts: List[float]) -> Optional[float]:
    """What set a step off: the previous step's Result, or, when the
    planner had gone idle after it, the first Mail after it went idle (or
    the going idle itself, when that Mail came during it)."""
    lo = prev_result if prev_result is not None else float("-inf")
    idle = [t for t in idle_ts if lo <= t <= intent_ts]
    if not idle:
        return prev_result
    return next((t for t in mail_ts if idle[-1] <= t <= intent_ts), idle[-1])
