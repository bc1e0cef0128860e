"""LogAct-governed serving: batched generation requests through the
Intent -> Vote -> Commit -> Execute machinery.

Requests arrive as ``Mail`` entries. Two serving disciplines share this
module:

* **Static batching** (``ServePlanner`` / ``serve_batch``): all pending
  mail becomes ONE closed-loop generation intent; requests arriving
  mid-generation wait for the whole batch to finish. Simple, and the
  baseline the serving benchmark measures against.

* **Continuous batching** (``ContinuousServePlanner`` / ``serve_step``):
  the planner is a step-level scheduler over the paged decode engine
  (``serving/engine.py``). Every intent covers one single-token decode
  step plus the admissions joining it, so new requests merge into the
  in-flight batch at the next step instead of the next batch. Each
  admission rides in the intent ``args`` — visible to voters *before*
  any prefill runs — which turns the paper's intent-before-execution hook
  into production admission control: per-tenant denylists/quotas and
  queue-depth bounds are ordinary ``RuleVoter`` rules
  (``SERVE_ADMISSION_RULES``), and a vetoed admission is re-proposed
  solo once and then dropped as rejected.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..core.agent import LogActAgent
from ..core.driver import Planner
from ..core.kernel import register_image
from ..core.voter import VoteDecision
from ..models.model import Model
from ..models.params import split_params
from .engine import PagedEngine


@dataclass
class ServeEnv:
    model: Model
    params: Any = None
    max_new_tokens: int = 16
    prefill_fn: Any = None
    decode_fn: Any = None

    def ensure_initialized(self, seed: int = 0) -> None:
        if self.params is None:
            values, _ = split_params(self.model.init(jax.random.PRNGKey(seed)))
            self.params = values
        if self.prefill_fn is None:
            self.prefill_fn = jax.jit(
                self.model.prefill,
                static_argnames=("kv_chunk", "extra_cache"))
            self.decode_fn = jax.jit(self.model.decode_step)


def h_serve_batch(args: Dict[str, Any], env: ServeEnv) -> Dict[str, Any]:
    env.ensure_initialized()
    prompts = [np.asarray(p, np.int32) for p in args["prompts"]]
    new_tokens = int(args.get("max_new_tokens", env.max_new_tokens))
    plen = max(len(p) for p in prompts)
    bsz = len(prompts)
    # optional fixed batch shape: pad with dummy rows so every batch hits
    # one compiled shape (XLA CPU's bsz-1 decode is pathologically slow;
    # fixed shapes also mirror the paged engine's fixed-lane decode step)
    n_rows = max(bsz, int(args.get("pad_batch") or 0))
    toks = np.zeros((n_rows, plen), np.int32)
    for i, p in enumerate(prompts):
        toks[i, plen - len(p):] = p  # left-pad
    batch = {"tokens": jnp.asarray(toks)}
    cfg = env.model.cfg
    if cfg.family == "audio":  # stubbed modality frontend (DESIGN.md)
        batch["frame_embed"] = jnp.zeros((n_rows, cfg.enc_seq, cfg.d_model),
                                         jnp.float32)
    if cfg.family == "vlm":
        batch["patch_embed"] = jnp.zeros(
            (n_rows, cfg.n_frontend_tokens, cfg.d_model), jnp.float32)
    logits, cache = env.prefill_fn(env.params, batch,
                                   extra_cache=new_tokens)
    out = []
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out.append(np.asarray(tok))
    # position of the first decoded token = total prefilled length
    # (vlm prefixes patch tokens ahead of the text)
    pos0 = plen + (cfg.n_frontend_tokens if cfg.family == "vlm" else 0)
    for t in range(new_tokens - 1):
        logits, cache = env.decode_fn(env.params, cache, tok,
                                      jnp.int32(pos0 + t))
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
        out.append(np.asarray(tok))
    gen = np.concatenate(out, axis=1)[:bsz]  # drop pad rows
    res = {"generated": gen.tolist(), "batch": bsz,
           "prefill_len": plen, "new_tokens": new_tokens}
    if "req_ids" in args:  # per-request attribution (serving benchmark)
        res["req_ids"] = list(args["req_ids"])
    return res


SERVE_HANDLERS = {"serve_batch": h_serve_batch}


class ServePlanner(Planner):
    """Batches all pending request mail into one serve_batch intention."""

    def __init__(self, max_batch: int = 8,
                 pad_batch: Optional[int] = None):
        self.max_batch = max_batch
        self.pad_batch = pad_batch
        self.served: int = 0
        self._req_n = 0

    def propose(self, context: Dict[str, Any]) -> Dict[str, Any]:
        pending: List[Dict[str, Any]] = []
        for m in context.get("mail", []):
            if "prompt_tokens" in m:
                pending.append(m)
        # also pick up requests that arrived while we were executing
        for h in context.get("history", []):
            if h.get("role") == "mail" and "prompt_tokens" in h["body"] \
                    and not h["body"].get("_served"):
                pending.append(h["body"])
        if not pending:
            return {"done": True, "note": "queue empty"}
        batch = pending[: self.max_batch]
        rids = []
        for b in batch:
            b["_served"] = True
            rids.append(b.get("req_id") or f"req-{self._req_n}")
            self._req_n += 1
        self.served += len(batch)
        args: Dict[str, Any] = {"prompts": [b["prompt_tokens"]
                                            for b in batch],
                                "req_ids": rids}
        if self.pad_batch:
            args["pad_batch"] = self.pad_batch
        return {"intent": {"kind": "serve_batch", "args": args},
                "note": f"serving batch of {len(batch)}"}


def build_serving_agent(cfg: ArchConfig, *, bus=None, voters=(),
                        max_batch: int = 8,
                        pad_batch: Optional[int] = None,
                        agent_id: str = "server") -> LogActAgent:
    env = ServeEnv(model=Model(cfg, dtype=jnp.float32))
    return LogActAgent(bus=bus,
                       planner=ServePlanner(max_batch, pad_batch=pad_batch),
                       env=env, handlers=SERVE_HANDLERS,
                       voters=list(voters), agent_id=agent_id)


# ---------------------------------------------------------------------------
# Continuous batching: serve_step scheduler over the paged engine
# ---------------------------------------------------------------------------

@dataclass
class ContinuousServeEnv:
    """Executor environment owning the paged decode engine."""

    cfg: ArchConfig
    max_batch: int = 8
    num_pages: int = 128
    page_size: int = 16
    max_new_tokens: int = 16
    seed: int = 0
    max_pages_per_seq: Optional[int] = None
    engine: Optional[PagedEngine] = None

    def ensure_initialized(self) -> None:
        if self.engine is None:
            self.engine = PagedEngine(
                self.cfg, max_batch=self.max_batch,
                num_pages=self.num_pages, page_size=self.page_size,
                seed=self.seed, max_pages_per_seq=self.max_pages_per_seq)


def h_serve_step(args: Dict[str, Any], env: ContinuousServeEnv
                 ) -> Dict[str, Any]:
    """One scheduler step: admit the proposed requests (prefill into the
    paged pool), then run one decode step for every in-flight lane.
    Admissions that don't fit (no free lane / pool pages) are reported
    ``denied`` — capacity backpressure, distinct from a voter veto."""
    env.ensure_initialized()
    eng = env.engine
    admitted, denied = [], []
    for r in args.get("admit", []):
        ok = eng.admit(r["req_id"], r["prompt_tokens"],
                       int(r.get("max_new_tokens", env.max_new_tokens)),
                       tenant=r.get("tenant", "default"))
        (admitted if ok else denied).append(r["req_id"])
    finished = eng.step()
    return {"step": int(args.get("step", -1)),
            "admitted": admitted, "denied": denied,
            "finished": [{"req_id": s.req_id, "generated": s.tokens,
                          "tenant": s.tenant} for s in finished],
            "n_inflight": eng.n_inflight, "pool": eng.pool.stats()}


SERVE_HANDLERS["serve_step"] = h_serve_step


class ContinuousServePlanner(Planner):
    """Step-level scheduler: one ``serve_step`` intent per decode step.

    Host-side state is rebuilt from the driver's context alone (mail +
    the trailing result/abort of the previous step), so the planner stays
    replay-compatible: a replayed lineage reuses logged InfOuts and never
    consults this object's state out of order.

    Veto handling (voters as admission control): when a step carrying
    admissions is aborted, each rider is re-proposed *solo* so the veto
    attributes to a single request; a solo admission that is aborted
    again is dropped as ``rejected``. Decode of already-admitted
    sequences always resumes on the next proposal (an abort stops the
    step, not the service).
    """

    def __init__(self, max_batch: int = 8, admit_per_step: int = 0,
                 max_new_tokens: int = 16):
        self.max_batch = max_batch
        self.admit_per_step = admit_per_step or max_batch
        self.max_new_tokens = max_new_tokens
        self.queue: List[Dict[str, Any]] = []
        self.outputs: Dict[str, List[int]] = {}   # finished req -> tokens
        self.rejected: List[str] = []             # dropped by voter veto
        self.vetoes: Dict[str, int] = {}
        self.n_inflight = 0
        self.step = 0
        self._awaiting: Optional[List[Dict[str, Any]]] = None  # admits out
        self._req_n = 0
        self._consec_fail = 0

    # -- context ingestion ---------------------------------------------------
    def _ingest_mail(self, m: Dict[str, Any]) -> None:
        if "prompt_tokens" not in m or m.get("_sched"):
            return
        m["_sched"] = True  # driver reuses the dict: flag survives
        rid = m.get("req_id") or f"req-{self._req_n}"
        self._req_n += 1
        self.queue.append({
            "req_id": rid,
            "tenant": m.get("tenant", "default"),
            "prompt_tokens": list(m["prompt_tokens"]),
            "max_new_tokens": int(m.get("max_new_tokens",
                                        self.max_new_tokens))})

    def _resolve_last(self, history: List[Dict[str, Any]]) -> None:
        """Fold the previous step's outcome (the trailing result/abort —
        the driver admits one intent in flight at a time)."""
        if self._awaiting is None and self.n_inflight == 0:
            return
        last = next((h for h in reversed(history)
                     if h.get("role") in ("result", "abort")), None)
        proposed, self._awaiting = self._awaiting or [], None
        by_id = {r["req_id"]: r for r in proposed}
        if last is None:
            self.queue = proposed + self.queue
            return
        if last["role"] == "abort" or not last["body"].get("ok", True):
            # voter veto (or handler failure): re-propose riders solo,
            # drop repeat offenders
            self._consec_fail += 1
            for r in proposed:
                n = self.vetoes[r["req_id"]] = \
                    self.vetoes.get(r["req_id"], 0) + 1
                if n >= 2:
                    self.rejected.append(r["req_id"])
                else:
                    self.queue.insert(0, r)
            return
        self._consec_fail = 0
        v = last["body"].get("value", {})
        for rid in v.get("denied", ()):  # capacity: requeue, retry later
            if rid in by_id:
                self.queue.insert(0, by_id[rid])
        for f in v.get("finished", ()):
            self.outputs[f["req_id"]] = f["generated"]
        self.n_inflight = int(v.get("n_inflight", self.n_inflight))

    # -- the scheduling decision --------------------------------------------
    def propose(self, context: Dict[str, Any]) -> Dict[str, Any]:
        for m in context.get("mail", []):
            self._ingest_mail(m)
        for h in context.get("history", []):
            if h.get("role") == "mail":
                self._ingest_mail(h["body"])
        self._resolve_last(context.get("history", []))
        if not self.queue and self.n_inflight == 0:
            return {"done": True,
                    "note": f"served {len(self.outputs)}, "
                            f"rejected {len(self.rejected)}"}
        if self._consec_fail >= 25:
            # every step is being vetoed / failing (e.g. a policy that
            # rejects all serve_steps): park instead of spinning
            return {"done": True,
                    "note": "stalled: 25 consecutive aborted steps"}
        # any previously-vetoed rider goes solo so a repeat veto
        # attributes to it alone
        cap = min(self.admit_per_step,
                  max(0, self.max_batch - self.n_inflight))
        admit: List[Dict[str, Any]] = []
        for r in list(self.queue):
            if len(admit) >= cap:
                break
            if self.vetoes.get(r["req_id"]) and admit:
                break
            self.queue.remove(r)
            admit.append(r)
            if self.vetoes.get(r["req_id"]):
                break
        self._awaiting = admit
        self.step += 1
        return {"intent": {"kind": "serve_step",
                           "args": {"step": self.step, "admit": admit,
                                    "n_inflight": self.n_inflight}},
                "note": f"step {self.step}: +{len(admit)} admit, "
                        f"{self.n_inflight} in flight"}


# -- admission-control voter rules (paper: intent-before-execution as QoS) --

def rule_serve_tenant_denylist(body, pol) -> Optional[VoteDecision]:
    """Block admissions from denylisted tenants."""
    if body["kind"] != "serve_step":
        return None
    deny = set(pol.get("tenant_denylist", ()) or ())
    for r in body.get("args", {}).get("admit", ()):
        if r.get("tenant", "default") in deny:
            return VoteDecision(False, f"tenant {r.get('tenant')!r} denied "
                                       f"(req {r.get('req_id')})")
    return None


def rule_serve_admission_cap(body, pol) -> Optional[VoteDecision]:
    """Rate-limit admissions per scheduler step."""
    if body["kind"] != "serve_step":
        return None
    cap = pol.get("max_admit_per_step")
    n = len(body.get("args", {}).get("admit", ()))
    if cap is not None and n > int(cap):
        return VoteDecision(False, f"{n} admissions > cap {cap}")
    return None


def rule_serve_inflight_bound(body, pol) -> Optional[VoteDecision]:
    """Bound the declared post-admission batch occupancy."""
    if body["kind"] != "serve_step":
        return None
    bound = pol.get("max_inflight")
    args = body.get("args", {})
    if bound is not None and \
            args.get("n_inflight", 0) + len(args.get("admit", ())) \
            > int(bound):
        return VoteDecision(False, "in-flight bound exceeded")
    return None


def rule_serve_prompt_budget(body, pol) -> Optional[VoteDecision]:
    """Reject admissions whose token budget exceeds the per-request cap."""
    if body["kind"] != "serve_step":
        return None
    cap = pol.get("max_tokens_per_request")
    if cap is None:
        return None
    for r in body.get("args", {}).get("admit", ()):
        tot = len(r.get("prompt_tokens", ())) + \
            int(r.get("max_new_tokens", 0))
        if tot > int(cap):
            return VoteDecision(
                False, f"req {r.get('req_id')}: {tot} tokens > cap {cap}")
    return None


SERVE_ADMISSION_RULES = (rule_serve_tenant_denylist,
                         rule_serve_admission_cap,
                         rule_serve_inflight_bound,
                         rule_serve_prompt_budget)


def build_continuous_serving_agent(cfg: ArchConfig, *, bus=None, voters=(),
                                   max_batch: int = 8, num_pages: int = 128,
                                   page_size: int = 16,
                                   max_new_tokens: int = 16,
                                   max_pages_per_seq: Optional[int] = None,
                                   snapshot_store=None,
                                   agent_id: str = "server") -> LogActAgent:
    env = ContinuousServeEnv(cfg=cfg, max_batch=max_batch,
                             num_pages=num_pages, page_size=page_size,
                             max_new_tokens=max_new_tokens,
                             max_pages_per_seq=max_pages_per_seq)
    planner = ContinuousServePlanner(max_batch=max_batch,
                                     max_new_tokens=max_new_tokens)
    return LogActAgent(bus=bus, planner=planner, env=env,
                       handlers=SERVE_HANDLERS, voters=list(voters),
                       snapshot_store=snapshot_store, agent_id=agent_id)


@register_image("serving-continuous")
def _image_serving_continuous(bus=None, snapshot_store=None,
                              arch: str = "qwen3_4b", smoke_cfg: bool = True,
                              **kw) -> LogActAgent:
    """AgentKernel spawn image: a continuous-batching serving agent on the
    kernel's bus (CPU smoke config by default)."""
    from ..configs.base import get_config, smoke
    cfg = get_config(arch)
    if smoke_cfg:
        cfg = smoke(cfg)
    return build_continuous_serving_agent(
        cfg, bus=bus, snapshot_store=snapshot_store, **kw)
