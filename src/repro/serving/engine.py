"""Continuous-batching decode engine over the paged KV pool.

``PagedEngine`` owns the model params, a ``KVPool`` and two jitted device
functions:

* **prefill** — one sequence at a time, right-padded to a page-multiple
  bucket (so a handful of shapes cover all prompt lengths). Pad positions
  are pushed to a huge value so the causal mask (``pos_k <= pos_q``)
  hides pad keys from real queries without NaN-producing fully-masked
  rows. Returns the per-layer post-RoPE K/V (scattered into the pool's
  pages) and the first generated token.

* **decode step** — ONE token for EVERY in-flight sequence at once, fixed
  ``(max_batch, max_pages_per_seq)`` shapes. Each lane embeds its last
  token at its own position, writes the new K/V into its pool slot
  (inactive lanes write to the pool's null page), and attends over its
  block table with paged attention. New sequences are admitted into free
  lanes *between* steps — continuous batching — so a request never waits
  for the whole batch's generation to finish, only for the current
  single-token step.

The paged attention runs as the compiled Pallas kernel
(``kernels/paged_attention.paged_attention``) on TPU and as the pure-jnp
gather oracle (``paged_attention_ref``) on every other backend, since
interpret-mode Pallas is orders of magnitude slower than XLA on CPU.

Scope: the dense decoder family without sliding windows or frontend
tokens (the serving configs in this repo; asserted in ``__init__``).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..kernels.paged_attention import paged_attention, paged_attention_ref
from ..models.layers import attn_out, attn_project_qkv, mlp_block, rmsnorm
from ..models.model import Model
from ..models.params import split_params
from .kv_pool import KVPool

PAD_POS = 1 << 28  # pad-token position: causally invisible to real queries


def _paged_attention(*args, **kw):
    """The compiled kernel on TPU, the jnp gather elsewhere. Chosen at
    trace time from the backend; a kernel failure is not caught."""
    fn = (paged_attention if jax.default_backend() == "tpu"
          else paged_attention_ref)
    return fn(*args, **kw)


@dataclass
class Sequence:
    """Host-side state of one in-flight request."""

    req_id: str
    prompt_len: int
    max_new_tokens: int
    tenant: str = "default"
    lane: int = -1
    tokens: List[int] = field(default_factory=list)  # generated so far

    @property
    def finished(self) -> bool:
        return len(self.tokens) >= self.max_new_tokens


class PagedEngine:
    def __init__(self, cfg: ArchConfig, *, max_batch: int = 8,
                 num_pages: int = 128, page_size: int = 16,
                 params: Any = None, seed: int = 0,
                 max_pages_per_seq: Optional[int] = None):
        assert cfg.family == "dense", "paged serving: dense decoders only"
        assert cfg.window is None and not cfg.local_global_pattern, \
            "paged serving does not support sliding-window attention"
        self.cfg = cfg
        self.model = Model(cfg, dtype=jnp.float32)
        if params is None:
            params, _ = split_params(self.model.init(jax.random.PRNGKey(seed)))
        self.params = params
        self.max_batch = max_batch
        self.pool = KVPool(cfg.n_layers, cfg.n_kv_heads, cfg.head_dim,
                           num_pages=num_pages, page_size=page_size)
        # widest block table any sequence may hold — the decode step's
        # static gather width (and so its cost): bound it to the actual
        # per-request budget instead of the whole pool when known
        self.max_pages_per_seq = min(num_pages - 1,
                                     max_pages_per_seq or (num_pages - 1))
        self.seqs: Dict[str, Sequence] = {}      # in-flight, keyed by req_id
        self.lanes: List[Optional[str]] = [None] * max_batch
        self.n_steps = 0
        self._prefill_jit = jax.jit(self._prefill,
                                    static_argnames=("s_pad",))
        self._decode_jit = jax.jit(self._decode_step, donate_argnums=(1, 2))

    # -- device functions ----------------------------------------------------
    def _attend(self, q, k_pages, v_pages, block_tables, context_lens):
        cfg = self.cfg
        return _paged_attention(q, k_pages, v_pages, block_tables,
                                context_lens, scale=cfg.attn_logit_scale,
                                softcap=cfg.attn_softcap)

    def _prefill(self, params, tokens, true_len, *, s_pad: int):
        """tokens (1, s_pad) right-padded; true_len scalar int32.
        Returns (k (L, s_pad, Kv, Dh), v, first_token scalar int32)."""
        cfg = self.cfg
        model = self.model
        # pad keys get position PAD_POS: masked from real queries by the
        # causal rule pos_k <= pos_q; pad *queries* still see real keys so
        # no row is fully masked (softmax stays NaN-free), and their
        # outputs are simply never read.
        positions = jnp.where(jnp.arange(s_pad) < true_len,
                              jnp.arange(s_pad), PAD_POS)[None].astype(
                                  jnp.int32)
        x = model._embed(params, tokens)

        def body(x, lp):
            from ..models.layers import attention
            h = rmsnorm(x, lp["ln1"], cfg.rmsnorm_eps)
            q, k, v = attn_project_qkv(h, lp["attn"], cfg, positions)
            o = attention(q, k, v, pos_q=positions, pos_k=positions,
                          causal=True, window=None, softcap=cfg.attn_softcap,
                          scale=cfg.attn_logit_scale)
            x = x + attn_out(o, lp["attn"])
            h = rmsnorm(x, lp["ln2"], cfg.rmsnorm_eps)
            x = x + mlp_block(h, lp["mlp"], cfg)
            return x, (k[0], v[0])

        x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
        x = rmsnorm(x, params["final_norm"], cfg.rmsnorm_eps)
        last = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)
        logits = model._logits(params, last)            # (1, 1, V)
        tok = jnp.argmax(logits[0, 0]).astype(jnp.int32)
        return ks, vs, tok

    def _decode_step(self, params, k_arena, v_arena, tokens, positions,
                     block_tables, slot_pages, slot_offs, attn_lens):
        """One token for every lane.

        tokens/positions/slot_pages/slot_offs/attn_lens: (B,) int32;
        block_tables (B, max_pages). Inactive lanes carry attn_len 0 and
        slots on the null page. Returns (next_tokens (B,), k_arena,
        v_arena)."""
        cfg = self.cfg
        model = self.model
        x = model._embed(params, tokens[:, None], pos0=positions)
        pos2d = positions[:, None]

        def body(carry, lp):
            x, ka, va, li = carry
            h = rmsnorm(x, lp["ln1"], cfg.rmsnorm_eps)
            q, k, v = attn_project_qkv(h, lp["attn"], cfg, pos2d)
            # write each lane's new K/V into its page slot (batched
            # scatter; inactive lanes all hit the null page, whose
            # contents are never read)
            ka = ka.at[li, slot_pages, :, slot_offs].set(k[:, 0])
            va = va.at[li, slot_pages, :, slot_offs].set(v[:, 0])
            o = self._attend(q[:, 0], ka[li], va[li],
                             block_tables, attn_lens)
            x = x + attn_out(o[:, None], lp["attn"])
            h = rmsnorm(x, lp["ln2"], cfg.rmsnorm_eps)
            x = x + mlp_block(h, lp["mlp"], cfg)
            return (x, ka, va, li + 1), None

        carry = (x, k_arena, v_arena, jnp.int32(0))
        (x, k_arena, v_arena, _), _ = jax.lax.scan(
            body, carry, params["layers"])
        x = rmsnorm(x, params["final_norm"], cfg.rmsnorm_eps)
        logits = model._logits(params, x)               # (B, 1, V)
        nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        return nxt, k_arena, v_arena

    # -- admission -----------------------------------------------------------
    @property
    def n_inflight(self) -> int:
        return len(self.seqs)

    @property
    def n_free_lanes(self) -> int:
        return self.lanes.count(None)

    def can_admit(self, prompt_len: int, max_new_tokens: int) -> bool:
        total = prompt_len + max_new_tokens
        return (self.n_free_lanes > 0
                and self.pool.pages_needed(total) <= self.max_pages_per_seq
                and self.pool.can_admit(total))

    def admit(self, req_id: str, prompt_tokens, max_new_tokens: int,
              tenant: str = "default") -> bool:
        """Prefill + join the in-flight batch. False = no capacity (the
        caller reports it denied; the scheduler requeues)."""
        prompt = np.asarray(prompt_tokens, np.int32)
        plen = len(prompt)
        if req_id in self.seqs or not self.can_admit(plen, max_new_tokens):
            return False
        lane = self.lanes.index(None)
        self.pool.allocate(req_id, plen + max_new_tokens)
        # bucket the pad length to page multiples: few distinct jit shapes
        s_pad = max(self.pool.page_size,
                    self.pool.pages_needed(plen) * self.pool.page_size)
        toks = np.zeros((1, s_pad), np.int32)
        toks[0, :plen] = prompt
        ks, vs, tok = self._prefill_jit(self.params, jnp.asarray(toks),
                                        jnp.int32(plen), s_pad=s_pad)
        self.pool.write_prefill(req_id, ks, vs, plen)
        seq = Sequence(req_id=req_id, prompt_len=plen,
                       max_new_tokens=max_new_tokens, tenant=tenant,
                       lane=lane, tokens=[int(tok)])
        self.seqs[req_id] = seq
        self.lanes[lane] = req_id
        return True

    def _retire(self, req_id: str) -> Sequence:
        seq = self.seqs.pop(req_id)
        self.lanes[seq.lane] = None
        self.pool.free(req_id)
        return seq

    # -- the continuous-batching step ---------------------------------------
    def step(self) -> List[Sequence]:
        """One decode step across all lanes; returns sequences finished by
        this step (already retired from their lanes/pool pages)."""
        # sequences admitted with max_new_tokens == 1 finish at prefill
        done = [r for r, s in self.seqs.items() if s.finished]
        active = [r for r in self.lanes if r is not None
                  and not self.seqs[r].finished]
        if active:
            self.n_steps += 1
            ids = list(self.lanes)  # lane-ordered, None for free lanes
            tokens = np.zeros(self.max_batch, np.int32)
            for i, r in enumerate(ids):
                if r is not None and not self.seqs[r].finished:
                    tokens[i] = self.seqs[r].tokens[-1]
                elif r is not None:
                    ids[i] = None  # finished at prefill: don't decode
            ctx = self.pool.context_lens(ids)
            amask = np.asarray([r is not None for r in ids])
            sp, so = self.pool.slots(ids)
            bt = self.pool.block_table(ids, self.max_pages_per_seq)
            nxt, ka, va = self._decode_jit(
                self.params, self.pool.k, self.pool.v,
                jnp.asarray(tokens), jnp.asarray(ctx), jnp.asarray(bt),
                jnp.asarray(sp), jnp.asarray(so),
                jnp.asarray(ctx + amask, np.int32))
            self.pool.swap_arenas(ka, va)
            nxt = np.asarray(nxt)
            for i, r in enumerate(ids):
                if r is None:
                    continue
                self.pool.advance(r)
                self.seqs[r].tokens.append(int(nxt[i]))
                if self.seqs[r].finished:
                    done.append(r)
        return [self._retire(r) for r in done]

    def stats(self) -> Dict[str, Any]:
        return {"n_inflight": self.n_inflight, "n_steps": self.n_steps,
                **self.pool.stats()}
