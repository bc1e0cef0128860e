"""Paged KV cache pool: one preallocated arena shared by all sequences.

Instead of each batch materializing a dense ``(bsz, plen+new)`` cache from
``model.init_cache``, the serving engine owns two device arenas

    k, v : (n_layers, num_pages, n_kv_heads, page_size, head_dim)

(kv-head-major, so each page of one kv head is a whole
``(page_size, head_dim)`` tile for the paged-attention kernel) and a
host-side **free-list allocator**: each sequence holds an ordered
list of physical page ids (its *block table*); logical token ``t`` lives
at ``(pages[t // page_size], t % page_size)``. Admission reserves
``ceil((prompt + max_new) / page_size)`` pages up front (so an admitted
sequence can never hit mid-decode OOM — admission control is the only
backpressure point, which is exactly where the LogAct voters sit);
retirement returns the pages to the free list for reuse.

Physical page 0 is reserved as the **null page**: it is never allocated,
inactive batch lanes in the fixed-shape decode step direct their K/V
writes at it, and unused block-table slots point at it (the paged
attention kernel's gather must always resolve to a valid page; masked-out
positions are simply never read).

The arenas are jax arrays updated functionally: the engine's jitted step
returns new arenas and the pool re-binds them (``swap_arenas``). All
allocator bookkeeping is plain host Python — it runs once per admission /
retirement, never per token.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class KVPoolError(RuntimeError):
    """Allocator misuse (double free, unknown sequence, over-reservation)
    or pool exhaustion."""


@dataclass
class SeqBlocks:
    """Per-sequence block table + write cursor."""

    pages: List[int]
    n_tokens: int = 0       # tokens written so far
    reserved: int = 0       # token capacity (len(pages) * page_size floor)

    def slot(self, page_size: int) -> Tuple[int, int]:
        """(physical page, in-page offset) of the *next* token to write."""
        return (self.pages[self.n_tokens // page_size],
                self.n_tokens % page_size)


class KVPool:
    NULL_PAGE = 0

    def __init__(self, n_layers: int, n_kv_heads: int, head_dim: int, *,
                 num_pages: int, page_size: int,
                 dtype=jnp.float32) -> None:
        assert num_pages >= 2, "need at least the null page + one real page"
        self.n_layers = n_layers
        self.n_kv_heads = n_kv_heads
        self.head_dim = head_dim
        self.num_pages = num_pages
        self.page_size = page_size
        shape = (n_layers, num_pages, n_kv_heads, page_size, head_dim)
        self.k = jnp.zeros(shape, dtype)
        self.v = jnp.zeros(shape, dtype)
        # LIFO free list (page 0 = null page, never handed out). LIFO makes
        # reuse-after-retirement visible in tests: freed pages come back
        # first.
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        self._seqs: Dict[str, SeqBlocks] = {}
        self.pages_in_use_hwm = 0  # high-water mark (telemetry)

    # -- allocator -----------------------------------------------------------
    def pages_needed(self, n_tokens: int) -> int:
        return -(-int(n_tokens) // self.page_size)

    @property
    def n_free_pages(self) -> int:
        return len(self._free)

    @property
    def n_pages_in_use(self) -> int:
        return (self.num_pages - 1) - len(self._free)

    def can_admit(self, n_tokens: int) -> bool:
        return self.pages_needed(n_tokens) <= len(self._free)

    def allocate(self, seq_id: str, n_tokens: int) -> List[int]:
        """Reserve pages for ``n_tokens`` total capacity. Raises
        ``KVPoolError`` if the sequence already holds pages or the pool
        can't satisfy the reservation (callers check ``can_admit`` and
        turn that into an admission decision)."""
        if seq_id in self._seqs:
            raise KVPoolError(f"sequence {seq_id!r} already allocated")
        need = self.pages_needed(n_tokens)
        if need > len(self._free):
            raise KVPoolError(
                f"pool exhausted: need {need} pages, {len(self._free)} free")
        pages = [self._free.pop() for _ in range(need)]
        self._seqs[seq_id] = SeqBlocks(pages=pages,
                                       reserved=need * self.page_size)
        self.pages_in_use_hwm = max(self.pages_in_use_hwm,
                                    self.n_pages_in_use)
        return pages

    def free(self, seq_id: str) -> int:
        """Retire a sequence, returning its pages to the free list.
        Raises ``KVPoolError`` on unknown / already-freed sequences (the
        double-free guard)."""
        sb = self._seqs.pop(seq_id, None)
        if sb is None:
            raise KVPoolError(f"free of unknown sequence {seq_id!r}")
        self._free.extend(sb.pages)
        return len(sb.pages)

    def seq(self, seq_id: str) -> SeqBlocks:
        try:
            return self._seqs[seq_id]
        except KeyError:
            raise KVPoolError(f"unknown sequence {seq_id!r}") from None

    def slot(self, seq_id: str) -> Tuple[int, int]:
        """(page, offset) where this sequence's next token is written."""
        sb = self.seq(seq_id)
        if sb.n_tokens >= sb.reserved:
            raise KVPoolError(
                f"{seq_id!r}: write past reservation ({sb.reserved} tokens)")
        return sb.slot(self.page_size)

    def advance(self, seq_id: str, n: int = 1) -> int:
        """Record ``n`` tokens written; returns the new length."""
        sb = self.seq(seq_id)
        if sb.n_tokens + n > sb.reserved:
            raise KVPoolError(
                f"{seq_id!r}: {sb.n_tokens}+{n} exceeds reservation "
                f"{sb.reserved}")
        sb.n_tokens += n
        return sb.n_tokens

    # -- batch views for the jitted step ------------------------------------
    def block_table(self, seq_ids: Sequence[Optional[str]],
                    n_pages: int) -> np.ndarray:
        """(len(seq_ids), n_pages) int32 logical->physical map; unused
        slots and ``None`` lanes point at the null page."""
        bt = np.full((len(seq_ids), n_pages), self.NULL_PAGE, np.int32)
        for i, sid in enumerate(seq_ids):
            if sid is None:
                continue
            pages = self.seq(sid).pages
            assert len(pages) <= n_pages, (sid, len(pages), n_pages)
            bt[i, : len(pages)] = pages
        return bt

    def context_lens(self, seq_ids: Sequence[Optional[str]]) -> np.ndarray:
        return np.asarray([0 if sid is None else self.seq(sid).n_tokens
                           for sid in seq_ids], np.int32)

    def slots(self, seq_ids: Sequence[Optional[str]]
              ) -> Tuple[np.ndarray, np.ndarray]:
        """Next-token write targets per lane: (pages, offsets), inactive
        lanes aimed at the null page."""
        pages = np.zeros(len(seq_ids), np.int32)
        offs = np.zeros(len(seq_ids), np.int32)
        for i, sid in enumerate(seq_ids):
            if sid is None:
                continue
            pages[i], offs[i] = self.slot(sid)
        return pages, offs

    # -- data-plane writes ---------------------------------------------------
    def write_prefill(self, seq_id: str, k: jax.Array, v: jax.Array,
                      n_tokens: int) -> None:
        """Scatter a prefilled sequence's K/V into its pages.

        k/v: (n_layers, S_pad, n_kv_heads, head_dim) with ``S_pad >=
        n_tokens`` (right-padded; pad rows land in the reservation's tail
        slots and are masked out by the context length)."""
        sb = self.seq(seq_id)
        if n_tokens > sb.reserved:
            raise KVPoolError(f"{seq_id!r}: prefill {n_tokens} exceeds "
                              f"reservation {sb.reserved}")
        n_p = self.pages_needed(n_tokens)
        pad = n_p * self.page_size
        k = k[:, :pad] if k.shape[1] >= pad else jnp.pad(
            k, ((0, 0), (0, pad - k.shape[1]), (0, 0), (0, 0)))
        v = v[:, :pad] if v.shape[1] >= pad else jnp.pad(
            v, ((0, 0), (0, pad - v.shape[1]), (0, 0), (0, 0)))
        shape = (self.n_layers, n_p, self.page_size,
                 self.n_kv_heads, self.head_dim)
        idx = jnp.asarray(sb.pages[:n_p], jnp.int32)

        def paged(x):  # (L, n_p*page, Kv, Dh) -> (L, n_p, Kv, page, Dh)
            return x.reshape(shape).transpose(0, 1, 3, 2, 4).astype(
                self.k.dtype)

        self.k = self.k.at[:, idx].set(paged(k))
        self.v = self.v.at[:, idx].set(paged(v))
        sb.n_tokens = n_tokens

    def swap_arenas(self, k: jax.Array, v: jax.Array) -> None:
        """Re-bind the arenas after a jitted decode step returned updated
        copies (the step writes each lane's new token in-place via
        scatter; see ``serving/engine.py``)."""
        assert k.shape == self.k.shape and v.shape == self.v.shape
        self.k, self.v = k, v

    # -- invariants / telemetry ---------------------------------------------
    def check_invariants(self) -> None:
        """Free list and block tables partition the non-null pages."""
        held = [p for sb in self._seqs.values() for p in sb.pages]
        all_pages = sorted(self._free) + sorted(held)
        assert sorted(all_pages) == list(range(1, self.num_pages)), \
            "pages leaked or duplicated"
        assert self.NULL_PAGE not in held and self.NULL_PAGE not in self._free
        for sid, sb in self._seqs.items():
            assert sb.n_tokens <= sb.reserved, (sid, sb)

    def stats(self) -> Dict[str, int]:
        return {"num_pages": self.num_pages,
                "page_size": self.page_size,
                "pages_in_use": self.n_pages_in_use,
                "pages_free": self.n_free_pages,
                "pages_in_use_hwm": self.pages_in_use_hwm,
                "n_sequences": len(self._seqs)}
