"""Paged decode-attention Pallas TPU kernel (vLLM-shaped).

Single-token queries (one per in-flight sequence) attend to K/V that live
in a block-allocated page pool (``serving/kv_pool.py``): physical pages of
``page_size`` tokens, stitched into a logical sequence by a per-sequence
block table. The kernel gathers pages *through the block table* — the
table and the per-sequence context lengths ride in as scalar-prefetch
operands (``pltpu.PrefetchScalarGridSpec``), so the K/V BlockSpec index
maps resolve the physical page id before each grid step's DMA is issued.

The pool is kv-head-major, ``(pages, kv_heads, page_size, head_dim)``, so
one grid step's K/V block ``(1, 1, page_size, head_dim)`` is a whole
``(page_size, head_dim)`` tile of the array.

Grid: ``(n_seqs, n_kv_heads, n_pages)`` with the page axis minor-most so
the fp32 online-softmax accumulators persist in VMEM scratch across page
steps (same schedule as ``kernels/flash_attention.py``, which was the
starting template). GQA is handled by blocking the query over kv-head
groups: each grid step processes the ``rep = n_heads // n_kv_heads``
query heads that share one kv head. Ragged sequence lengths are handled
by masking key positions ``>= context_lens[s]`` and skipping pages that
start beyond the sequence's length (scratch init and the final write are
the only work those steps do). Unused block-table slots must point at a
valid physical page (pad with 0): the gather still runs for skipped
steps, it is just never read.

Oracle: ``ref.mha_ref`` on the gathered dense K/V (see
``paged_attention_ref`` and ``tests/test_serving.py``).

TPU alignment: Mosaic requires the last two dims of every block to be
divisible by (8, 128) or to equal the array's own last two dims. The
kv-head-major K/V block equals the pool's ``(page_size, head_dim)`` and
the query/output block ``(rep, head_dim)`` equals the blocked query's,
so any width compiles; a token-major pool, whose block would end in
``(1, head_dim)``, is refused. For full (8, 128) fp32 and (16, 128) bf16
tiles, keep ``page_size`` a multiple of 16 and ``head_dim`` a multiple of
128, as the serving engine's pools are (``tests/test_tpu_compile.py``
compiles the kernel for a v5e at qwen3_4b widths in both dtypes).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(bt_ref, cl_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *,
                  scale: float, softcap: Optional[float],
                  page_size: int, n_pages: int):
    s_i = pl.program_id(0)
    b = pl.program_id(2)

    @pl.when(b == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ctx = cl_ref[s_i]
    base = b * page_size

    # Pages starting at/after the sequence's length contribute nothing:
    # skip everything except scratch init and the final write.
    @pl.when(base < ctx)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (rep, Dh)
        k = k_ref[0, 0].astype(jnp.float32)                # (page, Dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (rep, page)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        kpos = base + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
        s = jnp.where(kpos < ctx, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new

    @pl.when(b == n_pages - 1)
    def _finalize():
        # Sequences with context_len 0 (inactive batch lanes) fall through
        # with l == 0: the clamp makes their output exactly 0.
        l = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, context_lens: jax.Array, *,
                    scale: Optional[float] = None,
                    softcap: Optional[float] = None,
                    interpret: bool = False) -> jax.Array:
    """Paged decode attention.

    q:             (S, H, Dh)  one query token per sequence
    k_pages/v_pages: (P, Kv, page_size, Dh) physical page pool
    block_tables:  (S, n_pages) int32 logical->physical page map (pad
                   unused slots with any valid page id, e.g. 0)
    context_lens:  (S,) int32 tokens of context per sequence (0 = lane
                   inactive; its output row is 0)
    Returns (S, H, Dh) in q's dtype.
    """
    s_n, h, dh = q.shape
    _, kv, page, _ = k_pages.shape
    rep = h // kv
    assert h == kv * rep, (h, kv)
    n_pages = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    qr = q.reshape(s_n, kv, rep, dh)  # q head j*rep+r <-> kv head j

    kernel = functools.partial(_paged_kernel, scale=scale, softcap=softcap,
                               page_size=page, n_pages=n_pages)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_tables, context_lens
        grid=(s_n, kv, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, rep, dh),
                         lambda s, g, b, bt, cl: (s, g, 0, 0)),
            pl.BlockSpec((1, 1, page, dh),
                         lambda s, g, b, bt, cl: (bt[s, b], g, 0, 0)),
            pl.BlockSpec((1, 1, page, dh),
                         lambda s, g, b, bt, cl: (bt[s, b], g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, dh),
                               lambda s, g, b, bt, cl: (s, g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_n, kv, rep, dh), q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      qr, k_pages, v_pages)
    return out.reshape(s_n, h, dh)


def paged_attention_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        block_tables: jax.Array, context_lens: jax.Array, *,
                        scale: Optional[float] = None,
                        softcap: Optional[float] = None) -> jax.Array:
    """Pure-jnp oracle (and the CPU serving data path): gather pages into
    dense per-sequence K/V, masked softmax in fp32. Same contract as
    ``paged_attention``; inactive lanes (context_len 0) return 0."""
    s_n, h, dh = q.shape
    _, kv, page, _ = k_pages.shape
    rep = h // kv
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)

    def gather(pages):  # (S, n, Kv, page, Dh) -> (S, n_ctx, Kv, Dh)
        return pages[block_tables].transpose(0, 1, 3, 2, 4).reshape(
            s_n, -1, kv, dh)

    k, v = gather(k_pages), gather(v_pages)
    kx = jnp.repeat(k, rep, axis=2)                     # (S, n_ctx, H, Dh)
    vx = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("shd,snhd->shn", q.astype(jnp.float32) * scale,
                   kx.astype(jnp.float32))
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    mask = (jnp.arange(k.shape[1])[None, None, :]
            < context_lens[:, None, None])
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("shn,snhd->shd", p, vx.astype(jnp.float32))
    o = jnp.where((context_lens > 0)[:, None, None], o, 0.0)
    return o.astype(q.dtype)
