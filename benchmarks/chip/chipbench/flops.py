"""Operations and bytes the work needs, computed from shapes.

Counted once for the algorithm, never for what a kernel happens to do:
pad tokens, masked keys and re-fetched pages are not work. A multiply
and an add are two operations.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Iterable


def layer_matmul_params(cfg: Dict[str, Any]) -> int:
    d, h, kv, dh, f = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                       cfg["d_head"], cfg["d_ff"])
    return d * h * dh + 2 * d * kv * dh + h * dh * d + 3 * d * f


def attention_flops(cfg: Dict[str, Any], n_keys: int) -> int:
    """One query token over ``n_keys`` keys, one layer: q.k and p.v."""
    return 4 * cfg["n_heads"] * cfg["d_head"] * n_keys


def head_flops(cfg: Dict[str, Any]) -> int:
    return 2 * cfg["d_model"] * cfg["vocab"]


def decode_flops(cfg: Dict[str, Any], attn_lens: Iterable[int]) -> int:
    """One decode step: one token for each lane attending to
    ``attn_len`` keys (its context and the new token)."""
    lens = list(attn_lens)
    per_token = cfg["n_layers"] * 2 * layer_matmul_params(cfg) \
        + head_flops(cfg)
    return len(lens) * per_token + cfg["n_layers"] * sum(
        attention_flops(cfg, n) for n in lens)


def prefill_flops(cfg: Dict[str, Any], n_tokens: int) -> int:
    """Prefill of a prompt of ``n_tokens``: every layer for every token,
    causal attention (token i sees i + 1 keys), the head for the last."""
    causal_keys = n_tokens * (n_tokens + 1) // 2
    return (n_tokens * cfg["n_layers"] * 2 * layer_matmul_params(cfg)
            + cfg["n_layers"] * attention_flops(cfg, causal_keys)
            + head_flops(cfg))


def paged_attention_cost(cfg: Dict[str, Any], attn_lens: Iterable[int],
                         page_size: int, itemsize: int = 4
                         ) -> Dict[str, int]:
    """Operations and bytes one paged-attention call (one layer of one
    decode step) needs: each active lane reads its pages up to its
    context, K and V, for every kv head, plus its query and its output.
    Pages past the context are not needed, whatever the kernel walks."""
    kv, h, dh = cfg["n_kv_heads"], cfg["n_heads"], cfg["d_head"]
    lens = [n for n in attn_lens if n > 0]
    kv_bytes = sum(math.ceil(n / page_size) * page_size for n in lens) \
        * kv * dh * itemsize * 2
    qo_bytes = len(lens) * h * dh * itemsize * 2
    return {"flops": sum(attention_flops(cfg, n) for n in lens),
            "bytes": kv_bytes + qo_bytes}
