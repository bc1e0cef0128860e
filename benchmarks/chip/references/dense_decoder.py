"""Plain reference of the dense decoder family: qwen3 and chatglm3.

A straightforward ``jax.numpy`` forward pass over one whole sequence, in
the precision that the configuration states: float32 weights,
activations and K/V, with dots at the precision its ``dot_precision``
names (``default``: on a TPU a float32 dot takes bfloat16 operands and
accumulates in float32, as the served path's dots do). No kernel, cache
or batching. It imports nothing of the program under test and reads the
published-form weights that the benchmark made
(``chipbench/weights.py``).

Per layer, as published: x += Attn(RMSNorm(x) * g1); x += MLP(RMSNorm(x)
* g2), with SwiGLU MLP ``down(silu(gate(h)) * up(h))``, grouped-query
attention with a causal mask, RoPE on the first ``rope_fraction`` of
each head's dims, optional per-head RMSNorm of q and k before RoPE
(qwen3), a final RMSNorm and a tied or untied head.

Departures from the published models, shared with the program:
- chatglm3 adds a bias to q, k and v (``add_qkv_bias``); this block has
  none (under 5k parameters a layer).
- chatglm3 rotates interleaved pairs (2i, 2i + 1) of the rotary half;
  this block rotates pairs (i, i + rot/2), the rotate-half convention.
  The two differ by a fixed permutation of the rotary columns of the q
  and k projections, so with random weights they are the same model.

``dtype=jnp.bfloat16`` gives the control: weights, activations and K/V
stored in bfloat16, with the RMSNorm statistics, the softmax and the
logits in float32, as a bfloat16 serving path would compute them.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

Q_BLOCK = 512  # most query rows per attention block: bounds the scores


def _rms(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta, fraction):
    """x (T, heads, Dh); rotate-half RoPE on the first fraction of Dh."""
    rot = int(x.shape[-1] * fraction)
    rot -= rot % 2
    half = rot // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:rot].astype(jnp.float32)
    y = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([y.astype(x.dtype), x[..., rot:]], -1)


def logits_at(w: Dict[str, Any], tokens: jax.Array, positions: jax.Array,
              *, cfg: Dict[str, Any], precision: str = "default",
              dtype=jnp.float32) -> jax.Array:
    """Logits (len(positions), vocab), float32, of the model over
    ``tokens`` (T,) read at ``positions``, with every dot at
    ``precision`` (``default``, ``high`` or ``highest``). Keys after a
    query are masked, so right-padding ``tokens`` changes no logit
    before the padding."""
    prec = jax.lax.Precision(precision)
    eps = cfg["rmsnorm_eps"]
    h_n, kv_n, dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_head"]
    rep = h_n // kv_n
    t = tokens.shape[0]
    qb_n = math.gcd(t, Q_BLOCK)
    pos = jnp.arange(t)
    cast = lambda a: a.astype(dtype)  # noqa: E731

    def dot(spec, a, b):
        return jnp.einsum(spec, a, cast(b), precision=prec)

    def layer(x, lw):
        a = lw["attn"]
        h = _rms(x, lw["ln1"], eps)
        q = dot("td,dhk->thk", h, a["wq"])
        k = dot("td,dhk->thk", h, a["wk"])
        v = dot("td,dhk->thk", h, a["wv"])
        if cfg["qk_norm"]:
            q, k = _rms(q, a["q_norm"], eps), _rms(k, a["k_norm"], eps)
        q = _rope(q, pos, cfg["rope_theta"], cfg["rope_fraction"])
        k = _rope(k, pos, cfg["rope_theta"], cfg["rope_fraction"])
        scale = dh ** -0.5

        def block(i):
            qb = jax.lax.dynamic_slice_in_dim(q, i * qb_n, qb_n)
            qb = qb.reshape(qb_n, kv_n, rep, dh)
            s = jnp.einsum("qgrd,kgd->grqk", qb, k, precision=prec,
                           preferred_element_type=jnp.float32) * scale
            qpos = i * qb_n + jnp.arange(qb_n)
            s = jnp.where(pos[None, :] <= qpos[:, None], s, -jnp.inf)
            p = jax.nn.softmax(s, -1).astype(dtype)
            o = jnp.einsum("grqk,kgd->qgrd", p, v, precision=prec)
            return o.reshape(qb_n, h_n, dh)

        o = jax.lax.map(block, jnp.arange(t // qb_n))
        o = o.reshape(t, h_n, dh)
        x = x + dot("thk,hkd->td", o, a["wo"])
        h = _rms(x, lw["ln2"], eps)
        m = lw["mlp"]
        u = jax.nn.silu(dot("td,df->tf", h, m["w_gate"])) \
            * dot("td,df->tf", h, m["w_up"])
        return x + dot("tf,fd->td", u, m["w_down"]), None

    x = cast(w["embed"][tokens])
    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = _rms(x[positions], w["final_norm"], eps)
    vocab = cfg["vocab"]
    head = (w["embed"][:vocab].T if cfg["tie_embeddings"]
            else w["lm_head"][:, :vocab])
    return jnp.einsum("pd,dv->pv", x, cast(head), precision=prec,
                      preferred_element_type=jnp.float32)
