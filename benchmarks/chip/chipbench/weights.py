"""Random weights from a seed, made on the device in one jitted call.

The shapes come from the program's abstract parameter tree; the values
come from here, so the reference takes no weight the program made. The
scales keep activations O(1) at every width: N(0, 1/fan_in) for every
matrix (fan_in is the contracted size: d_model for the q/k/v, gate, up
and head projections, n_heads * d_head for the output projection, d_ff
for the down projection), N(0, 0.02^2) for the embedding (the published
models' ``initializer_range``), and 1 + N(0, 0.1^2) for every RMSNorm
gain. A small embedding matters for a tied head: with N(0, 1) rows the
residual stream is mostly the current token's own row, its logit
outgrows every other, and the model repeats its input token whatever
the context.

The published form holds each norm's gain g, as the published
architectures write RMSNorm (``y * g``). The program's tree holds
``g - 1``, because its RMSNorm computes ``y * (1 + w)``. Both trees share
every matrix; only the small gain vectors are stored twice.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

GAINS = ("ln1", "ln2", "final_norm", "q_norm", "k_norm")
SHARP = 2.0
EMBED_STD = 0.02


def key_data(seed: int) -> np.ndarray:
    """Two uint32 words of threefry key data from any whole number."""
    return np.random.SeedSequence(seed % (1 << 128)).generate_state(
        2, np.uint32)


def _std(name: str, cfg: Dict[str, Any]) -> float:
    d, f = cfg["d_model"], cfg["d_ff"]
    fan = {"wq": d, "wk": d, "wv": d, "w_gate": d, "w_up": d, "lm_head": d,
           "wo": cfg["n_heads"] * cfg["d_head"], "w_down": f}
    if name == "embed":
        return EMBED_STD
    if name not in fan:
        raise KeyError(f"no scale for parameter {name!r}: the program's "
                       f"parameter tree has a leaf the benchmark does not "
                       f"know")
    return fan[name] ** -0.5


def make(abstract: Any, cfg: Dict[str, Any], seed: int) -> Tuple[Any, Any]:
    """(program tree, published tree) for the abstract parameter tree
    ``abstract`` (leaves with ``.shape``/``.dtype``), from ``seed``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [path[-1].key for path, _ in flat]
    shapes = [(tuple(a.shape), a.dtype) for _, a in flat]
    qk_norm = "q_norm" in names
    sharp = [SHARP if (n in ("q_norm", "k_norm") if qk_norm
                       else n in ("wq", "wk")) else 1.0 for n in names]
    scales = [None if n in GAINS else _std(n, cfg) for n in names]

    def init(words):
        key = jax.random.wrap_key_data(words)
        mats, gains, shifted = [], [], []
        for i, ((shape, dtype), scale, k) in enumerate(
                zip(shapes, scales, sharp)):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if scale is None:
                g = (k * (1.0 + 0.1 * z)).astype(dtype)
                gains.append(g)
                shifted.append(g - 1)
            else:
                mats.append((z * (k * scale)).astype(dtype))
        return mats, gains, shifted

    mats, gains, shifted = jax.jit(init)(jnp.asarray(key_data(seed)))

    def tree(norms):
        m, g = iter(mats), iter(norms)
        return jax.tree_util.tree_unflatten(
            treedef, [next(g) if n in GAINS else next(m) for n in names])

    return tree(shifted), tree(gains)
