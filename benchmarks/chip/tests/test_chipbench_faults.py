"""The comparison that decides ``correct`` catches a broken timed path.

Each test drives a whole run of a tiny cell on the CPU, past the look
for a chip, with the engine's decode step broken underneath, and sees
``correct`` come out false: a step that returns its state (the KV
arenas) unchanged, a token altered where the step produces it, half of
the lanes of the batch left out, and half of each lane's context left
out of attention. A serving cell takes no mean over its batch and runs
on one chip, so it has no mean to skew and no exchange between chips to
leave out."""
import jax.numpy as jnp
import pytest

import chipbench_tiny as tiny
from repro.serving.engine import PagedEngine

DECODE = PagedEngine._decode_step


def stale_state(self, params, k, v, *rest):
    nxt, _, _ = DECODE(self, params, k, v, *rest)
    return nxt, k, v


def altered_token(self, params, k, v, *rest):
    nxt, k, v = DECODE(self, params, k, v, *rest)
    return (nxt + 1) % self.cfg.vocab, k, v


def half_batch(self, params, k, v, *rest):
    nxt, k, v = DECODE(self, params, k, v, *rest)
    return nxt.at[:nxt.shape[0] // 2].set(0), k, v


def half_context(self, params, k, v, tokens, positions, bt, sp, so, lens):
    return DECODE(self, params, k, v, tokens, positions, bt, sp, so,
                  jnp.maximum(lens // 2, jnp.minimum(lens, 1)))


@pytest.mark.parametrize("fault", [stale_state, altered_token, half_batch,
                                   half_context])
def test_broken_decode_step_is_not_correct(tmp_path, monkeypatch, fault):
    monkeypatch.setattr(PagedEngine, "_decode_step", fault)
    line = tiny.run(tiny.make_root(tmp_path))
    gap = line["checks"]["mean_logit_gap"]
    assert not line["correct"]
    assert gap["value"] > gap["limit"]


def test_sound_decode_step_is_correct(tmp_path):
    line = tiny.run(tiny.make_root(tmp_path))
    assert line["correct"], line["checks"]
    assert line["checks"]["mean_logit_gap"]["value"] <= 1e-3
