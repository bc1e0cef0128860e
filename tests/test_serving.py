"""Serving engine tests: paged attention kernel parity, KV pool allocator
invariants, continuous-batching engine correctness, and LogAct-governed
admission control."""
import importlib.util
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, smoke
from repro.core.acl import BusClient
from repro.core.voter import RuleVoter
from repro.kernels.paged_attention import paged_attention, paged_attention_ref
from repro.kernels.ref import mha_ref
from repro.models.model import Model
from repro.models.params import split_params
from repro.serving import engine as engine_mod
from repro.serving.engine import PagedEngine
from repro.serving.kv_pool import KVPool, KVPoolError
from repro.serving.server import (SERVE_ADMISSION_RULES, ServeEnv,
                                  build_continuous_serving_agent,
                                  h_serve_batch)


# ---------------------------------------------------------------------------
# paged attention kernel: interpret-mode parity vs mha_ref
# ---------------------------------------------------------------------------

def _paged_case(rng, s_n, h, kv, dh, page, n_pages_pool, ctx_lens):
    """Random pool + block tables realizing the given context lengths."""
    k_pages = jnp.asarray(rng.standard_normal(
        (n_pages_pool, kv, page, dh)), jnp.float32)
    v_pages = jnp.asarray(rng.standard_normal(
        (n_pages_pool, kv, page, dh)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((s_n, h, dh)), jnp.float32)
    max_pages = -(-max(max(ctx_lens), 1) // page)
    # disjoint, shuffled physical pages per sequence (page 0 = pad)
    avail = list(rng.permutation(np.arange(1, n_pages_pool)))
    bt = np.zeros((s_n, max_pages), np.int32)
    for i, cl in enumerate(ctx_lens):
        need = -(-cl // page)
        for j in range(need):
            bt[i, j] = avail.pop()
    return q, k_pages, v_pages, jnp.asarray(bt), \
        jnp.asarray(ctx_lens, jnp.int32)


def _dense_oracle(q, k_pages, v_pages, bt, cls, softcap=None):
    """Per-sequence mha_ref over the gathered dense K/V."""
    s_n, h, dh = q.shape
    kv = k_pages.shape[1]
    outs = []
    for i in range(s_n):
        cl = int(cls[i])
        if cl == 0:
            outs.append(jnp.zeros((h, dh), q.dtype))
            continue
        # (n, Kv, page, Dh) -> (Kv, n*page, Dh), first cl tokens
        kd = k_pages[bt[i]].transpose(1, 0, 2, 3).reshape(kv, -1, dh)[:, :cl]
        vd = v_pages[bt[i]].transpose(1, 0, 2, 3).reshape(kv, -1, dh)[:, :cl]
        o = mha_ref(q[i][:, None], kd, vd, causal=False, softcap=softcap)
        outs.append(o[:, 0])
    return jnp.stack(outs)


@pytest.mark.parametrize("h,kv", [(4, 4), (4, 2), (8, 1)])  # GQA ratios
def test_paged_attention_parity_gqa(h, kv):
    rng = np.random.default_rng(0)
    case = _paged_case(rng, s_n=3, h=h, kv=kv, dh=32, page=8,
                       n_pages_pool=16, ctx_lens=[5, 16, 23])
    out = paged_attention(*case, interpret=True)
    np.testing.assert_allclose(out, paged_attention_ref(*case),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(out, _dense_oracle(*case),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_ragged_and_boundaries():
    """Sub-page, exact page boundary, boundary+1, and an inactive lane."""
    rng = np.random.default_rng(1)
    case = _paged_case(rng, s_n=5, h=4, kv=2, dh=16, page=8,
                       n_pages_pool=24, ctx_lens=[1, 7, 8, 17, 0])
    out = paged_attention(*case, interpret=True)
    np.testing.assert_allclose(out, _dense_oracle(*case),
                               rtol=2e-5, atol=2e-5)
    assert np.all(np.asarray(out[4]) == 0.0)  # inactive lane -> exact zeros


def test_paged_attention_softcap_and_scale():
    rng = np.random.default_rng(2)
    q, kp, vp, bt, cls = _paged_case(rng, s_n=2, h=4, kv=2, dh=16, page=4,
                                     n_pages_pool=12, ctx_lens=[6, 11])
    out = paged_attention(q, kp, vp, bt, cls, softcap=30.0, scale=0.25,
                          interpret=True)
    ref = paged_attention_ref(q, kp, vp, bt, cls, softcap=30.0, scale=0.25)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# KV pool allocator invariants
# ---------------------------------------------------------------------------

def _pool(num_pages=8, page_size=4):
    return KVPool(n_layers=2, n_kv_heads=2, head_dim=8,
                  num_pages=num_pages, page_size=page_size)


def test_kv_pool_reserve_and_free():
    p = _pool()
    pages = p.allocate("a", 9)          # ceil(9/4) = 3 pages
    assert len(pages) == 3 and KVPool.NULL_PAGE not in pages
    assert p.n_pages_in_use == 3
    p.check_invariants()
    assert p.free("a") == 3
    assert p.n_pages_in_use == 0
    p.check_invariants()


def test_kv_pool_double_free_and_unknown():
    p = _pool()
    p.allocate("a", 4)
    p.free("a")
    with pytest.raises(KVPoolError):
        p.free("a")                     # double free
    with pytest.raises(KVPoolError):
        p.free("ghost")                 # never allocated
    with pytest.raises(KVPoolError):
        p.slot("a")                     # freed seq has no slots


def test_kv_pool_block_reuse_after_retirement():
    p = _pool(num_pages=4, page_size=4)  # 3 usable pages
    first = p.allocate("a", 12)          # takes all 3
    assert not p.can_admit(1)
    with pytest.raises(KVPoolError):
        p.allocate("b", 4)               # exhausted
    p.free("a")
    second = p.allocate("b", 12)
    assert sorted(first) == sorted(second)  # same physical pages recycled
    p.check_invariants()


def test_kv_pool_reservation_is_a_hard_cap():
    p = _pool()
    p.allocate("a", 4)                  # 1 page = 4 token capacity
    for _ in range(4):
        p.slot("a")
        p.advance("a")
    with pytest.raises(KVPoolError):
        p.slot("a")                     # write past reservation
    with pytest.raises(KVPoolError):
        p.advance("a")
    with pytest.raises(KVPoolError):
        p.allocate("a", 4)              # already allocated


def test_kv_pool_batch_views():
    p = _pool()
    p.allocate("a", 6)
    p.advance("a", 5)
    bt = p.block_table(["a", None], n_pages=4)
    assert bt.shape == (2, 4)
    assert list(bt[1]) == [0, 0, 0, 0]          # inactive lane -> null page
    assert list(p.context_lens(["a", None])) == [5, 0]
    pages, offs = p.slots(["a", None])
    assert (pages[0], offs[0]) == (bt[0, 1], 1)  # token 5 -> page 1, off 1
    assert (pages[1], offs[1]) == (0, 0)


# ---------------------------------------------------------------------------
# continuous-batching engine vs the closed-loop oracle
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def serve_cfg():
    return smoke(get_config("qwen3_4b"))


@pytest.fixture(scope="module")
def oracle_env(serve_cfg):
    model = Model(serve_cfg, dtype=jnp.float32)
    values, _ = split_params(model.init(jax.random.PRNGKey(0)))
    return ServeEnv(model=model, params=values)


def _oracle_tokens(env, prompt, n):
    return h_serve_batch({"prompts": [prompt], "max_new_tokens": n},
                         env)["generated"][0]


def test_engine_matches_closed_loop(serve_cfg, oracle_env):
    eng = PagedEngine(serve_cfg, max_batch=4, num_pages=32, page_size=8,
                      params=oracle_env.params)
    prompt = [5, 17, 99, 3, 42]
    assert eng.admit("r", prompt, 6)
    out = []
    for _ in range(8):
        out += eng.step()
        if not eng.n_inflight:
            break
    assert out[0].tokens == _oracle_tokens(oracle_env, prompt, 6)
    eng.pool.check_invariants()
    assert eng.pool.n_pages_in_use == 0   # retirement freed everything


def test_engine_staggered_admission_parity(serve_cfg, oracle_env):
    """Sequences admitted mid-decode produce the same tokens as dedicated
    closed-loop runs — the point of paged attention + per-seq positions."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 100, size=n).tolist() for n in (5, 9, 13, 2)]
    eng = PagedEngine(serve_cfg, max_batch=3, num_pages=32, page_size=8,
                      params=oracle_env.params)
    queue = list(enumerate(prompts))
    done = {}
    steps = 0
    while queue or eng.n_inflight:
        if queue and eng.can_admit(len(queue[0][1]), 6):
            i, p = queue.pop(0)
            assert eng.admit(f"r{i}", p, 6)
        for s in eng.step():
            done[s.req_id] = s.tokens
        steps += 1
        assert steps < 60
    for i, p in enumerate(prompts):
        assert done[f"r{i}"] == _oracle_tokens(oracle_env, p, 6), i
    eng.pool.check_invariants()


def test_engine_capacity_backpressure(serve_cfg, oracle_env):
    eng = PagedEngine(serve_cfg, max_batch=2, num_pages=8, page_size=8,
                      params=oracle_env.params)
    assert eng.admit("a", [1, 2, 3], 4)
    assert eng.admit("b", [4, 5], 4)
    assert not eng.admit("c", [6], 4)      # no free lane
    assert not eng.admit("a", [9], 4)      # duplicate id
    while eng.n_inflight:
        eng.step()
    assert eng.admit("c", [6], 4)          # lane + pages recycled


# ---------------------------------------------------------------------------
# LogAct-governed continuous serving (scheduler + voters as admission control)
# ---------------------------------------------------------------------------

def _governed_agent(cfg, **kw):
    agent = build_continuous_serving_agent(cfg, max_batch=4, num_pages=64,
                                           page_size=8, max_new_tokens=4,
                                           **kw)
    voter = RuleVoter(BusClient(agent.bus, "v-rule", "voter"),
                      rules=SERVE_ADMISSION_RULES)
    agent.add_voter(voter, from_tail=False)
    agent.set_policy("decider", {"mode": "first_voter"})
    return agent


def test_continuous_serving_end_to_end(serve_cfg, oracle_env):
    agent = _governed_agent(serve_cfg)
    agent.executor.env.engine = PagedEngine(
        serve_cfg, max_batch=4, num_pages=64, page_size=8,
        params=oracle_env.params)
    prompts = [[7, 8, 9], [11, 12], [13, 14, 15, 16]]
    for i, p in enumerate(prompts):
        agent.send_mail(f"req {i}", prompt_tokens=p, req_id=f"r{i}")
    agent.run_until_idle()
    pl = agent.driver.planner
    assert set(pl.outputs) == {"r0", "r1", "r2"}
    for i, p in enumerate(prompts):
        assert pl.outputs[f"r{i}"] == _oracle_tokens(oracle_env, p, 4), i
    # every decode step went through intent-vote-commit
    assert pl.step == agent.executor.env.engine.n_steps or pl.step > 0


def test_admission_control_tenant_denylist(serve_cfg):
    agent = _governed_agent(serve_cfg)
    agent.set_policy("voter:rule", {"tenant_denylist": ["evil"]})
    agent.send_mail("ok", prompt_tokens=[1, 2], req_id="good")
    agent.send_mail("no", prompt_tokens=[3, 4], req_id="bad",
                    tenant="evil")
    agent.run_until_idle()
    pl = agent.driver.planner
    assert "good" in pl.outputs
    assert "bad" not in pl.outputs
    assert pl.rejected == ["bad"]
    # the veto shows on the log as Abort entries, not as silence
    from repro.core.entries import PayloadType
    aborts = [e for e in agent.external_client("t", "admin").read(0)
              if e.type == PayloadType.ABORT]
    assert aborts, "vetoed admission must be an auditable Abort"


def test_admission_control_prompt_budget(serve_cfg):
    agent = _governed_agent(serve_cfg)
    agent.set_policy("voter:rule", {"max_tokens_per_request": 6})
    agent.send_mail("small", prompt_tokens=[1], req_id="small")  # 1+4 <= 6
    agent.send_mail("big", prompt_tokens=[1, 2, 3], req_id="big")  # 3+4 > 6
    agent.run_until_idle()
    pl = agent.driver.planner
    assert "small" in pl.outputs and "big" not in pl.outputs
    assert pl.rejected == ["big"]


def test_engine_with_interpret_kernel(serve_cfg, oracle_env, monkeypatch):
    """The Pallas kernel path (interpret mode) generates the same tokens
    as the jnp paged reference inside the full engine."""
    prompt = [3, 1, 4, 1, 5]
    outs = []
    for attend in (paged_attention_ref,
                   partial(paged_attention, interpret=True)):
        # the engine traces its decode step on first use, so the
        # attention it finds then is the one compiled in
        monkeypatch.setattr(engine_mod, "_paged_attention", attend)
        eng = PagedEngine(serve_cfg, max_batch=2, num_pages=16, page_size=8,
                          params=oracle_env.params)
        assert eng.admit("r", prompt, 4)
        done = []
        while eng.n_inflight:
            done += eng.step()
        outs.append(done[0].tokens)
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# chip_smoke.py: its phases at smoke size (everything but the TPU check)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_phases_at_smoke_size(serve_cfg, chip_smoke):
    problems, facts = chip_smoke.run(
        serve_cfg, on_chip=False, n_requests=6, max_new_tokens=4,
        max_batch=4, num_pages=64, max_pages_per_seq=8,
        prompt_pages=(1, 3))
    assert problems == []
    assert facts["tokens"] == 6 * 4
    assert facts["governed_steps"] == facts["decode_steps"] > 0
    assert not facts["kernel_in_decode_step"]   # CPU serves the jnp gather
    assert facts["kernel_vs_ref_rel_err"] <= 1e-5


def test_chip_smoke_refuses_cpu(chip_smoke, capsys):
    assert chip_smoke.main() != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU found" in out.err


def test_compile_cache_placement(monkeypatch, tmp_path):
    from repro.launch.mesh import DEFAULT_COMPILE_CACHE, configure_compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was  # left to JAX
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert configure_compile_cache() == str(DEFAULT_COMPILE_CACHE)
        assert jax.config.jax_compilation_cache_dir == str(
            DEFAULT_COMPILE_CACHE)
        assert DEFAULT_COMPILE_CACHE == \
            Path(__file__).resolve().parents[1] / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
