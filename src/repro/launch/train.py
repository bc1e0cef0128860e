"""Training launcher: LogAct-governed training for any assigned arch.

Smoke scale by default (reduced config on CPU). On a real TPU deployment
the same entrypoint runs the full config with the production mesh (the
distribution config is exercised by launch/dryrun.py in this container).

  PYTHONPATH=src python -m repro.launch.train --arch qwen3_4b --steps 32
"""
from __future__ import annotations

import argparse
import tempfile

from ..configs.base import ALIASES, ARCH_IDS, get_config, smoke
from ..core.acl import BusClient
from ..core.bus import MemoryBus, make_bus
from ..core.introspect import TRACE_TYPES, summarize_bus, trace_intents
from ..core.voter import RuleVoter, StatVoter, STANDARD_RULES
from ..data.pipeline import DataConfig
from ..optim.optimizer import OptimizerConfig
from ..train.train_step import StepConfig
from ..train.trainer import build_env, build_training_agent
from .mesh import configure_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_4b", choices=ARCH_IDS
                    + list(ALIASES))
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full architecture config (TPU scale)")
    ap.add_argument("--bus", default="memory",
                    choices=["memory", "sqlite", "kv"])
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--dual-voter", action="store_true")
    args = ap.parse_args()
    configure_compile_cache()

    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = smoke(cfg, vocab=256)
    workdir = args.workdir or tempfile.mkdtemp(prefix="repro-train-")
    env = build_env(
        cfg,
        OptimizerConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                        total_steps=args.steps),
        StepConfig(remat="none" if not args.full_config else "dots"),
        DataConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                   global_batch=args.global_batch),
        f"{workdir}/ckpts")
    bus = (MemoryBus() if args.bus == "memory"
           else make_bus(args.bus, path=f"{workdir}/bus"
                         + (".db" if args.bus == "sqlite" else "")))
    agent = build_training_agent(env, total_steps=args.steps,
                                 steps_per_intention=8,
                                 ckpt_every=max(args.steps // 3, 8), bus=bus)
    agent.add_voter(RuleVoter(BusClient(bus, "rule-voter", "voter"),
                              rules=STANDARD_RULES), from_tail=False)
    if args.dual_voter:
        agent.add_voter(StatVoter(BusClient(bus, "stat-voter", "voter"),
                                  override_for="rule"), from_tail=False)
        agent.set_policy("decider", {"mode": "boolean_OR",
                                     "voter_types": ["rule", "stat"]})
    else:
        agent.set_policy("decider", {"mode": "first_voter"})
    agent.send_mail(f"train {args.arch} for {args.steps} steps")
    agent.run_until_idle(max_rounds=10 ** 6)

    losses = [t.result["value"]["loss"]
              for t in trace_intents(bus.read(bus.trim_base(),
                                              types=TRACE_TYPES))
              if t.kind == "train_chunk" and t.result and t.result["ok"]]
    s = summarize_bus(bus)
    print(f"arch={cfg.arch_id} steps={env.step}/{args.steps} "
          f"ckpts={env.ckpts.list_steps()} workdir={workdir}")
    print(f"loss first={losses[0]:.3f} last={losses[-1]:.3f}; "
          f"log {s['tail']} entries / {s['total_bytes'] / 1e3:.1f} KB "
          f"({s['n_committed']} commits, {s['n_aborted']} aborts)")


if __name__ == "__main__":
    main()
