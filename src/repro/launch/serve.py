"""Serving launcher: LogAct-governed batched generation.

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral_8x7b -n 8
"""
from __future__ import annotations

import argparse

from ..configs.base import ALIASES, ARCH_IDS, get_config, smoke
from ..core.acl import BusClient
from ..core.introspect import TRACE_TYPES, summarize_bus, trace_intents
from ..core.voter import RuleVoter, STANDARD_RULES
from ..serving.server import build_serving_agent
from .mesh import configure_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_4b", choices=ARCH_IDS
                    + list(ALIASES))
    ap.add_argument("-n", "--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--full-config", action="store_true")
    args = ap.parse_args()
    configure_compile_cache()

    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = smoke(cfg, vocab=256)
    agent = build_serving_agent(cfg, max_batch=args.max_batch)
    agent.add_voter(RuleVoter(BusClient(agent.bus, "rv", "voter"),
                              rules=STANDARD_RULES), from_tail=False)
    agent.set_policy("decider", {"mode": "first_voter"})
    for r in range(args.requests):
        agent.send_mail(f"req-{r}", prompt_tokens=[1 + r, 2 + r, 3 + r])
    agent.run_until_idle(max_rounds=10 ** 6)
    served = 0
    for t in trace_intents(agent.bus.read(agent.bus.trim_base(),
                                          types=TRACE_TYPES)):
        if t.kind == "serve_batch" and t.result and t.result["ok"]:
            served += t.result["value"]["batch"]
            print(f"batch of {t.result['value']['batch']} "
                  f"({t.result['value']['new_tokens']} new tokens each) "
                  f"decision={t.decision}")
    s = summarize_bus(agent.bus)
    print(f"served {served}/{args.requests} requests; log {s['tail']} "
          f"entries / {s['total_bytes'] / 1e3:.1f} KB")


if __name__ == "__main__":
    main()
