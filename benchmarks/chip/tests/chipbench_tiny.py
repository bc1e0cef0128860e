"""A tiny cell for the CPU tests: the benchmark's own readers and
reference in a temporary checkout, with a small configuration, a short
traffic mix and its own ``BENCHMARK.json``. Nothing here touches a TPU
library."""
from __future__ import annotations

import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Dict, Optional

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parents[1]
for p in (str(BENCH), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

CELL = "tiny.talk"
SEED = 2 ** 31 + 7  # more than 32 signed bits hold

TINY_CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "intermediate_size": 128, "vocab_size": 512,
    "num_hidden_layers": 2}

TINY_TRAFFIC = {
    "rate_rps": 4.0, "schedule_seed": 0, "lanes": 4, "page_size": 16,
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.5, "min": 8,
               "max": 64, "page_buckets": [1, 2, 4]},
    "output": {"dist": "lognormal", "median": 16, "sigma": 0.4, "min": 8,
               "max": 32},
    "check": {"tokens": 96, "max_requests": 8}}


def make_root(tmp: Path, config: Optional[Dict[str, Any]] = None,
              traffic: Optional[Dict[str, Any]] = None,
              limits: Optional[Dict[str, Any]] = None) -> Path:
    """A checkout-shaped directory holding one tiny cell, ``tiny.talk``,
    with the benchmark's metric readers, reference and peaks. Its limits
    are ``limits``, else the chat cell's."""
    bench = tmp / "benchmarks" / "chip"
    for d in ("metrics", "references"):
        shutil.copytree(BENCH / d, bench / d)
    for d in ("configs", "traffic", "limits"):
        (bench / d).mkdir(parents=True)
    shutil.copy(BENCH / "peaks.json", bench / "peaks.json")
    cfg = json.loads((BENCH / "configs" / "qwen3_4b.json").read_text())
    cfg.update(config or TINY_CONFIG)
    (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "talk.json").write_text(
        json.dumps(traffic or TINY_TRAFFIC))
    if limits is None:
        limits = json.loads(
            (BENCH / "limits" / "qwen3_4b.chat.json").read_text())
        limits["compared_tokens"]["at_least"] = 16
    (bench / "limits" / f"{CELL}.json").write_text(json.dumps(limits))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "benchmarks/chip/configs/tiny.json",
                        "reduced": [], "why": "test"}]
    spec["workloads"] = [{"name": CELL, "config": "tiny", "traffic": "talk",
                          "chips": 1, "why": "test"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (tmp / "BENCHMARK.json").write_text(json.dumps(spec))
    return tmp


def run(root: Path, seconds: float = 2.0, trace: bool = False,
        control: bool = False) -> Dict[str, Any]:
    """One run of the tiny cell on the CPU, past the harness's look for
    a chip; returns the result line."""
    from chipbench import harness, spec
    cell = spec.load_cell(CELL, root)
    return harness.run_cell(cell, SEED, seconds, trace, t_start=time.time(),
                            root=root, require_tpu=False, control=control)
