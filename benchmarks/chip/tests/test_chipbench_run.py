"""``run.py`` refuses to run off the TPU: it exits nonzero and prints no
result line, both in the repository and in a directory that holds only
the benchmark's files. On the CPU a tiny cell runs end to end past the
look for a chip, and its result line has the keys the contract names."""
import json
import os
import shutil
import subprocess
import sys

import chipbench_tiny as tiny
from chipbench import traffic as T

ARGS = ["--workload", "qwen3_4b.chat", "--seed", str(2 ** 31 + 3),
        "--seconds", "10", "--trace", "0"]


def run_py(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "benchmarks/chip/run.py", *ARGS],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def no_result(out):
    return not any(line.startswith("{") for line in out.splitlines())


def test_off_the_tpu_exits_nonzero_without_a_result():
    p = run_py(tiny.REPO)
    assert p.returncode != 0
    assert no_result(p.stdout), p.stdout
    assert "TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(tiny.REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(tiny.BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_py(tmp_path)
    assert p.returncode != 0
    assert no_result(p.stdout), p.stdout


def test_result_line_on_the_cpu(tmp_path):
    line = tiny.run(tiny.make_root(tmp_path))
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert set(line["metrics"]) == {"ttft_p90_ms", "itl_p95_ms",
                                    "tokens_per_s", "setup_s"}
    assert line["device"]["platform"] == "cpu"
    due = T.generate(tiny.TINY_TRAFFIC, tiny.SEED, 2.0, 512)
    assert line["attempted"] == len(due) and line["failed"] == 0
    assert all(m["value"] > 0 for m in line["metrics"].values())
    json.dumps(line)
