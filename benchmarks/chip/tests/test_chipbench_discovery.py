"""A configuration, a traffic mix and a per-layer metric added as new
files are found by their names: no code of the harness changes."""
import json

import chipbench_tiny as tiny
from chipbench import traffic as T


def test_new_files_alone_make_a_new_cell_and_metric(tmp_path):
    root = tiny.make_root(tmp_path)
    bench = root / "benchmarks" / "chip"
    # a further configuration, traffic mix and per-layer metric, as files
    cfg = json.loads((bench / "configs" / "tiny.json").read_text())
    cfg["num_hidden_layers"] = 1
    (bench / "configs" / "tiny1.json").write_text(json.dumps(cfg))
    talk = json.loads((bench / "traffic" / "talk.json").read_text())
    talk["rate_rps"] = 3.0
    (bench / "traffic" / "slow.json").write_text(json.dumps(talk))
    (bench / "limits" / "tiny1.slow.json").write_text(
        (bench / "limits" / f"{tiny.CELL}.json").read_text())
    (bench / "metrics" / "admitted_share.py").write_text(
        "def read(run):\n"
        "    n = sum(q['admit_step'] is not None for q in run.requests)\n"
        "    return 100.0 * n / len(run.requests)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "tiny1", "source": "test",
                            "file": "benchmarks/chip/configs/tiny1.json",
                            "reduced": ["num_hidden_layers"], "why": "test"})
    spec["workloads"].append({"name": "tiny1.slow", "config": "tiny1",
                              "traffic": "slow", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "admitted_share", "unit": "%",
                              "better": "higher", "source": "program_counter",
                              "layer": "scheduler", "moves": "ttft_p90_ms",
                              "workloads": ["tiny1.slow"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    from chipbench import harness, spec as S
    cell = S.load_cell("tiny1.slow", root)
    assert cell.config["n_layers"] == 1 and cell.traffic["rate_rps"] == 3.0
    assert "admitted_share" in [m.name for m in cell.per_layer]
    # the metric is the new cell's alone
    assert "admitted_share" not in [
        m.name for m in S.load_cell(tiny.CELL, root).per_layer]
    line = harness.run_cell(cell, tiny.SEED, 2.0, True, t_start=0.0,
                            root=root, require_tpu=False)
    assert line["correct"], line["checks"]
    assert line["metrics"]["admitted_share"] == {"value": 100.0, "unit": "%"}
    assert line["attempted"] == len(T.generate(talk, tiny.SEED, 2.0, 512))
