"""CPU rehearsals of every cell at a tiny size, the no-chip refusals, and a
cell added by files alone."""

import json
import os
import shutil

import pytest

from conftest import BENCH, ROOT, rehearse, run_cli

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_line(workload, trace):
    line = rehearse(workload, seed=2**31 + 11, trace=trace)
    assert [k for k in line if k != "breakdown"] == KEYS
    assert line["correct"] is True, line["checks"]
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    import run
    e2e, per = run.cell_metrics(BENCHMARK, workload)
    names = {m["name"] for m in (per if trace else e2e)}
    assert set(line["metrics"]) <= names
    if trace:
        # no device plane on a CPU: no device metric, no busy time
        assert "busy_s" not in line["device"] and "breakdown" not in line
        assert not any(m["source"] == "device_trace" for m in per if m["name"] in line["metrics"])
    else:
        assert set(line["metrics"]) == names


@pytest.mark.parametrize("workload", ["stream-mixed", "backfill-15s"])
def test_no_tpu_no_result(workload):
    rc, out, err = run_cli(["--workload", workload, "--seed", "3", "--seconds", "2",
                            "--trace", "0"])
    assert rc != 0
    assert not any(line.startswith("{") and '"correct"' in line for line in out)
    assert "no TPU" in err or "not tpu" in err


def _copy(tmp_path, with_program):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns(
        ".cache", "__pycache__"))
    if with_program:
        for name in ("reporter_tpu", "native"):
            os.symlink(os.path.join(ROOT, name), root / name)
    return root


def test_only_the_benchmark_is_refused(tmp_path):
    root = _copy(tmp_path, with_program=False)
    rc, out, _err = run_cli(["--workload", CELLS[0], "--seed", "1", "--seconds", "2",
                             "--trace", "0"], root=str(root))
    assert rc != 0 and not out


def test_cell_added_by_files_alone(tmp_path):
    """A configuration, a traffic mix and a per-layer metric, each a new file
    and a new entry in BENCHMARK.json, are found by name."""
    root = _copy(tmp_path, with_program=True)
    b = root / "benchmark"
    cfg = json.loads((b / "configs" / "metro-stream.json").read_text())
    cfg["city"] = dict(cfg["city"], rows=10, cols=10, seed=4)
    (b / "configs" / "tiny-stream.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "kafka-mixed.json").read_text())
    mix["sample_interval_s"] = [5]
    (b / "traffic" / "tiny-mix.json").write_text(json.dumps(mix))
    (b / "metrics" / "answered.tiny.py").write_text(
        "def read(rec):\n    return rec['attempted'] - rec['failed']\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny-stream", "source": "https://example.org/tiny",
                             "file": "benchmark/configs/tiny-stream.json", "reduced": [],
                             "why": "test"})
    bench["workloads"].append({"name": "tiny-cell", "config": "tiny-stream",
                               "traffic": "tiny-mix", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "answered.tiny", "unit": "requests", "better": "higher",
                               "source": "host_clock", "layer": "test",
                               "moves": "points_per_s", "workloads": ["tiny-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    line = rehearse("tiny-cell", seed=9, trace=1, root=str(root))
    assert line["correct"] is True
    assert line["metrics"]["answered.tiny"]["value"] == line["attempted"]
