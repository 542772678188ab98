"""A later PR adds a configuration, a cell, a loop, a traffic generator, a
per-layer metric, a reader or a cost function as NEW files and entries, and
edits no file that is there. Shown on a copy of the benchmark."""

import json
import os
import shutil
import subprocess
import sys

from benchmark import harness

LOOP = '''
from benchmark import harness

def run(ctx):
    gen = harness.plugin("traffic", ctx["cell"]["traffic"]["generator"])
    with ctx["spans"].span("bench.engine_build"):
        n = gen.count(ctx["seed"], ctx["cell"])
    return {"attempted": n, "failed": 0,
            "checks": [{"name": "counted", "value": n, "limit": n, "ok": True}],
            "end_to_end": {"setup_s": 0.5, "things_per_s": n / ctx["seconds"]},
            "device": harness.device_report(ctx["devices"]), "facts": {}}
'''


def test_new_files_only(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(harness.HERE, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "*.pb"))
    bench = harness.load_benchmark()
    before = {p: open(os.path.join(d, p)).read()
              for d, _s, fs in os.walk(root / "benchmark") for p in fs
              if p.endswith((".py", ".json"))}
    b = root / "benchmark"
    (b / "configs" / "tiny.json").write_text(json.dumps(
        {"name": "tiny", "source": "none", "width": 8, "reference": "gpt2",
         "toy": {}}))
    (b / "workloads" / "tiny.count.json").write_text(json.dumps(
        {"name": "tiny.count", "config": "tiny", "chips": 1, "loop": "count",
         "traffic": {"generator": "counted", "things": 12}, "why": "a test"}))
    (b / "loops" / "count.py").write_text(LOOP)
    (b / "traffic" / "counted.py").write_text(
        "def count(seed, cell):\n    return cell['traffic']['things']\n")
    bench["configs"].append({"name": "tiny", "source": "none",
                             "file": "benchmark/configs/tiny.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "tiny.count", "config": "tiny",
                               "traffic": "count", "chips": 1, "why": "a test"})
    bench["end_to_end"].append({"name": "things_per_s", "unit": "things/s",
                                "better": "higher", "bound": 0.03,
                                "source": "host_clock",
                                "workloads": ["tiny.count"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "tiny.count",
         "--seed", "1", "--seconds", "4", "--trace", "0", "--rehearse"],
        cwd=root, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["attempted"] == 12
    assert line["metrics"]["things_per_s"] == {"value": 3.0, "unit": "things/s"}
    # engine_build_s lists no workloads, so the new cell reports it too
    assert line["device"]["platform"] == "cpu"
    after = {p: open(os.path.join(d, p)).read()
             for d, _s, fs in os.walk(root / "benchmark") for p in fs
             if p in before}
    assert after == before


def test_no_chip_no_result(tmp_path):
    """Without --rehearse a machine with no TPU gets no result line."""
    out = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         "gpt2-large.train-seq1024", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=harness.ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def test_every_layer_metric_has_its_file_and_reader():
    """BENCHMARK.json holds a metric's name, unit, layer, arrow and cells;
    its file holds only how it is read."""
    bench = harness.load_benchmark()
    for entry in bench["per_layer"]:
        spec = harness.load_json("layer_metrics", entry["name"] + ".json")
        assert set(spec) <= {"reader", "args"}, entry["name"]
        assert hasattr(harness.plugin("readers", spec["reader"]), "read")
    cells = {w["name"] for w in bench["workloads"]}
    for entry in bench["per_layer"] + bench["end_to_end"]:
        assert set(entry.get("workloads", [])) <= cells
