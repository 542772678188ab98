"""``setup_s`` since ISSUE 48: counted from the moment the loop has its
devices, every second of it under a name, and the three per-layer metrics
that name what ``engine_build_s`` did not. Read off ONE sound rehearsal
(``conftest.sound_training``: toy size, CPU, control flow only)."""

import json
import time

import pytest

from benchmark import harness
from benchmark.readers import span_total

PARTS = {"weights_s": ("bench.weights", "benchmark"),
         "engine_build_s": ("bench.engine_build", "entry points"),
         "first_steps_s": ("bench.first_steps", "training engine"),
         "read_state_s": ("bench.read_state", "benchmark")}
# the cells of this PR: a later cell joins the lists, none of these leaves
CELLS_48 = {
    "gpt2-large.train-seq1024", "bert-large.pretrain-seq128",
    "gpt2-large.zero2-dp4", "nemotron3-super-120b-a12b.train-seq8192",
    "gpt2-large.train-accum1", "qwen3-next-80b-a3b.train-seq16384",
    "bert-large.pretrain-seq512", "ouro-2.6b.train-seq8192",
    "sdar-30b-a3b-chat.train-blockdiff-seq8192", "bert-large.squad-seq384",
    "laguna-s-2.1.train-seq8192", "joyai-llm-flash.train-seq8192"}


def setup_line(run):
    (line,) = [n for n in run["notes"] if n["note"] == "setup"]
    return line


def test_setup_is_counted_from_the_loops_devices(sound_training):
    ctx, line = sound_training["ctx"], setup_line(sound_training)
    setup_s = sound_training["result"]["end_to_end"]["setup_s"]
    assert line["setup_s"] == setup_s
    # the fixture aged the process by 1,000 s: none of them is in it
    assert 0 < setup_s < time.perf_counter() - ctx["t_loop"] < 1000.0
    assert line["before_the_loop_s"] == ctx["t_loop"] - ctx["t_process"] > 1000.0


def test_the_old_reading_is_the_new_plus_before_the_loop(sound_training):
    line = setup_line(sound_training)
    assert line["process_to_first_window_s"] == pytest.approx(
        line["setup_s"] + line["before_the_loop_s"], abs=1e-9)


def test_the_named_parts_and_unnamed_add_up_to_setup(sound_training):
    ctx, line = sound_training["ctx"], setup_line(sound_training)
    parts = {name: span_total.read(ctx, None, span)
             for name, (span, _layer) in PARTS.items()}
    assert all(value > 0 for value in parts.values())
    # a metric reads what the set-up line prints under the span's own name
    assert all(line[PARTS[name][0] + "_s"] == value
               for name, value in parts.items())
    assert sum(parts.values()) + line["unnamed_s"] == pytest.approx(
        line["setup_s"], abs=1e-6)
    # the loop's own imports and glue: small beside the parts, on any machine
    assert 0 <= line["unnamed_s"] < 0.1 * line["setup_s"]


@pytest.mark.parametrize("name", list(PARTS))
def test_a_part_has_its_file_its_reader_and_every_cell(name):
    span, layer = PARTS[name]
    (entry,) = [e for e in harness.load_benchmark()["per_layer"]
                if e["name"] == name]
    assert (entry["unit"], entry["better"], entry["source"], entry["layer"],
            entry["moves"]) == ("s", "lower", "host_clock", layer, "setup_s")
    assert CELLS_48 <= set(entry["workloads"])
    assert harness.load_json("layer_metrics", name + ".json") == {
        "reader": "span_total", "args": {"span": span}}


# ---- benchmark/setup_pairs.py, driven whole over a stand-in for the command


STAND_IN = '''
import json, signal, sys, time
seed = int(sys.argv[sys.argv.index("--seed") + 1])
if seed % 2 and "{deaf}":
    signal.signal(signal.SIGINT, signal.SIG_IGN)   # a run that will not unwind
setup = 10.0 + seed % 100
parts = {{"bench.weights_s": 1.0, "bench.engine_build_s": 2.0,
          "bench.first_steps_s": setup - 4.5, "bench.read_state_s": 1.0}}
print(json.dumps({{"note": "setup", "setup_s": setup, "before_the_loop_s": 5.0,
                   "process_to_first_window_s": setup + 5.0, "unnamed_s": 0.5,
                   **parts}}), flush=True)
if seed >= 900:                                    # a checkout's first run
    print(json.dumps({{"correct": True, "attempted": 1}}), flush=True)
else:
    time.sleep(60)                                 # the window and the reference
'''


@pytest.mark.parametrize("deaf", ["", "yes"])
def test_same_tree_sets_are_made_stopped_and_reduced(tmp_path, monkeypatch,
                                                     capsys, deaf):
    from benchmark import setup_pairs

    def checkout(name):
        root = tmp_path / name / "benchmark"
        root.mkdir(parents=True)
        (root / "run.py").write_text(STAND_IN.format(deaf=deaf))
        return str(tmp_path / name)

    monkeypatch.setattr(setup_pairs, "checkout", checkout)
    monkeypatch.setattr(setup_pairs, "PATIENCE", 0.5)
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    started = time.perf_counter()
    assert setup_pairs.main(["--workload", "a.cell", "--seed", "0"]) == 0
    # every run but a checkout's first was stopped after its `setup` line
    assert time.perf_counter() - started < 30
    said = capsys.readouterr().out.splitlines()
    runs = [json.loads(n) for n in said if n.startswith('{"set"') and "seed" in n]
    assert [(r["set"], r["seed"]) for r in runs] == [
        (0, 0), (1, 0), (1, 1), (0, 1), (0, 2), (1, 2),
        (1, 3), (0, 3), (0, 4), (1, 4), (1, 5), (0, 5)]
    report = json.loads(
        (tmp_path / "chiprun_out" / "setup_pairs" / "a.cell.json").read_text())
    first = report["first_runs"]
    assert first[0]["result"]["correct"] is True and first[1]["result"] is None
    table = report["table"]
    assert set(table) == set(setup_pairs.PARTS)
    # seeds 0..5 read 10..15 s in both sets: quartiles 10.75 and 14.25
    assert table["setup_s"]["median_by_set"] == [12.5, 12.5]
    assert table["setup_s"]["spread_by_set"] == [pytest.approx(3.5 / 12.5)] * 2
    # the worst two pairs of the twelve: (10, 10) against (15, 15)
    assert table["setup_s"]["two_pair_gap"] == pytest.approx(0.5)
    assert table["before_the_loop_s"]["two_pair_gap"] == 0.0
