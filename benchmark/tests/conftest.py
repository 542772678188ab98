"""These tests are the benchmark's own (run by hand and in the rehearsal,
on the CPU): ``JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q``.
They are not part of the repo's tier-1 suite."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import json

import pytest


@pytest.fixture
def serve_toy(monkeypatch):
    """No cell of BENCHMARK.json serves yet (PERF.md, Open questions): the
    serving loop is driven on a toy cell of the tests' own, found under the
    name it gives as any cell is."""
    from benchmark import harness

    with open(os.path.join(os.path.dirname(__file__), "serve-toy.json")) as fd:
        cell = json.load(fd)
    real = harness.load_cell

    def load_cell(name):
        if name != cell["name"]:
            return real(name)
        _cell, config, bench = real("gpt2-large.train-seq1024")
        return json.loads(json.dumps(cell)), config, bench

    monkeypatch.setattr(harness, "load_cell", load_cell)
    return cell["name"]


@pytest.fixture(scope="session")
def sound_training():
    """ONE sound rehearsal of cell 1 through the training loop, in this
    process, for every test that reads a sound run: its context, what the
    loop returned and its earlier lines. The process is made to look 1,000 s
    old when the loop gets its devices, so a set-up counted from the
    process's start would show."""
    import contextlib
    import io

    from benchmark import run
    from benchmark.loops import train

    ctx = run.context("gpt2-large.train-seq1024", 11, 1.0, rehearse=True)
    ctx["t_process"] -= 1000.0
    said = io.StringIO()
    with contextlib.redirect_stdout(said):
        result = train.run(ctx)
    notes = [json.loads(line) for line in said.getvalue().splitlines()
             if line.startswith("{")]
    return {"ctx": ctx, "result": result, "notes": notes}
