"""bench.py orchestration logic (no hardware): north-star-first section
order, per-attempt emit, soft-budget skips, vs_prev regression deltas.

The round-3 driver run died compiling GPT-2 LAST (BENCH_r03.json rc 124,
extras.gpt2 null) — these tests pin the round-4 fixes so the flagship
number can't silently fall off the end of the budget again."""

import importlib
import json
import sys

import pytest


@pytest.fixture()
def bench(monkeypatch):
    monkeypatch.delenv("BENCH_ONLY", raising=False)
    monkeypatch.delenv("BENCH_GPT2", raising=False)
    monkeypatch.delenv("BENCH_WORKER", raising=False)
    mod = importlib.import_module("bench")
    importlib.reload(mod)
    return mod


def _result(metric, value=100.0):
    return {
        "metric": metric, "value": value, "unit": "u", "vs_baseline": 1.5,
    }


def test_gpt2_runs_first_and_emits_per_attempt(bench, monkeypatch, capsys):
    calls = []

    def fake_attempt(spec, timeout=1500):
        calls.append(spec)
        kind = spec["kind"]
        if kind == "gpt2":
            return _result(
                f"{spec['model']}_causal_lm_seq1024_tokens_per_sec_per_chip"
            )
        return _result(f"{kind}_metric")

    monkeypatch.setattr(bench, "_run_attempt", fake_attempt)
    bench.main()
    out = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    # the FIRST dispatched attempt is the GPT-2 north star
    assert calls[0]["kind"] == "gpt2"
    assert calls[0]["model"] == "gpt2_1.5b"
    # every successful attempt re-emitted a full JSON line
    assert len(out) >= 4
    # the north star rides extras.gpt2 in every line from the first on
    assert "gpt2_1.5b" in out[0]["extras"]["gpt2"]["metric"]
    assert "gpt2_1.5b" in out[-1]["extras"]["gpt2"]["metric"]


def test_budget_skips_tail_sections_not_gpt2(bench, monkeypatch, capsys):
    calls = []

    def fake_attempt(spec, timeout=1500):
        calls.append(spec)
        if spec["kind"] == "gpt2":
            return _result("gpt2_1.5b_causal_lm")
        return _result(spec["kind"])

    monkeypatch.setattr(bench, "_run_attempt", fake_attempt)
    monkeypatch.setattr(bench, "_BUDGET", -1.0)  # budget already exhausted
    bench.main()
    out = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    kinds = {c["kind"] for c in calls}
    assert "gpt2" in kinds          # the north star always runs
    assert "bert" not in kinds      # stable sections skipped on low budget
    assert out and "gpt2" in out[-1]["extras"]


def _bench_round_file(tmp_path, n, extras):
    """Driver-shaped BENCH_r{n}.json with the given parsed extras."""
    (tmp_path / f"BENCH_r{n:02d}.json").write_text(json.dumps({
        "n": n, "rc": 0, "parsed": {"metric": "m", "extras": extras},
    }))


def test_vs_prev_attached_from_previous_round(bench, monkeypatch, capsys,
                                              tmp_path):
    """A prior round's bert=374.41 in a BENCH file must give a new bert
    result with the same metric name a vs_prev ratio. Hermetic: reads a
    tmpdir, not the repo root."""
    _bench_round_file(tmp_path, 3, {
        "bert": _result(
            "bert_large_pretrain_seq128_samples_per_sec_per_chip",
            value=374.41,
        ),
    })
    orig = bench._load_prev_extras
    monkeypatch.setattr(
        bench, "_load_prev_extras", lambda: orig(search_dir=str(tmp_path))
    )

    def fake_attempt(spec, timeout=1500):
        if spec["kind"] == "bert" and spec.get("seq", 128) == 128:
            return _result(
                "bert_large_pretrain_seq128_samples_per_sec_per_chip",
                value=411.85,  # = 1.1 * 374.41
            )
        return None

    monkeypatch.setattr(bench, "_run_attempt", fake_attempt)
    monkeypatch.setenv("BENCH_ONLY", "bert")
    bench.main()
    out = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    assert out, "no emit"
    bert = out[-1]["extras"]["bert"]
    assert bert.get("vs_prev") == pytest.approx(1.1, abs=0.01)


def test_prev_extras_merge_across_partial_rounds(bench, tmp_path):
    """r03 measured bert+squad (gpt2 null), r04 only gpt2: the merged view
    must keep ALL three sections, taking the newest value per section."""
    _bench_round_file(tmp_path, 3, {
        "bert": _result("bert_metric", value=374.41),
        "squad": _result("squad_metric", value=99.3),
        "gpt2": None,
    })
    _bench_round_file(tmp_path, 4, {
        "gpt2": _result("gpt2_metric", value=5352.7),
        "bert": None,
    })
    merged = bench._load_prev_extras(search_dir=str(tmp_path))
    assert merged["bert"]["value"] == 374.41
    assert merged["squad"]["value"] == 99.3
    assert merged["gpt2"]["value"] == 5352.7


def test_prev_extras_newer_round_wins_per_section(bench, tmp_path):
    _bench_round_file(tmp_path, 3, {"bert": _result("bert_metric", 374.41)})
    _bench_round_file(tmp_path, 4, {"bert": _result("bert_metric", 380.0)})
    merged = bench._load_prev_extras(search_dir=str(tmp_path))
    assert merged["bert"]["value"] == 380.0


def test_headline_sections_run_before_gpt2_proxies(bench, monkeypatch):
    """r04 lesson: the driver run died compiling the 774m PROXY before
    BERT ever ran. Order must be: 1.5B north star, then bert/bert512/
    squad, then proxies only on leftover budget."""
    calls = []

    def fake_attempt(spec, timeout=1500):
        calls.append(spec)
        if spec["kind"] == "gpt2":
            return _result(
                f"{spec['model']}_causal_lm_seq1024_tokens_per_sec_per_chip"
            )
        return _result(f"{spec['kind']}_metric")

    monkeypatch.setattr(bench, "_run_attempt", fake_attempt)
    bench.main()
    order = [
        c["model"] if c["kind"] == "gpt2" else c["kind"] for c in calls
    ]
    first_bert = order.index("bert")
    first_proxy = order.index("gpt2_large_774m")
    assert order[0] == "gpt2_1.5b"
    assert first_bert < first_proxy
    assert "squad" in order[:first_proxy]


def test_worker_attempt_timeout_capped_by_budget(bench, monkeypatch):
    seen = {}

    class FakeProc:
        returncode = bench.OOM_EXIT
        stdout = ""
        stderr = ""

    def fake_run(cmd, env=None, capture_output=None, text=None, timeout=None):
        seen["timeout"] = timeout
        return FakeProc()

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.setattr(bench, "_BUDGET", 0.0)
    assert bench._run_attempt({"kind": "bert"}) is None
    # grace window (~60s) past the exhausted budget, floored at 120s
    assert seen["timeout"] <= 121.0


# ---------------------------------------------------------------------------
# no hidden fallbacks (PR 21): only an OOM may move down a ladder, and the
# training ladder measures a TPU or nothing
# ---------------------------------------------------------------------------
def _fake_worker(bench, monkeypatch, returncode, stderr=""):
    class FakeProc:
        stdout = ""

    FakeProc.returncode = returncode
    FakeProc.stderr = stderr
    monkeypatch.setattr(
        bench.subprocess, "run", lambda *a, **k: FakeProc()
    )


def test_oom_worker_moves_down_the_ladder(bench, monkeypatch):
    _fake_worker(bench, monkeypatch, bench.OOM_EXIT)
    assert bench._run_attempt({"kind": "gpt2"}) is None


def test_non_oom_worker_death_fails_the_run(bench, monkeypatch):
    _fake_worker(
        bench, monkeypatch, 1, stderr="Traceback\nValueError: bad shape\n"
    )
    with pytest.raises(SystemExit) as exc:
        bench._run_attempt({"kind": "gpt2"})
    # non-zero, and the worker's own words reach the operator
    assert exc.value.code not in (0, None)
    assert "ValueError: bad shape" in str(exc.value.code)
    assert "not OOM" in str(exc.value.code)


def test_worker_that_finds_no_tpu_fails_the_run(bench, monkeypatch):
    _fake_worker(bench, monkeypatch, bench.NOT_TPU_EXIT)
    with pytest.raises(SystemExit) as exc:
        bench._run_attempt({"kind": "bert"})
    assert "no TPU" in str(exc.value.code)


def test_worker_timeout_fails_the_run(bench, monkeypatch):
    def expire(cmd, timeout=None, **kw):
        raise bench.subprocess.TimeoutExpired(cmd, timeout)

    monkeypatch.setattr(bench.subprocess, "run", expire)
    with pytest.raises(SystemExit) as exc:
        bench._run_attempt({"kind": "squad"})
    assert "timed out" in str(exc.value.code)


def test_training_ladder_main_exits_nonzero_on_worker_crash(
    bench, monkeypatch
):
    """End to end through main(): the first section's crash ends the run;
    no later, weaker rung gets to print a number."""
    calls = []

    def crashing(cmd, **kw):
        calls.append(cmd)

        class P:
            returncode, stdout, stderr = 134, "", "Fatal Python error"

        return P()

    monkeypatch.setattr(bench.subprocess, "run", crashing)
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    assert len(calls) == 1


def test_worker_refuses_a_non_tpu_platform(bench, monkeypatch):
    """The worker is the process that holds the chip: on this CPU it must
    leave with its own exit code before building anything."""
    monkeypatch.setenv(
        "BENCH_WORKER", json.dumps({"kind": "bert", "policy": "full",
                                    "micro": 1, "total": 1})
    )
    monkeypatch.setattr(
        bench, "bert_attempt",
        lambda *a, **k: pytest.fail("attempt ran on a CPU"),
    )
    with pytest.raises(SystemExit) as exc:
        bench._worker_main()
    assert exc.value.code == bench.NOT_TPU_EXIT


@pytest.mark.parametrize(
    "text,oom",
    [
        ("RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
         "memory in memory space hbm. Used 18.00G of 15.75G hbm.", True),
        ("RESOURCE_EXHAUSTED: Error allocating device buffer", True),
        ("Out of memory while trying to allocate 1073741824 bytes", True),
        # the bare substring "OOM" used to match all of these
        ("KeyError: 'ZOOM_LEVEL'", False),
        ("cannot open BLOOM checkpoint", False),
        ("ValueError: flash_attention found no block size", False),
    ],
)
def test_is_oom_matches_only_xla_memory_errors(bench, text, oom):
    assert bench._is_oom(RuntimeError(text)) is oom


def test_every_result_line_names_its_device(bench):
    line = json.loads(bench._result_json({"metric": "m", "value": 1.0}))
    assert line["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    assert line["metric"] == "m"


def test_compile_cache_block_carries_no_path(bench, monkeypatch):
    """One rule for where the cache lives (runtime/compile_cache.py);
    bench.py neither reads BENCH_CACHE_DIR nor names a directory."""
    monkeypatch.setenv("BENCH_CACHE_DIR", "/nonexistent/ignored")
    import importlib

    importlib.reload(bench)
    assert "cache_dir" not in bench.COMPILE_CACHE_BLOCK
    assert bench.COMPILE_CACHE_BLOCK["enabled"] is True
    assert not hasattr(bench, "CACHE_DIR")
