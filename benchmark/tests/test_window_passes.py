"""The readers ISSUE 35 added, ``pass_time`` and ``unscoped_time``, on
``phases.xplane.pb`` (three fused training windows of a two-layer GPT-2 on a
TPU v5 lite, recorded by ``record_phases.py`` BEFORE the dense block had
scopes of its own). A reader finds a layer by ``/name/`` in an operation's
path and a flax module's name is a path component like a ``jax.named_scope``,
so the recorded path components ``h`` (the layer scan's blocks), ``ln_f`` and
``jit(blocked_lm_head_loss)`` stand in for the layers here; the two sums
that the acceptance of ISSUE 35 asks for are checked on them. The rule for
the pass and the HLO opcode are checked on made paths, and the new entries
of ``BENCHMARK.json`` against their files."""

import json
import os

import pytest

from benchmark import harness, trace
from benchmark.readers import pass_time, scope_time, unscoped_time

HERE = os.path.dirname(os.path.abspath(__file__))
MODULE, WINDOW = "train_window", "window_fwd_bwd"
LAYERS = ["h", "ln_f", "jit(blocked_lm_head_loss)"]

# the eight cells of PR 35, and which of them each of its metrics listed
# then: a later cell joins a list, and none of these leaves one
CELLS_35 = [
    "gpt2-large.train-seq1024", "bert-large.pretrain-seq128",
    "gpt2-large.zero2-dp4", "nemotron3-super-120b-a12b.train-seq8192",
    "gpt2-large.train-accum1", "qwen3-next-80b-a3b.train-seq16384",
    "bert-large.pretrain-seq512", "ouro-2.6b.train-seq8192"]
DENSE, ALL = (0, 1, 2, 4, 6), tuple(range(8))
NEW = {
    "dense_attn_ms.train": ("scope_time", DENSE),
    "dense_ffn_ms.train": ("scope_time", DENSE),
    "embed_ms.train": ("scope_time", ALL),
    "head_loss_ms.train": ("scope_time", ALL[:7]),
    "stack_norms_ms.train": ("scope_time", (3, 5, 7)),
    "forward_ms.train": ("pass_time", ALL), "recompute_ms.train": ("pass_time", ALL),
    "backward_ms.train": ("pass_time", ALL), "unscoped_ms.train": ("unscoped_time", ALL),
    "grad_accum_ms.train": ("scope_time", (0, 1, 2, 3, 5, 6, 7)),
    "stack_scan_ms.train": ("unscoped_time", DENSE + (7,)),
}


@pytest.fixture
def ctx():
    return {"trace": trace.Trace(
        None, path=os.path.join(HERE, "phases.xplane.pb"))}


def notes(capsys, kind):
    return [line for line in map(json.loads, filter(
        None, capsys.readouterr().out.splitlines()))
        if line.get("note") == kind]


def test_the_three_passes_add_up_to_the_scope(ctx, capsys):
    whole = scope_time.read(ctx, None, MODULE, WINDOW)
    parts = {which: pass_time.read(ctx, None, MODULE, WINDOW, which)
             for which in pass_time.PASSES}
    assert all(value > 0 for value in parts.values())
    assert sum(parts.values()) == pytest.approx(whole, rel=1e-9)
    # the recorded recipe keeps the products: remat runs elementwise work only
    assert parts["recompute"] < 0.1 * parts["forward"] < parts["backward"]
    # inside the scan's blocks too, and the note says the same, once a run
    blocks = [pass_time.read(ctx, None, MODULE, "h", w) for w in pass_time.PASSES]
    assert sum(blocks) == pytest.approx(
        scope_time.read(ctx, None, MODULE, "h"), rel=1e-9)
    (said,) = notes(capsys, "passes_by_scope")
    assert said["runs"] == 3 and said["passes"] == list(pass_time.PASSES)
    assert said["ms_per_run"][WINDOW] == pytest.approx(
        [parts[w] for w in pass_time.PASSES])
    # the update is derived by nobody: all of it reads as forward
    assert said["ms_per_run"]["window_optimizer_update"][1:] == [0.0, 0.0]
    assert "dense_attn" not in said["ms_per_run"]    # recorded before PR 35


def test_a_scope_nobody_opened_reads_nothing(ctx, capsys):
    assert pass_time.read(ctx, None, MODULE, "dense_attn", "forward") is None
    assert pass_time.read(ctx, None, "no_such_program", WINDOW, "forward") is None
    assert unscoped_time.read(ctx, None, "no_such_program", WINDOW, []) is None
    with pytest.raises(ValueError):
        pass_time.read(ctx, None, MODULE, WINDOW, "sideways")
    capsys.readouterr()
    assert unscoped_time.read(ctx, None, MODULE, "stack_scan") is None
    assert notes(capsys, "unscoped_ops")[0]["reason"] == \
        "no operation under stack_scan"


def test_a_program_without_the_names_reads_nothing_left(ctx, capsys):
    """The trace was recorded BEFORE any scope of ``benchmark/scopes/`` was
    opened, as a warm compile cache's program is after a change of names:
    the remainder of scopes that are nowhere is no number, and the note says
    why, with the rows that would have been counted."""
    assert unscoped_time.read(ctx, None, MODULE, WINDOW) is None
    said = notes(capsys, "unscoped_ops")[0]
    assert said["reason"] == ("no operation under window_fwd_bwd carries any "
                              "of the scopes to leave out")
    assert said["ms_per_run"] == pytest.approx(
        scope_time.read(ctx, None, MODULE, WINDOW), rel=1e-9)
    assert len(said["largest"]) == unscoped_time.TOP
    # one scope that IS there is enough
    assert unscoped_time.read(ctx, None, MODULE, WINDOW, ["dense_attn", "h"]) > 0
    assert notes(capsys, "unscoped_ops")[0]["reason"] is None


def test_the_scopes_are_read_from_every_file_of_the_folder(tmp_path, monkeypatch):
    named = pass_time.listed()
    assert {"dense_attn", "grad_accum"} <= set(named["layers"])
    assert named["around"] == ["stack_scan"] and "loop_pass" in named["other"]
    everything = [s for kind in named.values() for s in kind]
    assert len(everything) == len(set(everything))
    # a later PR's file joins the lists; a scope stays where it was first
    # put. Shown on a stand-in folder against ITSELF: the real folder's lists
    # grow with every PR that opens a scope
    folder = tmp_path / "scopes"
    folder.mkdir()
    (folder / "program.json").write_text(json.dumps(
        harness.load_json("scopes", "program.json")))
    monkeypatch.setattr(harness, "HERE", str(tmp_path))
    alone = pass_time.listed()
    (folder / "zz_later.json").write_text(json.dumps(
        {"layers": ["new_mixer", "embed"], "other": ["dense_attn"]}))
    later = pass_time.listed()
    assert later["layers"] == alone["layers"] + ["new_mixer"]
    assert later["around"] == alone["around"] and later["other"] == alone["other"]


def test_the_layers_and_what_is_left_add_up_to_the_window(ctx, capsys):
    whole = scope_time.read(ctx, None, MODULE, WINDOW)
    layers = [scope_time.read(ctx, None, MODULE, name) for name in LAYERS]
    left = unscoped_time.read(ctx, None, MODULE, WINDOW, LAYERS)
    assert all(value > 0 for value in layers) and 0 < left < 0.25 * whole
    assert sum(layers) + left == pytest.approx(whole, rel=1e-9)
    # with no layer named, everything under the window is left
    assert unscoped_time.read(ctx, None, MODULE, WINDOW, []) == \
        pytest.approx(whole, rel=1e-9)
    said = notes(capsys, "unscoped_ops")[0]
    assert said["ms_per_run"] == pytest.approx(left)
    rows = said["largest"]
    assert len(rows) == unscoped_time.TOP
    assert [r["ms"] for r in rows] == sorted((r["ms"] for r in rows), reverse=True)
    # what the two-layer model leaves outside its blocks: the token table's
    # scatter-add (backward) and gather (forward), then the scan's copies
    assert (rows[0]["pass"], rows[0]["opcode"]) == ("backward", "fusion kCustom")
    assert rows[0]["path"].endswith("/transformer/scatter-add:")
    assert rows[1]["path"].endswith("/transformer/gather:")
    assert all(len(r["path"]) <= 120 and f"/{WINDOW}/" in r["path"] or
               len(r["path"]) == 120 for r in rows)
    assert not any(f"/{name}/" in r["path"] for r in rows for name in LAYERS)


def test_a_scope_around_the_layers_has_its_own_time(ctx):
    """``stack_scan_ms.train``'s arithmetic, with the recorded ``transformer``
    module standing in for the scan's scope: the layers, what is left under
    the scope around them and what is left under the window add up."""
    whole = scope_time.read(ctx, None, MODULE, WINDOW)
    parts = LAYERS + ["transformer"]
    layers = [scope_time.read(ctx, None, MODULE, name) for name in LAYERS[:2]]
    head = scope_time.read(ctx, None, MODULE, LAYERS[2])     # outside it
    own = unscoped_time.read(ctx, None, MODULE, "transformer", parts)
    left = unscoped_time.read(ctx, None, MODULE, WINDOW, parts)
    assert 0 < own == pytest.approx(
        scope_time.read(ctx, None, MODULE, "transformer") - sum(layers))
    assert sum(layers) + head + own + left == pytest.approx(whole, rel=1e-9)
    assert left < unscoped_time.read(ctx, None, MODULE, WINDOW, LAYERS)


def test_the_rows_are_the_hand_tools(ctx):
    """``tools/window_ops.py`` prints ``op_rows`` of the whole scope."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "window_ops", os.path.join(harness.ROOT, "tools", "window_ops.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    table = tool.rows(os.path.join(HERE, "phases.xplane.pb"), WINDOW, MODULE)
    assert table["runs"] == 3
    assert table["total_ms"] == pytest.approx(
        scope_time.read(ctx, None, MODULE, WINDOW), rel=1e-9)
    first = table["ops"][0]
    assert set(first) == {"name", "calls", "ms", "pass", "opcode", "category",
                          "hlo", "scope"}
    # a flash kernel leads at two layers and 1,024 positions, then matmuls
    assert (first["opcode"], first["category"]) == ("custom-call", "custom-call")
    assert first["name"].startswith("flash_")
    assert "convolution fusion" in {row["category"] for row in table["ops"][:8]}


@pytest.mark.parametrize("path, which", [
    ("jit(train_window)/window_fwd_bwd/while/body/closed_call/"
     "jvp(GPT2LMHeadModel)/transformer/while/body/closed_call/h/dense_ffn/"
     "dot_general:", "forward"),
    ("jit(train_window)/window_fwd_bwd/while/body/closed_call/"
     "transpose(jvp(GPT2LMHeadModel))/transformer/while/body/closed_call/h/"
     "checkpoint/dense_ffn/dot_general:", "backward"),
    ("jit(train_window)/window_fwd_bwd/while/body/closed_call/"
     "transpose(jvp(GPT2LMHeadModel))/transformer/while/body/closed_call/h/"
     "checkpoint/rematted_computation/dense_ffn/tanh:", "recompute"),
    # a checkpoint inside a checkpoint's backward (the hybrid stack's path)
    ("jit(train_window)/window_fwd_bwd/while/body/closed_call/"
     "transpose(jvp(HybridCausalLM))/model/jvp(HybridCausalLM)/model/"
     "checkpoint/rematted_computation/stack_norms/mul:", "recompute"),
    ("jit(train_window)/window_fwd_bwd/while:", "forward"),
    ("", "forward"),
])
def test_the_pass_is_read_off_the_path(path, which):
    assert pass_time.which_pass(path) == which


@pytest.mark.parametrize("hlo, opcode", [
    ("%fusion.662 = bf16[2048,256]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[512,256]"
     "{1,0:T(8,128)(2,1)} %get-tuple-element.3907), kind=kCustom, "
     "calls=%fused_computation.4", "fusion kCustom"),
    ("%slice_reduce_fusion = (u32[2]{0:T(128)S(1)}, u32[2]{0:T(128)S(1)}) "
     "fusion(u32[2,2]{1,0:T(2,128)} %key.1), kind=kLoop, calls=%f", "fusion kLoop"),
    ("%copy-done.74 = s32[1,8,2,128]{3,2,1,0:T(2,128)} copy-done((s32[1,8,2,"
     "128]{3,2,1,0:T(2,128)}, u32[]{:S(2)}) %copy-start.74)", "copy-done"),
    ("%while.338 = (s32[]{:T(128)}, /*index=5*/bf16[2,768]{1,0}) while(%t), "
     "condition=%c, body=%b", "while"),
])
def test_the_opcode_is_read_off_the_hlo_text(hlo, opcode):
    assert unscoped_time.opcode(hlo) == opcode


def test_every_new_metric_has_its_file_its_reader_and_its_cells():
    bench = harness.load_benchmark()
    entries = {e["name"]: e for e in bench["per_layer"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    for name, (reader, at_pr_35) in NEW.items():
        entry = entries[name]
        assert (entry["unit"], entry["better"], entry["source"],
                entry["moves"]) == ("ms", "lower", "device_trace",
                                    "train_tokens_per_s_per_chip")
        assert {CELLS_35[i] for i in at_pr_35} <= set(entry["workloads"])
        assert set(entry["workloads"]) <= set(cells)
        spec = harness.load_json("layer_metrics", name + ".json")
        assert set(spec) == {"reader", "args"} and spec["reader"] == reader
        assert spec["args"]["module"] == MODULE
        assert hasattr(harness.plugin("readers", reader), "read")
    # appended together and in this order; later PRs' metrics follow them
    first = list(entries).index("dense_attn_ms.train")
    assert list(entries)[first:first + len(NEW)] == list(NEW)
    dense = {c for c in cells if c.startswith(("gpt2-large.", "bert-large."))}
    assert set(entries["dense_attn_ms.train"]["workloads"]) == dense
    assert set(entries["stack_norms_ms.train"]["workloads"]) == set(cells) - dense
    assert set(cells) - set(entries["head_loss_ms.train"]["workloads"]) == {
        "ouro-2.6b.train-seq8192"}       # it has loop_head_loss_ms.train
    # what is left is left of EVERY model-level scope a cell's metrics name,
    # of the engine's gradient sum and of the scans (benchmark/scopes/)
    named = {harness.load_json("layer_metrics", n + ".json")["args"]["scope"]
             for n, e in entries.items()
             if harness.load_json("layer_metrics", n + ".json")["reader"]
             == "scope_time" and e["layer"] in ("model blocks", "training engine")}
    listed = pass_time.listed()
    # (ONE listed layer has no metric of its own: PR 44's mtp_proj)
    assert named - set(listed["other"]) == set(listed["layers"]) - {"mtp_proj"}
    assert "mtp_proj" in listed["layers"]
    for name, under in [("unscoped_ms.train", WINDOW),
                        ("stack_scan_ms.train", "stack_scan")]:
        assert harness.load_json("layer_metrics", name + ".json")["args"] == {
            "module": MODULE, "under": under}
    assert listed["around"] == ["stack_scan"]
