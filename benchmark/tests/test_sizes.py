"""``harness.sizes()`` hands program and reference what a published config
holds (ISSUE 49): numbers, strings, lists and dicts. Every number that the
configurations handed over before is handed over unchanged, and every
reference builds the same stack from the new dict as from the old one."""

import json

import pytest

from benchmark import harness

# the configurations that ran under the numbers-only ``sizes()`` (PR 48's
# eight). One added later never did, and is not held to it: add none here.
PARENTS = (
    "bert-large", "gpt2-large", "joyai-llm-flash", "laguna-s-2.1",
    "nemotron3-super-120b-a12b", "ouro-2.6b", "qwen3-next-80b-a3b",
    "sdar-30b-a3b-chat")
PATTERNS = ("pattern", "kinds", "layer_kinds")   # a reference's layer kinds


def numbers_only(config, rehearse):
    """``harness.sizes()`` as it stood before PR 49."""
    out = {k: v for k, v in config.items() if isinstance(v, (int, float))}
    out.update({k: v for k, v in config.get("assumed", {}).items()
                if isinstance(v, (int, float))})
    if rehearse:
        out.update(config["toy"])
    return out


def test_a_list_a_string_and_a_nested_dict_go_through():
    config = {
        "name": "made-up", "source": "none", "reference": "gpt2",
        "hidden_size": 8, "tie": False, "scaling": None,
        "layer_types": ["mamba", "mamba", "attention"],
        "hidden_act": "silu", "hidden_act_why": "a note",
        "rope_parameters": {"full": {"theta": 5e5, "type": "yarn"}},
        "reduced": ["layer_types"], "published": {"layer_types": ["mamba"] * 9},
        "reduced_why": {"layer_types": "a note"}, "deployment": "a note",
        "assumed": {"tile": 256, "tile_why": "a note", "heads": [4, 2, 2],
                    "groups": {"full": 2},
                    "hidden_act": "tanh form, as the first release had it",
                    "weights": "normal(0, 0.02), a note with no _why"},
        "program": {}, "train": {}, "serve": {}, "toy_why": "a note",
        "toy": {"hidden_size": 4, "layer_types": ["mamba", "attention"]},
    }
    assert harness.sizes(config, False) == {
        "hidden_size": 8, "tie": False,
        "layer_types": ["mamba", "mamba", "attention"],
        "hidden_act": "silu",   # a sentence under `assumed` shadows nothing
        "rope_parameters": {"full": {"theta": 5e5, "type": "yarn"}},
        "tile": 256, "heads": [4, 2, 2], "groups": {"full": 2}}
    toy = harness.sizes(config, True)
    assert toy["layer_types"] == ["mamba", "attention"]
    assert toy["hidden_size"] == 4 and toy["heads"] == [4, 2, 2]


@pytest.mark.parametrize("rehearse", [False, True], ids=["real", "rehearsal"])
@pytest.mark.parametrize("name", PARENTS)
def test_every_number_and_every_stack_is_the_parents(name, rehearse):
    """A cell's ``size`` is its configuration's: these eight cover the
    twelve cells that PR 48's BENCHMARK.json had."""
    config = harness.load_json("configs", name + ".json")
    old, new = numbers_only(config, rehearse), harness.sizes(config, rehearse)
    assert {k: v for k, v in new.items()
            if isinstance(v, (int, float))} == old
    assert not any(k in harness.OWN_SECTIONS or k.endswith("_why")
                   for k in new)
    json.dumps(new)   # nothing but what a config file can hold
    # a string is a published one: the sentences under `assumed` stay behind
    toy = config["toy"] if rehearse else {}
    assert all(v == toy.get(k, config.get(k)) for k, v in new.items()
               if isinstance(v, str))
    # the program's config gets the same arguments
    args = config["program"]["config_args"]
    assert ({a: new[k] for a, k in args.items()}
            == {a: old[k] for a, k in args.items()})
    # and the reference builds the same stack
    ref = harness.plugin("reference", config["reference"])
    assert ref.shapes(new) == ref.shapes(old)
    assert list(ref.shapes(new)) == list(ref.shapes(old))
    for fn in PATTERNS:
        if hasattr(ref, fn):
            assert getattr(ref, fn)(new) == getattr(ref, fn)(old), fn


def test_berts_note_on_its_activation_does_not_shadow_the_published_one():
    config = harness.load_json("configs", "bert-large.json")
    assert len(config["assumed"]["hidden_act"]) > 20   # a sentence
    assert harness.sizes(config, False)["hidden_act"] == "gelu"


@pytest.mark.parametrize("rehearse", [False, True], ids=["real", "rehearsal"])
def test_nemotrons_pattern_now_comes_from_the_published_string(rehearse):
    """``reference/nemotron_h.py:pattern`` prefers ``hybrid_override_pattern``
    and fell back to the number ``layer_kinds`` while strings were dropped:
    the file's string is the cut's own 11 letters, so both say the same."""
    from benchmark.reference import nemotron_h

    config = harness.load_json("configs", "nemotron3-super-120b-a12b.json")
    old, new = numbers_only(config, rehearse), harness.sizes(config, rehearse)
    assert "hybrid_override_pattern" not in old
    assert new["hybrid_override_pattern"] == "MEMEMEMEM*E"
    assert nemotron_h.pattern(new) == nemotron_h.pattern(old) == "MEMEMEMEM*E"
    assert len(nemotron_h.pattern(new)) == new["num_hidden_layers"]

