"""The device scopes that the benchmark's per-layer metrics read (PR 35;
docs/observability.md, "Device scopes"), on the fused ``train_window`` of each
family at its benchmark cell's recipe and toy size, compiled for the CPU:
only a compiled program has whole paths (the lowered module names an
operation inside the function that holds it; XLA joins caller and callee when
it inlines). The persistent compile cache is off around them: it leaves
metadata out of its key, so a program can come back under the names of
whoever wrote the entry
(``test_a_cached_program_keeps_the_names_it_was_compiled_with``).
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, program
from benchmark.readers import pass_time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# ``benchmark/scopes/*.json``: the parts of a window (``unscoped_ms.train``
# counts what none of them holds) and the scans around a stack's layers
LAYER_SCOPES = tuple(pass_time.listed()["layers"])
AROUND = tuple(pass_time.listed()["around"])

ALL = {"forward", "recompute", "backward"}
ONCE = {"forward", "backward"}          # nothing runs it again: so the
# blocked head loss, whose forward takes its gradient (ops/cross_entropy.py)
SUM = {"forward"}                       # the engine's: autodiff never sees it

# family -> (its cell, {scope: the passes its operations must show})
FAMILIES = {
    "gpt2": ("gpt2-large.train-accum1", {
        "dense_attn": ALL, "dense_ffn": ALL, "embed": ONCE,
        "head_loss": ONCE, "stack_scan": ALL}),
    "bert": ("bert-large.pretrain-seq128", {
        "dense_attn": ALL, "dense_ffn": ALL, "embed": ONCE,
        "head_loss": ONCE, "stack_scan": ALL, "grad_accum": SUM}),
    # the latent experts' routed sum is kept with the plan (PR 37): remat
    # runs their kernels no second time
    "nemotron": ("nemotron3-super-120b-a12b.train-seq8192", {
        "stack_norms": ALL, "embed": ONCE, "head_loss": ONCE,
        "mamba_mixer": ALL, "attn_mixer": ALL, "moe_route": ALL,
        "moe_experts": ONCE, "moe_shared": ALL, "grad_accum": SUM}),
    # the gated experts keep their plan and outputs by name: no second run
    "qwen3-next": ("qwen3-next-80b-a3b.train-seq16384", {
        "stack_norms": ALL, "embed": ONCE, "head_loss": ONCE,
        "gdn_mixer": ALL, "attn_mixer": ALL, "moe_route": ALL,
        "moe_experts": ONCE, "moe_shared": ALL, "grad_accum": SUM}),
    # two attention kinds in one stack, each under its own scope INSIDE
    # attn_mixer (``other`` in benchmark/scopes/attention_kinds.json): the
    # layers stay disjoint; ten sublayers unrolled, no scan, and no remat
    # (five layers' activations fit): nothing in the window runs twice
    "laguna": ("laguna-s-2.1.train-seq8192", {
        "stack_norms": ONCE, "embed": ONCE, "head_loss": ONCE,
        "attn_mixer": ONCE, "attn_full": ONCE, "attn_window": ONCE,
        "swiglu_ffn": ONCE, "moe_route": ONCE, "moe_experts": ONCE,
        "moe_shared": ONCE, "grad_accum": SUM}),
    # the latent mixers under attn_mla INSIDE attn_mixer and the module's
    # layer under mtp (both ``other`` in benchmark/scopes/latent_mtp.json:
    # the layers inside them stay the layers they are); the module's
    # projection and its pass through the shared head are layers of their
    # own (the projection outside every checkpoint: nothing runs it again);
    # a prefix and a scanned run under the siblings' remat policy
    "joyai": ("joyai-llm-flash.train-seq8192", {
        "stack_norms": ALL, "embed": ONCE, "head_loss": ONCE,
        "mtp_head_loss": ONCE, "mtp_proj": ONCE, "attn_mixer": ALL,
        "swiglu_ffn": ALL, "moe_route": ALL, "moe_experts": ONCE,
        "moe_shared": ALL, "stack_scan": ALL, "grad_accum": SUM}),
    "ouro": ("ouro-2.6b.train-seq8192", {
        "stack_norms": ALL, "embed": ONCE, "loop_head_loss": ONCE,
        "attn_mixer": ALL, "swiglu_ffn": ALL, "loop_pass": ALL,
        "exit_gate": ONCE, "stack_scan": ALL, "grad_accum": SUM}),
}


def operations(text):
    """[(opcode, op_name)] of every instruction of a compiled program that
    carries a path (``jit(train_window)/window_fwd_bwd/.../dot_general``),
    those inside a fusion's computation too."""
    return re.findall(
        r'^\s+(?:ROOT )?%[\w.\-]+ = .*? ([a-z\-]+)\(.*op_name="([^"]*)"',
        text, re.M)


@pytest.fixture(autouse=True, scope="module")
def _no_persistent_cache():
    """Names are read off compiled programs here, and a cached program keeps
    the names of whoever wrote the entry (the last test of this file)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def lowered_window(cell_name):
    """The engine's own fused window at the cell's recipe and toy size."""
    import chip_smoke
    import deepspeed_tpu
    from deepspeed_tpu.parallel.mesh import build_mesh

    cell, config, _bench = harness.load_cell(cell_name)
    cell.update(cell.get("toy", {}))
    size = harness.sizes(config, True)
    ref = harness.plugin("reference", config["reference"])
    gen = harness.plugin("traffic", cell["traffic"]["generator"])
    recipe = config["train"]
    # program.build_train without its compile_cache block: no test arms the
    # persistent cache for the tests that share its process
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=program.model(config, size, recipe["model_args"]),
        model_parameters=program.to_tree(config, {
            k: np.zeros(s, np.float32) for k, s in ref.shapes(size).items()}),
        config_params=dict(
            recipe["engine"], train_micro_batch_size_per_gpu=cell["micro"],
            gradient_accumulation_steps=cell["accum"]),
        mesh=build_mesh(devices=jax.devices()[:cell["chips"]]))
    try:
        batch = program.feed(config, next(gen.micro_batches(0, cell, size)))
        return chip_smoke.lower_train_window(jax, engine, batch, cell["accum"])
    finally:
        program.close_train(engine)


@pytest.fixture(scope="module")
def window_ops():
    kept = {}

    def of(family):
        if family not in kept:
            kept[family] = [
                (opcode, path) for opcode, path in operations(
                    lowered_window(FAMILIES[family][0]).compile().as_text())
                if "/window_fwd_bwd/" in path]
        return kept[family]

    return of


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_each_scope_shows_the_passes_it_runs_in(window_ops, family):
    """Every scope of the family carries operations of the forward pass,
    of the backward pass and, where the recipe's checkpoint runs it again,
    of the recomputation: the three words ``pass_time`` splits a window by."""
    ops = window_ops(family)
    assert len(ops) > 500
    for scope, expected in FAMILIES[family][1].items():
        found = {pass_time.which_pass(path) for _op, path in ops
                 if f"/{scope}/" in path}
        assert found == expected, (scope, found)
    # and no scope of another family strays in
    strangers = set(LAYER_SCOPES + AROUND) - set(FAMILIES[family][1])
    assert not [p for _op, p in ops if any(f"/{s}/" in p for s in strangers)]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_no_product_lies_outside_every_layers_scope(window_ops, family):
    """``unscoped_ms.train`` is the engine's and the scans' (the gradient
    sum, casts, the stacks' slices): no matrix product may read there."""
    products = [path for _op, path in window_ops(family)
                if path.endswith(("/dot_general", "/conv_general_dilated"))]
    assert len(products) >= 10
    outside = [path for path in products
               if not any(f"/{s}/" in path for s in LAYER_SCOPES)]
    assert outside == []


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_the_layer_scopes_are_disjoint(window_ops, family):
    """The model-level scopes' metrics and ``unscoped_ms.train`` add up to
    ``fwd_bwd_ms.train`` only if no operation carries two of them."""
    twice = [path for _op, path in window_ops(family)
             if sum(f"/{s}/" in path for s in LAYER_SCOPES) > 1]
    assert twice == []


@pytest.mark.parametrize("family", sorted(
    f for f, (_cell, scopes) in FAMILIES.items() if "stack_scan" in scopes))
def test_the_scans_own_time_is_slices_and_writes(window_ops, family):
    """``stack_scan_ms.train`` is what lies under ``stack_scan`` and outside
    every layer: the loop, the slices out of the stacked parameters and
    residuals, the writes into them. Every product of the stack's layers
    lies inside (the compiler hoists a mask or a cast out of the loops, and
    such an operation keeps its layer's name without the scan's)."""
    ops = window_ops(family)
    own = {path.rsplit("/", 1)[1] for _op, path in ops
           if "/stack_scan/" in path
           and not any(f"/{s}/" in path for s in LAYER_SCOPES)}
    assert {"while", "dynamic_slice", "dynamic_update_slice"} <= own
    stack = set(FAMILIES[family][1]) - {
        "embed", "head_loss", "loop_head_loss", "exit_gate", "grad_accum",
        "stack_scan", "mtp_head_loss", "mtp_proj"}
    outside = [path for _op, path in ops if "/stack_scan/" not in path
               and path.endswith("/dot_general")
               and any(f"/{s}/" in path for s in stack)]
    if family != "joyai":
        assert not outside
        return
    # a prefix before the scanned run and a module after the final norm: the
    # leading dense layer's products (the only ``swiglu_ffn``) and the
    # module's lie outside the scan, the five sparse layers' inside
    assert outside and all(
        "/mtp/" in path or "/swiglu_ffn/" in path or "/attn_mixer/" in path
        for path in outside)
    assert not [path for _op, path in ops
                if "/swiglu_ffn/" in path and "/stack_scan/" in path]
    assert [path for _op, path in ops if "/stack_scan/" in path
            and "/moe_experts/" in path and path.endswith("/dot_general")]


def test_the_latent_and_module_scopes_enclose_what_they_name(window_ops):
    """``attn_mla`` lies inside ``attn_mixer`` on every operation that carries
    it, in the stack and in the module; ``mtp`` holds a latent mixer, the
    experts, the norms and its own projection, and neither head pass; the
    module's head pass reads under ``mtp_head_loss`` and not ``head_loss``."""
    ops = [path for _op, path in window_ops("joyai")]
    latent = [p for p in ops if "/attn_mla/" in p]
    assert latent and all("/attn_mixer/attn_mla/" in p for p in latent)
    assert any("/stack_scan/" in p for p in latent)
    module = [p for p in ops if "/mtp/" in p]
    for scope in ("attn_mixer/attn_mla", "moe_route", "moe_experts",
                  "moe_shared", "stack_norms", "mtp_proj"):
        assert any(f"/mtp/{scope}/" in p for p in module), scope
    assert not [p for p in module if "head_loss/" in p or "/stack_scan/" in p]
    assert all("/mtp/mtp_proj/" in p for p in ops if "/mtp_proj/" in p)
    heads = [p for p in ops if "head_loss/" in p]
    assert {("/mtp_head_loss/" in p, "/head_loss/" in p) for p in heads} == {
        (True, False), (False, True)}


def test_every_scope_the_program_opens_is_in_the_catalog():
    """The catalog of docs/observability.md names every ``jax.named_scope``
    of the program's source (a literal, or the ``scope=`` a layer kind's
    ``AttentionSpec`` hands ``attention_mixer``), and ``benchmark/scopes/``
    (what the readers split a window by) lists none that the program does
    not open. A new scope needs its row in the catalog; it joins the readers'
    lists by a new file under ``benchmark/scopes/``, and reads as unscoped
    until then."""
    opened = set()
    for folder, _dirs, files in os.walk(os.path.join(REPO, "deepspeed_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(folder, name)) as fd:
                    opened |= set(re.findall(
                        r'(?:jax\.named_scope\(\s*|\bscope=)"(\w+)"',
                        fd.read()))
    with open(os.path.join(REPO, "docs", "observability.md")) as fd:
        catalog = fd.read().split("## Device scopes", 1)[1]
    assert not [s for s in opened if f"`{s}`" not in catalog]
    listed = pass_time.listed()
    assert {s for kind in listed.values() for s in kind} <= opened
    assert not set(listed["layers"]) & set(listed["around"])


def test_a_cached_program_keeps_the_names_it_was_compiled_with(tmp_path):
    """Why the cache is off in this file, and why a trace taken after a
    change of scope names needs a cold compile cache: the cache's key leaves
    metadata out, so the entry a program without the scope wrote answers for
    the program with it, under the old names."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def program_under(scope):
        def fn(x):
            with jax.named_scope(scope):
                return jnp.tanh(x @ x) * 3.0

        return jax.jit(fn).lower(jnp.ones((64, 64)))

    was = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir", "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    cc.reset_cache()
    try:
        first = program_under("scope_before").compile().as_text()
        second = program_under("scope_after")
        assert "/scope_after/" in second.as_text(debug_info=True)
        compiled = second.compile().as_text()
    finally:
        for key, value in was.items():
            jax.config.update(key, value)
        cc.reset_cache()
    assert "/scope_before/" in first
    assert "/scope_before/" in compiled and "/scope_after/" not in compiled
