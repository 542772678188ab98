"""Speculative decoding beside the fused Pallas decode path
(docs/inference.md "Fused decode attention" / "Speculative decoding")
on a toy GPT-2: the one place the two decode paths meet directly. The
unit suites pin each against the XLA truth alone.

  - PARITY: the speculative engine's greedy tokens are bitwise those of
    a FUSED non-speculative paged engine over a mixed workload with a
    mid-flight join;
  - ACCEPTANCE > 0: the draft is the target's first block and the
    target's upper block is zero-residual, so the pair agrees by
    construction;
  - NO RECOMPILES: steps whose bursts commit different token counts add
    no XLA backend compile after warm-up;
  - the infer/spec_* streams move.
"""

import jax
import numpy as np

import deepspeed_tpu
from _common import agreeing_draft_target, prompt, toy_gpt2


def test_speculative_engine_matches_the_fused_engine():
    cfg, model, params = toy_gpt2(np.random.default_rng(0))
    # zero-residual upper block => target logits == 1-layer draft logits
    tgt, dmodel, dparams = agreeing_draft_target(
        cfg, jax.tree_util.tree_map(np.asarray, params), draft_layers=1
    )
    block = {"max_batch_slots": 4, "max_seq_len": 48, "prefill_len": 32,
             "kv_block_size": 8, "sampling": {"greedy": True}}
    e_ref = deepspeed_tpu.init_inference(
        model=model, model_parameters=tgt,
        config={"inference": dict(block, fused_decode=True)},
    )
    e_spec = deepspeed_tpu.init_inference(
        model=model, model_parameters=tgt,
        config={"inference": dict(block, speculative={"k": 3})},
        draft_model=dmodel, draft_parameters=dparams,
    )
    try:
        # PARITY over a mixed workload
        prompts = [prompt(9, 1), prompt(5, 2), prompt(13, 3)]
        ref_out = e_ref.generate(prompts, max_new_tokens=10)
        spec_out = e_spec.generate(prompts, max_new_tokens=10)
        assert spec_out == ref_out, "speculative greedy output diverged"

        # NO RECOMPILES across varied acceptance lengths + a mid-flight join
        recompiles = e_spec.metrics.counter("jax/recompiles")
        warm = recompiles.value
        assert warm > 0
        r1 = e_spec.submit(prompt(8, 4), max_new_tokens=12)
        r1r = e_ref.submit(prompt(8, 4), max_new_tokens=12)
        e_spec.scheduler.step()
        e_ref.scheduler.step()
        r2 = e_spec.submit(prompt(7, 5), max_new_tokens=8)
        r2r = e_ref.submit(prompt(7, 5), max_new_tokens=8)
        e_spec.scheduler.run_until_idle()
        e_ref.scheduler.run_until_idle()
        assert r1.result(0) == r1r.result(0)
        assert r2.result(0) == r2r.result(0)
        spec_recompiles = int(recompiles.value - warm)
        assert spec_recompiles == 0, (
            f"{spec_recompiles} recompiles across acceptance lengths"
        )

        # ACCEPTANCE > 0 and the spec_* streams move
        snap = e_spec.metrics.snapshot()
        assert snap["infer/spec_proposed"] > 0, "no proposals counted"
        assert snap["infer/spec_accepted"] > 0, "zero draft tokens accepted"
        assert snap["infer/spec_acceptance_rate"] > 0, (
            "acceptance rate stayed 0"
        )
        # multi-token commits: fewer decode steps than tokens generated
        steps = snap["infer/token_latency_ms/count"]
        tokens = snap["infer/tokens_generated"]
        assert steps < tokens, (steps, tokens)
        assert e_ref.metrics.gauge("infer/fused_decode").value == 1
    finally:
        e_ref.close()
        e_spec.close()
