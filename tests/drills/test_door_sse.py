"""The HTTP/SSE front door over a REAL toy GPT-2 fleet (docs/serving.md
"Networked fleet"): the first SSE token event arrives BEFORE generation
completes (the first received chunk carries a token event but no done
event, the rest of the stream arriving afterwards), every token is its
own event, the done payload is bitwise engine.generate's, and an
abandoned stream's slot frees via cancel instead of decoding to its
budget. tests/unit/test_door.py pins the same over a host-side fake
engine; here the engine is the served model."""

import json
import time

import numpy as np
import pytest

import deepspeed_tpu
from _common import open_sse, toy_gpt2, wait_for
from deepspeed_tpu.serving import FleetRouter, HTTPDoor, InProcessReplica

N_TOKENS = 40


@pytest.fixture(scope="module")
def door_fleet():
    rng = np.random.default_rng(3)
    _cfg, model, params = toy_gpt2(rng)
    engine_block = {
        "max_batch_slots": 2, "max_seq_len": 64, "prefill_len": 16,
        "sampling": {"greedy": True},
    }

    def engine_factory():
        return deepspeed_tpu.init_inference(
            model=model, model_parameters=params,
            config={"inference": dict(engine_block)},
        )

    prompt = [int(t) for t in rng.integers(0, 128, 9)]
    single = engine_factory()
    reference = single.generate([prompt], max_new_tokens=N_TOKENS)[0]
    single.close()

    replica = InProcessReplica("0", engine_factory)
    router = FleetRouter([replica], monitor_interval=0.005).start()
    door = HTTPDoor(router)
    try:
        address = door.start()
        yield address, router, replica, prompt, reference
    finally:
        door.shutdown()
        router.shutdown()


def _post_stream(address, prompt):
    sock = open_sse(address, {
        "prompt": prompt, "max_new_tokens": N_TOKENS, "stream": True,
    })
    buf = b""
    while b"event: token" not in buf:
        buf += sock.recv(4096)
    return sock, buf


def test_first_token_event_arrives_before_generation_completes(door_fleet):
    address, router, _replica, prompt, reference = door_fleet
    before = router.metrics.snapshot()
    sock, buf = _post_stream(address, prompt)
    t_first = time.monotonic()
    # the acceptance pin: at first-token time the terminal event has
    # not been sent — 39 decode steps still separate us from done
    assert b"event: done" not in buf, (
        "the whole generation arrived with the first event: "
        "streaming is not incremental"
    )
    while b"event: done" not in buf:
        chunk = sock.recv(4096)
        assert chunk, "stream ended without a done event"
        buf += chunk
    t_done = time.monotonic()
    sock.close()
    assert t_done > t_first
    tokens = [
        json.loads(line[6:])
        for line in buf.split(b"\n")
        if line.startswith(b"data: ") and b'"t"' in line
    ]
    dones = [
        json.loads(line[6:])
        for line in buf.split(b"\n")
        if line.startswith(b"data: ") and b"finish_reason" in line
    ]
    assert len(tokens) == N_TOKENS, (
        f"{len(tokens)} token events for {N_TOKENS} tokens — "
        "not one event per token"
    )
    assert [t["i"] for t in tokens] == list(range(N_TOKENS))
    assert [t["t"] for t in tokens] == reference, (
        "streamed tokens diverged from engine.generate"
    )
    assert dones and dones[0]["tokens"] == reference
    assert dones[0]["finish_reason"] == "max_new_tokens"
    snap = router.metrics.snapshot()
    assert snap["door/stream_ttft_ms/count"] == (
        before.get("door/stream_ttft_ms/count", 0) + 1
    )
    # the door drops the gauge after it has written the done event: the
    # client can read `done` a moment before the count falls
    wait_for(
        lambda: router.metrics.snapshot()["door/open_streams"] == 0, 5.0,
        "the finished stream still counts as open",
    )


def test_abandoned_stream_frees_its_slot_by_cancel(door_fleet):
    address, router, replica, prompt, _reference = door_fleet
    completed = replica.load_snapshot()["requests_completed"]
    disconnects = router.metrics.snapshot().get("door/client_disconnects", 0)
    sock, _buf = _post_stream(address, prompt)
    sock.close()  # walk away mid-generation
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        if replica.load_snapshot()["active_slots"] == 0:
            break
        time.sleep(0.005)
    snap_r = replica.load_snapshot()
    assert snap_r["active_slots"] == 0, "abandoned slot never freed"
    # cancelled, not completed: the scheduler's completion counter did
    # not move for the abandoned request
    assert snap_r["requests_completed"] == completed, snap_r
    snap = router.metrics.snapshot()
    assert snap["door/client_disconnects"] == disconnects + 1
