"""The durable control plane (docs/serving.md "Control-plane
durability") over REAL TCP — two stub node agents streaming one token
per 50 ms, a router child process with the journal armed
(router_failover_child.py), four greedy SSE streams with
Idempotency-Keys, then SIGKILL on the router mid-traffic. A fresh
router incarnation recovers the journal, adopts BOTH nodes' live
replicas, and every client retry (Idempotency-Key + Last-Event-ID)
replays its committed prefix and continues the same generation. Pins:
adoption count == 2, zero lost / zero duplicated requests (node-side
submit/complete counters stay at one per request), bitwise greedy
parity against the stub's pure-function answer, event ids continuing
exactly after each client's Last-Event-ID, and >= 1 stream resumed
mid-generation. The journal stays under the test's tmp_path for a
post-mortem."""

import json
import os
import subprocess
import sys

from _common import REPO, child_env, kill, launch_node, open_sse, stub_answer
from deepspeed_tpu.serving.transport import NodeControlClient
from deepspeed_tpu.telemetry.registry import wire_scalars

CHILD = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "router_failover_child.py")
N_TOKENS = 24
PROMPTS = [[7, 100 + i * 17] for i in range(4)]


def _launch_router(child_spec):
    proc = subprocess.Popen(
        [sys.executable, CHILD, child_spec],
        stdout=subprocess.PIPE, stderr=None, text=True,
        env=child_env(), cwd=REPO,
    )
    # the recovery incarnation logs adoption lines to stdout before
    # announcing — skip anything that is not the announce JSON
    while True:
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError(
                f"router child exited before serving (rc {proc.poll()})"
            )
        line = line.strip()
        if line.startswith("{"):
            info = json.loads(line)
            if info.get("event") == "serving":
                return proc, info


def _open_stream(host, port, i, last_event_id=None):
    headers = [("Idempotency-Key", f"drill-key-{i}")]
    if last_event_id is not None:
        headers.append(("Last-Event-ID", last_event_id))
    return open_sse((host, port), {
        "prompt": PROMPTS[i], "max_new_tokens": N_TOKENS, "stream": True,
    }, headers)


def _parse_events(buf):
    """SSE bytes -> ([(event_id, token_index, token)], done|None)."""
    tokens, done, cur_id = [], None, None
    for raw in buf.split(b"\n"):
        if raw.startswith(b"id: "):
            cur_id = int(raw[4:])
        elif raw.startswith(b"data: "):
            payload = json.loads(raw[6:])
            if "t" in payload and "i" in payload:
                tokens.append((cur_id, payload["i"], payload["t"]))
                cur_id = None
            elif "finish_reason" in payload:
                done = payload
    return tokens, done


def test_restarted_router_adopts_sessions_and_streams_resume(tmp_path):
    journal_dir = str(tmp_path / "journal")

    # one token per 50 ms: a 24-token answer is a ~1.2 s generation —
    # a real mid-stream window to crash into. The long resume grace
    # holds each node session (and its finished outbox) across the
    # dead-router window, which includes a jax import in the child.
    stub_spec = {"stub": {"token_delay_secs": 0.05}}
    proc_a, addr_a = launch_node(
        "fa", stub_spec, lease_secs=60.0, resume_grace_secs=120.0,
    )
    proc_b, addr_b = launch_node(
        "fb", stub_spec, lease_secs=60.0, resume_grace_secs=120.0,
    )
    procs = [proc_a, proc_b]
    try:
        nodes = {
            "fa": {"address": f"{addr_a[0]}:{addr_a[1]}", "replicas": ["r0"]},
            "fb": {"address": f"{addr_b[0]}:{addr_b[1]}", "replicas": ["r0"]},
        }
        child_spec = json.dumps({"nodes": nodes, "journal_dir": journal_dir})

        proc_r, info = _launch_router(child_spec)
        procs.append(proc_r)
        assert info["adopted"] == 0, info
        host, port = info["host"], info["port"]
        socks = [_open_stream(host, port, i) for i in range(4)]
        bufs = [b""] * 4
        # read stream 0 until it is demonstrably mid-generation, then
        # crash immediately — the other streams' prefixes are whatever
        # the kernel buffered (possibly nothing; Last-Event-ID is then
        # omitted on their retry and the replay starts at token 0)
        while bufs[0].count(b"event: token") < 3:
            chunk = socks[0].recv(4096)
            assert chunk, "stream 0 ended before 3 tokens"
            bufs[0] += chunk
        proc_r.kill()  # SIGKILL: no shutdown hooks, no journal flush
        proc_r.wait(30)
        for i, sock in enumerate(socks):
            sock.settimeout(10.0)
            try:
                while True:
                    chunk = sock.recv(4096)
                    if not chunk:
                        break
                    bufs[i] += chunk
            except OSError:
                pass
            sock.close()

        prefixes = []
        for i in range(4):
            toks, done = _parse_events(bufs[i])
            assert done is None, (
                f"stream {i} saw a terminal event before the crash", done,
            )
            # the delivered prefix is already bitwise-correct and contiguous
            answer = stub_answer(PROMPTS[i], N_TOKENS)
            assert [t[1] for t in toks] == list(range(len(toks))), toks
            assert all(t[0] == t[1] for t in toks), (
                "id: fields diverged from token indices", toks,
            )
            assert [t[2] for t in toks] == answer[:len(toks)], (i, toks)
            prefixes.append(toks)
        assert len(prefixes[0]) >= 3

        # ---- restart: recover, adopt, resume --------------------------
        proc_r2, info2 = _launch_router(child_spec)
        procs.append(proc_r2)
        assert info2["adopted"] == 2, (
            "the restarted router did not adopt both node replicas",
            info2,
        )
        host2, port2 = info2["host"], info2["port"]
        resumed = 0
        for i in range(4):
            last_id = prefixes[i][-1][0] if prefixes[i] else None
            if last_id is not None:
                resumed += 1
            sock = _open_stream(host2, port2, i, last_event_id=last_id)
            buf = b""
            while b"event: done" not in buf:
                chunk = sock.recv(65536)
                assert chunk, f"resumed stream {i} ended without done"
                buf += chunk
            sock.close()
            toks, done = _parse_events(buf)
            start = (last_id + 1) if last_id is not None else 0
            assert [t[0] for t in toks] == list(range(start, N_TOKENS)), (
                f"stream {i} replay ids did not continue after "
                f"Last-Event-ID {last_id}", toks,
            )
            answer = stub_answer(PROMPTS[i], N_TOKENS)
            full = [t[2] for t in prefixes[i]] + [t[2] for t in toks]
            assert full == answer, (
                f"stream {i} spliced prefix + resume diverged", full,
            )
            assert done is not None and done["tokens"] == answer, done
        assert resumed >= 1, "no stream was resumed mid-generation"

        # zero lost / zero duplicated: each node-side stub replica saw
        # every request exactly once — the adopted sessions carried the
        # generations across the dead-router window with no re-submit
        submitted = completed = 0
        for addr in (addr_a, addr_b):
            snap = NodeControlClient(addr).metrics_snapshot()
            for entries in snap["replicas"].values():
                scalars = wire_scalars(entries)
                submitted += scalars.get("infer/requests_submitted", 0)
                completed += scalars.get("infer/requests_completed", 0)
        assert submitted == 4, (
            f"{submitted} node-side submits for 4 requests — a lost "
            "request was re-placed or a duplicate was generated"
        )
        assert completed == 4, (
            f"{completed} node-side completions for 4 requests"
        )
        segs = [f for f in os.listdir(journal_dir)
                if f.startswith("journal-")]
        assert segs, "the journal directory holds no committed segments"
    finally:
        kill(*procs)
