"""What the drills under tests/drills/ share: the toy GPT-2 most of them
serve, the tiny worker spec the subprocess and node fleets build from,
the node-agent launcher, and a few one-liners.

A drill drives one vertical slice end to end on the CPU (real engines,
real subprocesses, real TCP) and asserts on what came out. The unit
suites next door pin each layer alone; a drill keeps only what no unit
test sees: the layers together.

No directory under tests/ is a package, so a module's base name must be
the only one of its kind under tests/: pytest refuses to collect a second
test_door.py. Hence test_door_sse.py and test_zero3_ckpt.py here.
"""

import copy
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def toy_gpt2(rng, **overrides):
    """A 2-layer, 32-wide GPT-2 with random weights: ``(cfg, model,
    params)``. ``rng`` is the drill's own numpy generator; the example
    ids are drawn from it, so the prompts a drill draws afterwards do
    not depend on which helper built the model."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import GPT2Config, GPT2LMHeadModel

    kw = dict(vocab_size=128, n_positions=64, n_embd=32, n_layer=2,
              n_head=4, dropout=0.0, use_flash=False)
    kw.update(overrides)
    cfg = GPT2Config(**kw)
    model = GPT2LMHeadModel(cfg)
    ids0 = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 8)), jnp.int32)
    params = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        ids0, ids0,
    )["params"]
    return cfg, model, params


def prompt(n, seed, vocab=128):
    return [int(t) for t in np.random.default_rng(seed).integers(0, vocab, n)]


def worker_spec(n_positions=32, **inference):
    """The tiniest real GPT-2 a worker process or node agent can build
    from a spec (serving/worker.py:build_engine_from_spec): one layer,
    16 wide, greedy."""
    block = {"max_batch_slots": 2, "max_seq_len": 24, "prefill_len": 8,
             "sampling": {"greedy": True}}
    block.update(inference)
    return {
        "model": {"vocab_size": 64, "n_positions": n_positions, "n_embd": 16,
                  "n_layer": 1, "n_head": 2, "use_flash": False},
        "init_seed": 0,
        "config": {"inference": block},
    }


def stub_answer(prompt_ids, n):
    """StubWorkerEngine's answer, a pure function of the prompt: the
    parity reference of the stub fleets needs no clean run."""
    return [(prompt_ids[-1] + j + 1) % 1000 for j in range(n)]


def telemetry_block(root, job_name, **extra):
    block = {
        "enabled": True,
        "output_path": os.path.join(str(root), "telemetry"),
        "job_name": job_name,
        "watchdog": {"enabled": False},
    }
    block.update(extra)
    return block


def wait_for(predicate, timeout, what):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.02)
    assert predicate(), what


def child_env():
    """The environment of a child this directory starts itself: the
    checkout first on PYTHONPATH, whatever the caller's directory."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (REPO, env.get("PYTHONPATH")) if p
    )
    return env


def launch_node(node_id, engine_spec, replicas=("r0",), lease_secs=10.0,
                resume_grace_secs=10.0, config=None):
    """Spawn one ``python -m deepspeed_tpu.serving.node`` subprocess and
    block on its stdout 'listening' announcement (printed only after
    every engine is built — a connecting client never races an
    initializing model). ``config`` is the node-level spec config block
    (e.g. a telemetry.tracing arm for the hub's drain_telemetry pulls).
    Returns (proc, (host, port))."""
    spec = {
        "node_id": node_id,
        "replicas": {name: engine_spec for name in replicas},
        "lease_secs": lease_secs,
        "resume_grace_secs": resume_grace_secs,
    }
    if config is not None:
        spec["config"] = config
    proc = subprocess.Popen(
        [sys.executable, "-m", "deepspeed_tpu.serving.node",
         "--spec", json.dumps(spec), "--port", "0"],
        stdout=subprocess.PIPE, stderr=None, text=True,
        env=child_env(), cwd=REPO,
    )
    line = proc.stdout.readline()
    if not line:
        raise RuntimeError(
            f"node {node_id} exited before announcing its port "
            f"(rc {proc.poll()})"
        )
    info = json.loads(line)
    assert info["event"] == "listening", info
    return proc, (info["host"], info["port"])


def open_sse(address, payload, headers=()):
    """POST ``payload`` to the door's /v1/generate over a raw socket and
    return the socket: the drills read the SSE bytes as they arrive."""
    sock = socket.create_connection(tuple(address))
    sock.settimeout(120.0)
    body = json.dumps(payload).encode()
    head = "POST /v1/generate HTTP/1.1\r\nHost: door\r\n"
    head += "".join(f"{name}: {value}\r\n" for name, value in headers)
    head += f"Content-Length: {len(body)}\r\n\r\n"
    sock.sendall(head.encode() + body)
    return sock


def kill(*procs):
    for proc in procs:
        proc.kill()
        proc.wait(30)


def agreeing_draft_target(cfg, params_host, draft_layers):
    """Build a zero-residual agreeing draft/target pair for the
    speculative-decoding drill: zero the residual-path OUTPUT
    projections (attn_ow/output_w + biases) of every layer >=
    ``draft_layers`` in a copy of ``params_host``, so the deep target's
    logits equal a ``draft_layers``-layer truncation's by construction
    (acceptance ceiling 1.0 — the drill exercises the speculative
    MACHINERY, not draft quality). Returns ``(target_params,
    draft_model, draft_params)``; the unit suite's ``_agreeing_pair``
    (tests/unit/test_speculative.py) relies on this exact key set, so a
    residual-path param change must update both."""
    from deepspeed_tpu.models import GPT2Config, GPT2LMHeadModel

    tgt = copy.deepcopy(params_host)
    th = tgt["transformer"]["h"]
    for key in ("attn_ow", "output_w", "attn_ob", "output_b"):
        arr = np.array(th[key])
        arr[draft_layers:] = 0.0
        th[key] = arr
    dcfg = GPT2Config(
        vocab_size=cfg.vocab_size, n_positions=cfg.n_positions,
        n_embd=cfg.n_embd, n_layer=draft_layers, n_head=cfg.n_head,
        dropout=0.0, use_flash=False,
    )
    dmodel = GPT2LMHeadModel(dcfg)
    dparams = copy.deepcopy(tgt)
    dparams["transformer"]["h"] = {
        k: np.array(v)[:draft_layers]
        for k, v in tgt["transformer"]["h"].items()
    }
    return tgt, dmodel, dparams
