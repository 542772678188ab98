"""Real multi-process distributed tests — the reference's
``@distributed_test`` spawner analog (reference: tests/unit/common.py:14-100
forks N ranks and init_process_group's NCCL between them).

Here each rank is a REAL subprocess: the launcher's DS_TPU_* environment
drives ``runtime/dist.py``'s ``jax.distributed.initialize`` bootstrap
(exactly the path a pod takes), the ranks rendezvous over localhost, and a
global mesh spans both processes — crossing an actual process boundary,
which the in-process 8-virtual-device harness cannot.

Each rank runs on the CPU backend with one local device, so the global
mesh is 2 devices over 2 processes.
"""

import os
import socket
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # compile-heavy; excluded from `make test-fast`

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RANK_BODY = """
import os, sys
sys.path.insert(0, {repo!r})

import deepspeed_tpu  # auto-runs the DS_TPU_* jax.distributed bootstrap
import jax

WORLD = int(os.environ["DS_TPU_NUM_PROCESSES"])
assert deepspeed_tpu.runtime.dist.is_initialized(), "bootstrap did not run"
assert jax.process_count() == WORLD, jax.process_count()
assert jax.device_count() == WORLD, jax.device_count()
assert jax.local_device_count() == 1

import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

mesh = Mesh(np.array(jax.devices()), ("data",))

# a global array sharded over the processes; psum-style reduction via
# jit: each rank contributes its own slice
rank = jax.process_index()
local = np.full((1, 4), float(rank + 1), np.float32)
garr = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P("data", None)), local, (WORLD, 4)
)
total = jax.jit(
    lambda x: jnp.sum(x, axis=0), out_shardings=NamedSharding(mesh, P())
)(garr)
expect = WORLD * (WORLD + 1) / 2.0
np.testing.assert_allclose(np.asarray(total), np.full((4,), expect))
print(f"RANK{{rank}} OK", flush=True)
"""


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


ENGINE_BODY = """
import os, sys
sys.path.insert(0, {repo!r})

import deepspeed_tpu
import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn

WORLD = int(os.environ["DS_TPU_NUM_PROCESSES"])
assert jax.process_count() == WORLD

from deepspeed_tpu.parallel.mesh import build_mesh

mesh = build_mesh(data_parallel_size=WORLD)  # one device per process


class MLP(nn.Module):
    @nn.compact
    def __call__(self, x, y, train=True):
        h = nn.relu(nn.Dense(32)(x))
        logits = nn.Dense(4)(h)
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


rank = jax.process_index()
rng = np.random.default_rng(0)  # SAME global data on all ranks...
X = rng.normal(size=(8, 8)).astype(np.float32)
Y = (X[:, 0] > 0).astype(np.int32) + 2 * (X[:, 1] > 0).astype(np.int32)
# ...but each rank feeds only ITS slice (DistributedSampler contract)
per = 8 // WORLD
Xl, Yl = X[rank * per:(rank + 1) * per], Y[rank * per:(rank + 1) * per]

model = MLP()
params = model.init({{"params": jax.random.PRNGKey(0)}},
                    jnp.asarray(X), jnp.asarray(Y))["params"]
engine, _, _, _ = deepspeed_tpu.initialize(
    model=model, model_parameters=params, mesh=mesh,
    config_params={{
        "train_batch_size": 8,
        "optimizer": {{"type": "Adam", "params": {{"lr": 1e-2}}}},
        "zero_optimization": {{"stage": 2}},
        "steps_per_print": 10_000,
    }},
    rng_seed=0,
)
assert engine.dp_world_size == WORLD
losses = []
for _ in range(16):
    loss = engine(Xl, Yl)   # per-host slice in, global batch assembled
    engine.backward(loss)
    engine.step()
    losses.append(float(loss))
# the fused train_batch() window must also cross the process boundary
for _ in range(4):
    loss = engine.train_batch([(Xl, Yl)])
    losses.append(float(loss))
assert losses[-1] < 0.5 * losses[0], losses
print(f"RANK{{rank}} ENGINE OK first={{losses[0]:.4f}} last={{losses[-1]:.4f}}",
      flush=True)

# dataloader path: every host sees the same GLOBAL dataset; the loader
# gives each host its slice and _place stitches the global batch
from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader

loader = DeepSpeedDataLoader((X, Y), batch_size=8, mesh=mesh, shuffle=True)
engine.eval()
for bx, by in loader:
    assert bx.shape[0] == 8, bx.shape          # global rows
    assert not bx.is_fully_addressable          # spans all processes
    l_eval = engine(bx, by)
print(f"RANK{{rank}} LOADER OK eval={{float(l_eval):.6f}}", flush=True)
"""


def _run_ranks(tmp_path, body, tag, world=2, extra_env=None, fmt=None):
    port = _free_port()
    script = tmp_path / f"rank_{tag}.py"
    script.write_text(textwrap.dedent(body.format(repo=REPO, **(fmt or {}))))
    procs = []
    for rank in range(world):
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        env["JAX_PLATFORMS"] = "cpu"
        env.update({
            "DS_TPU_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            "DS_TPU_NUM_PROCESSES": str(world),
            "DS_TPU_PROCESS_ID": str(rank),
        })
        env.update(extra_env or {})
        procs.append(
            subprocess.Popen(
                [sys.executable, str(script)],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    for rank, proc in enumerate(procs):
        try:
            out, _ = proc.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            pytest.fail(f"rank {rank} hung (rendezvous deadlock?)")
        outs.append(out)
    for rank, (proc, out) in enumerate(zip(procs, outs)):
        assert proc.returncode == 0, f"rank {rank} failed:\n{out}"
    return outs


@pytest.mark.parametrize("world", [2, 4])
def test_multi_process_engine_training(tmp_path, world):
    """Full engine training across REAL process boundaries (world sizes 2
    and 4, the reference harness's world_size=[1,2,4] grid,
    tests/unit/common.py:14-100): each rank feeds its slice of the global
    batch; ZeRO-2 shards optimizer state across the hosts; unfused steps
    AND the fused train_batch() window run; the loss must drop and agree
    between ranks (it is a replicated global mean)."""
    outs = _run_ranks(tmp_path, ENGINE_BODY, f"engine{world}", world=world)
    lasts, evals = [], []
    for rank, out in enumerate(outs):
        line = [l for l in out.splitlines() if f"RANK{rank} ENGINE OK" in l]
        assert line, out
        lasts.append(line[0].split("last=")[1])
        lline = [l for l in out.splitlines() if f"RANK{rank} LOADER OK" in l]
        assert lline, out
        evals.append(lline[0].split("eval=")[1])
    assert len(set(lasts)) == 1, f"ranks disagree on the loss: {lasts}"
    assert len(set(evals)) == 1, f"ranks disagree on the eval loss: {evals}"


@pytest.mark.parametrize("world", [2, 4])
def test_multi_process_rendezvous_and_collective(tmp_path, world):
    outs = _run_ranks(tmp_path, RANK_BODY, f"collective{world}", world=world)
    for rank, out in enumerate(outs):
        assert f"RANK{rank} OK" in out, out


CKPT_BODY = """
import os, sys
sys.path.insert(0, {repo!r})

import deepspeed_tpu
import jax
import jax.numpy as jnp
import numpy as np
import flax.linen as nn

WORLD = int(os.environ["DS_TPU_NUM_PROCESSES"])
PHASE = os.environ["CKPT_PHASE"]          # "save" | "load"
CKPT_DIR = os.environ["CKPT_DIR"]
assert jax.process_count() == WORLD

from deepspeed_tpu.parallel.mesh import build_mesh

mesh = build_mesh(data_parallel_size=WORLD)


class MLP(nn.Module):
    @nn.compact
    def __call__(self, x, y, train=True):
        h = nn.relu(nn.Dense(32)(x))
        logp = jax.nn.log_softmax(nn.Dense(4)(h))
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


rank = jax.process_index()
rng = np.random.default_rng(0)
X = rng.normal(size=(8, 8)).astype(np.float32)
Y = (X[:, 0] > 0).astype(np.int32) + 2 * (X[:, 1] > 0).astype(np.int32)
per = 8 // WORLD
Xl, Yl = X[rank * per:(rank + 1) * per], Y[rank * per:(rank + 1) * per]

model = MLP()
params = model.init({{"params": jax.random.PRNGKey(0)}},
                    jnp.asarray(X), jnp.asarray(Y))["params"]
engine, _, _, _ = deepspeed_tpu.initialize(
    model=model, model_parameters=params, mesh=mesh,
    config_params={{
        "train_batch_size": 8,
        "optimizer": {{"type": "Adam", "params": {{"lr": 1e-2}}}},
        "bf16": {{"enabled": True}},
        "zero_optimization": {{"stage": 2}},
        "steps_per_print": 10_000,
    }},
    rng_seed=0,
)

if PHASE == "save":
    for _ in range(10):
        loss = engine(Xl, Yl)
        engine.backward(loss)
        engine.step()
    engine.save_checkpoint(CKPT_DIR, tag="elastic")
    # post-save eval loss on a FIXED batch (divisible by both world
    # sizes) is the cross-phase fingerprint
    engine.eval()
    fp = float(engine(X[:4], Y[:4]))
    print(f"RANK{{rank}} SAVE OK steps={{engine.global_steps}} fp={{fp:.6f}}",
          flush=True)
else:
    engine.load_checkpoint(CKPT_DIR, tag="elastic")
    engine.eval()
    fp = float(engine(X[:4], Y[:4]))
    print(f"RANK{{rank}} LOAD OK steps={{engine.global_steps}} fp={{fp:.6f}}",
          flush=True)
    # resumed training must keep working on the NEW world size
    engine.train()
    for _ in range(4):
        loss = engine(Xl, Yl)
        engine.backward(loss)
        engine.step()
    print(f"RANK{{rank}} RESUME OK loss={{float(loss):.4f}}", flush=True)
"""


def test_checkpoint_elastic_dp2_to_dp4(tmp_path):
    """Checkpoint save on a dp2 process mesh, elastic reload on dp4 — the
    reference's elastic DP-resize capability (merge all shards, reshard on
    the current mesh, runtime/checkpointing.py:254+) exercised across REAL
    process boundaries in BOTH directions. The restored model must produce
    the saver's post-save eval loss bit-for-bit on the new world size."""
    ckpt_dir = str(tmp_path / "ckpt")
    os.makedirs(ckpt_dir, exist_ok=True)
    save_outs = _run_ranks(
        tmp_path, CKPT_BODY, "ckpt_save", world=2,
        extra_env={"CKPT_PHASE": "save", "CKPT_DIR": ckpt_dir},
    )
    fps = []
    for rank, out in enumerate(save_outs):
        line = [l for l in out.splitlines() if f"RANK{rank} SAVE OK" in l]
        assert line, out
        assert "steps=10" in line[0], line
        fps.append(line[0].split("fp=")[1])
    assert len(set(fps)) == 1

    load_outs = _run_ranks(
        tmp_path, CKPT_BODY, "ckpt_load", world=4,
        extra_env={"CKPT_PHASE": "load", "CKPT_DIR": ckpt_dir},
    )
    for rank, out in enumerate(load_outs):
        line = [l for l in out.splitlines() if f"RANK{rank} LOAD OK" in l]
        assert line, out
        assert "steps=10" in line[0], line  # counters restored
        # eval fingerprint on the SAME batch must match the saver's —
        # dp2-sharded state was merged and resharded onto dp4 losslessly.
        # (Tolerance, not bit-equality: dp2 and dp4 group the mean's
        # cross-device reduction differently, which may differ in the
        # last ulp.)
        got = float(line[0].split("fp=")[1])
        want = float(fps[0])
        assert abs(got - want) <= 1e-5 * max(abs(want), 1e-6), (line, fps)
        assert f"RANK{rank} RESUME OK" in out, out
