"""Per-node launcher: decode world info, set JAX distributed env, exec user
script.

Reference analog: deepspeed/pt/deepspeed_launch.py:58-121, which spawned one
subprocess per local GPU with RANK/WORLD_SIZE/CUDA_VISIBLE_DEVICES. The TPU
process model is one process per *host* driving all local chips, so this
launcher spawns a single subprocess and exports:

  DS_TPU_COORDINATOR_ADDRESS  host:port for jax.distributed.initialize
  DS_TPU_NUM_PROCESSES        number of participating hosts
  DS_TPU_PROCESS_ID           this host's process index (node rank)
  DS_TPU_LOCAL_CHIPS          comma-separated chip ids this host may use
                              (mapped to TPU_VISIBLE_CHIPS when restricted)

``deepspeed_tpu.initialize`` (engine dist bootstrap) consumes these to call
``jax.distributed.initialize`` — the mesh replaces NCCL process groups.
"""

import argparse
import base64
import json
import os
import socket
import subprocess
import sys

from ..config.constants import TORCH_DISTRIBUTED_DEFAULT_PORT
from ..utils.logging import logger


def parse_args(args=None):
    parser = argparse.ArgumentParser(description="per-node TPU launcher")
    parser.add_argument("--node_rank", type=str, default="0",
                        help="This node's rank; pdsh substitutes %%n.")
    parser.add_argument("--master_addr", type=str, default="127.0.0.1")
    parser.add_argument(
        "--master_port", type=int,
        default=int(TORCH_DISTRIBUTED_DEFAULT_PORT),
    )
    parser.add_argument("--world_info", type=str, default="e30=",
                        help="base64-encoded {host: [chip, ...]} dict")
    parser.add_argument("user_script", type=str)
    parser.add_argument("user_args", nargs=argparse.REMAINDER)
    return parser.parse_args(args=args)


def decode_world_info(encoded):
    return json.loads(base64.urlsafe_b64decode(encoded).decode())


def resolve_node_rank(args, world_info):
    """pdsh hands every node the same command line; %n (or a hostname
    lookup) recovers the per-node rank."""
    node_rank = args.node_rank
    if node_rank.isdigit():
        return int(node_rank)
    hosts = list(world_info.keys())
    hostname = socket.gethostname()
    for i, h in enumerate(hosts):
        if hostname == h or hostname.split(".")[0] == h.split(".")[0]:
            return i
    raise ValueError(
        f"cannot resolve node rank: hostname {hostname!r} not in world "
        f"info {hosts}"
    )


def build_env(args, world_info, node_rank):
    env = os.environ.copy()
    num_processes = max(len(world_info), 1)
    env["DS_TPU_COORDINATOR_ADDRESS"] = f"{args.master_addr}:{args.master_port}"
    env["DS_TPU_NUM_PROCESSES"] = str(num_processes)
    env["DS_TPU_PROCESS_ID"] = str(node_rank)
    # reference parity: same names the torch ecosystem expects, so user
    # scripts reading RANK/WORLD_SIZE keep working (process-level ranks)
    env["RANK"] = str(node_rank)
    env["WORLD_SIZE"] = str(num_processes)
    env["MASTER_ADDR"] = args.master_addr
    env["MASTER_PORT"] = str(args.master_port)
    hosts = list(world_info.keys())
    if hosts:
        local_chips = world_info[hosts[node_rank]]
        env["DS_TPU_LOCAL_CHIPS"] = ",".join(map(str, local_chips))
        if local_chips:
            # restrict which local chips this process binds
            env.setdefault("TPU_VISIBLE_CHIPS", ",".join(map(str, local_chips)))
    if env.get("DS_TPU_LATENCY_HIDING", "").strip().lower() not in (
        "", "0", "false", "no", "off"
    ):
        # ZeRO-3 collective/compute overlap (runtime/overlap.py): export
        # the latency-hiding scheduler flags BEFORE the training process
        # loads its TPU backend — the only point they are guaranteed to
        # take effect. They go in libtpu's own variable, never XLA_FLAGS
        # (jaxlib aborts on entries it does not register); a child that
        # never loads libtpu never reads it.
        from ..runtime.overlap import FLAGS_ENV, append_latency_hiding_flags

        env[FLAGS_ENV] = append_latency_hiding_flags(env.get(FLAGS_ENV, ""))
    return env


def main(args=None):
    args = parse_args(args)
    world_info = decode_world_info(args.world_info)
    node_rank = resolve_node_rank(args, world_info)
    logger.info(
        "launch node_rank=%s world=%s coordinator=%s:%s",
        node_rank, list(world_info.keys()) or ["localhost"],
        args.master_addr, args.master_port,
    )
    env = build_env(args, world_info, node_rank)
    cmd = [sys.executable, "-u", args.user_script] + args.user_args
    process = subprocess.Popen(cmd, env=env)
    process.wait()
    sys.exit(process.returncode)


if __name__ == "__main__":
    main()
