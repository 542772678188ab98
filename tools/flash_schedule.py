"""The flash forward's STATIC schedule, without the chip: how many VLIW bundles
each body of ``flash_fwd`` takes after the TPU compiler's scheduler, and how
many of each unit's slots they fill.

    JAX_PLATFORMS=cpu python3 tools/flash_schedule.py [shape ...] [--fwd-sub QxK ...]

A shape of ``tools/flash_kernels_alone.py`` at one program (batch 1, the heads
of one block) is compiled for a described v5e in a child process that asks
libtpu for its low-level dumps (``--xla_jf_dump_to``, ``--xla_jf_dump_llo_text``:
undocumented flags of the libtpu this container has; the child ABORTS after
the kernel's files are written, for want of a report template, and that is
expected). Read from the kernel's ``final_bundles`` and
``static-per-bundle-utilization`` files: the bundles between the ``pl.when``
of each class of grid step (``_walk_plan``: the bodies in the table's order,
then the last step's), and the slots filled there a unit. One JSON line a
(shape, sub-tiles).

A count of bundles is NOT a time: the chip adds what the schedule cannot see
(PR 50: the forward at 128 lanes ran ~1.4 x its bundles at 1.5 GHz, the fused
backward ~1.1 x). It says which unit a body is short of, whether the products
and the softmax passes overlap (slots a stretch of bundles), and which of two
forms the scheduler packs tighter; ``docs/TESTING.md`` has what it showed."""

import argparse
import glob
import json
import os
import re
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
UNITS = ("mxu", "xlu", "valu", "eup", "vload", "vload_fill", "vstore",
         "vstore_spill", "salu")


def compile_one(shape, sub):
    """The child: lower and compile the shape's forward for a described v5e."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, ROOT)
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from deepspeed_tpu.utils import device
    from tools import flash_kernels_alone as alone

    entry, _, _, s, d, *rest = alone.SHAPES[shape]
    # one program: what a kernel body is does not depend on how many run
    alone.SHAPES[shape] = (entry, 1, 1 if entry == "split" else 2, s, d, *rest)
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    device.on_tpu = lambda: True
    if sub:
        real = alone.att.pick_subtiles
        alone.att.pick_subtiles = lambda bq, bk, nq, nk, key_major, *m, **kw: (
            real(bq, bk, nq, nk, key_major, *m, **kw) if key_major
            else (min(sub[0], bq), min(sub[1], bk)))
    loss, args, _ = alone.build(shape)
    jax.jit(loss).lower(*jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one), args
    )).compile()


def read(dump):
    """``{"bundles": all, "bodies": [{"bundles": n, unit: slots...}, ...]}``
    of the dump's ``flash_fwd``."""
    bundles, = glob.glob(os.path.join(dump, "*flash_fwd*-final_bundles.txt"))
    slots, = glob.glob(os.path.join(
        dump, "*flash_fwd*-final_hlo-static-per-bundle-utilization.txt"))
    marks, at = [], 0
    for line in open(bundles):
        m = re.match(r"\s*(0x[0-9a-f]+)\s+(\w\w)?:?", line)
        if m:
            at = int(m.group(1), 16)
            if m.group(2) == "PF":    # a predicated region falls through
                marks.append(at)
    rows = [[int(x) for x in line.split()] for line in
            open(slots).read().split("== UTILIZATION:")[1].strip().split("\n")]

    def filled(a, b):
        return {"bundles": b - a, **{
            u: sum(r[i] for r in rows[a:b]) for i, u in enumerate(UNITS)}}

    spans = list(zip([0] + marks, marks + [at]))
    return {**filled(0, at), "bodies": [
        filled(a, b) for a, b in spans if b - a > 200] if marks else []}


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("shapes", nargs="*", default=["sdar"])
    parser.add_argument("--fwd-sub", action="append", default=[])
    parser.add_argument("--child", default=None)
    opts = parser.parse_args()
    if opts.child is not None:
        sub = tuple(int(x) for x in opts.child.split("x")) if opts.child else None
        return compile_one(opts.shapes[0], sub)
    for shape in opts.shapes:
        for sub in [""] + opts.fwd_sub:
            with tempfile.TemporaryDirectory() as dump:
                subprocess.run(
                    [sys.executable, __file__, shape, "--child", sub],
                    env={**os.environ, "LIBTPU_INIT_ARGS":
                         f"--xla_jf_dump_to={dump} --xla_jf_dump_llo_text=true"},
                    stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
                try:
                    line = read(dump)
                except ValueError as e:
                    line = {"error": f"no dump of flash_fwd: {e}"}
            print(json.dumps({"shape": shape, "fwd_sub": sub or "picked", **line}),
                  flush=True)


if __name__ == "__main__":
    main()
