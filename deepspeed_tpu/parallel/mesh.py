"""Device-mesh topology: the TPU-native replacement for process groups.

The reference had no communication module — NCCL process groups were created
inline (reference: deepspeed/pt/deepspeed_light.py:69-85,132-137 and
zero_utils.py:7-22). On TPU the mesh IS the backend: axes replace groups,
XLA collectives over ICI/DCN replace torch.distributed calls
(SURVEY.md §2.4).

Axes:
  pipe     — pipeline stages (DCN-friendly, outermost)
  data     — data parallel / ZeRO sharding
  sequence — sequence/context parallelism (ring attention)
  model    — tensor (Megatron-style) model parallelism (innermost: its
             collectives are latency-bound, so it rides the fastest ICI links)
"""

import dataclasses
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..config import constants as C

PIPE_AXIS = C.PIPELINE_AXIS
DATA_AXIS = C.DATA_AXIS
SEQ_AXIS = C.SEQUENCE_AXIS
MODEL_AXIS = C.MODEL_AXIS

MESH_AXES = (PIPE_AXIS, DATA_AXIS, SEQ_AXIS, MODEL_AXIS)


@dataclasses.dataclass(frozen=True)
class MeshTopology:
    pipe: int
    data: int
    sequence: int
    model: int

    @property
    def world_size(self):
        return self.pipe * self.data * self.sequence * self.model


def resolve_topology(
    num_devices: int,
    data_parallel_size: Optional[int] = None,
    model_parallel_size: int = 1,
    sequence_parallel_size: int = 1,
    pipeline_parallel_size: int = 1,
) -> MeshTopology:
    """Fill in the data-parallel degree from the device count when unset."""
    fixed = model_parallel_size * sequence_parallel_size * pipeline_parallel_size
    if num_devices % fixed != 0:
        raise ValueError(
            f"{num_devices} devices not divisible by mp*sp*pp = {fixed}"
        )
    dp = data_parallel_size if data_parallel_size is not None else num_devices // fixed
    topo = MeshTopology(
        pipe=pipeline_parallel_size,
        data=dp,
        sequence=sequence_parallel_size,
        model=model_parallel_size,
    )
    if topo.world_size != num_devices:
        raise ValueError(
            f"Mesh {topo} covers {topo.world_size} devices but "
            f"{num_devices} are available"
        )
    return topo


def build_mesh(
    topology: Optional[MeshTopology] = None, devices=None, **topo_kwargs
) -> Mesh:
    """Create the global device mesh.

    On a TPU ``mesh_utils.create_device_mesh`` maps the axis order onto
    the physical torus (model innermost => fastest ICI); a failure there
    is raised, never papered over with an arbitrary device order. Other
    platforms (the virtual CPU devices of the tests) have no topology,
    so a plain reshape is the mesh.
    """
    if devices is None:
        devices = jax.devices()
    if topology is None:
        topology = resolve_topology(len(devices), **topo_kwargs)
    shape = (topology.pipe, topology.data, topology.sequence, topology.model)
    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils

        mesh_devices = mesh_utils.create_device_mesh(shape, devices=devices)
    else:
        mesh_devices = np.asarray(devices).reshape(shape)
    return Mesh(mesh_devices, MESH_AXES)


def mesh_from_config(config, devices=None) -> Mesh:
    return build_mesh(
        devices=devices,
        data_parallel_size=config.data_parallel_size,
        model_parallel_size=config.model_parallel_size,
        sequence_parallel_size=config.sequence_parallel_size,
        pipeline_parallel_size=config.pipeline_parallel_size,
    )


def data_sharding(mesh: Mesh, *trailing_axes) -> NamedSharding:
    """Sharding for a batch: leading dim over (data, sequence? no) data axis."""
    return NamedSharding(mesh, PartitionSpec(DATA_AXIS, *trailing_axes))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def axis_size(mesh: Mesh, axis: str) -> int:
    return mesh.shape[axis]
