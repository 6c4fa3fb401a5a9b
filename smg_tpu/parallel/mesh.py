"""Device mesh construction.

The reference scales with NCCL/MPI inside external engines and gRPC between
processes (SURVEY.md §2.5).  TPU-native scaling instead declares a
``jax.sharding.Mesh`` over named axes and lets XLA insert collectives over
ICI/DCN.  Axis order matters: the innermost axes get the fastest ICI links, so
``tp`` (all-reduce per layer) is innermost, then ``sp``/``ep``, then ``dp``,
then ``pp`` (cross-slice / DCN) outermost.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from smg_tpu.engine.config import ParallelConfig

# Outer→inner axis order for device assignment.
AXIS_ORDER = ("pp", "dp", "ep", "sp", "tp")


@dataclass(frozen=True)
class MeshSpec:
    parallel: ParallelConfig

    @property
    def axis_names(self) -> tuple[str, ...]:
        return AXIS_ORDER

    @property
    def shape(self) -> tuple[int, ...]:
        sizes = self.parallel.axis_sizes()
        return tuple(sizes[a] for a in AXIS_ORDER)


def build_mesh(parallel: ParallelConfig, devices: list | None = None) -> Mesh:
    """Build a Mesh for the given parallel config.

    ``jax.experimental.mesh_utils`` places the axes on the torus.  Where it
    refuses the shape, virtual CPU devices (which have no torus) take a
    plain reshape; TPU devices do not, because a linear order there would
    put ``tp`` on whatever links it happens to get and say nothing.
    """
    spec = MeshSpec(parallel)
    if devices is None:
        devices = jax.devices()
    world = parallel.world_size
    if len(devices) < world:
        raise ValueError(
            f"parallel config needs {world} devices ({parallel}), found {len(devices)}"
        )
    devices = devices[:world]
    try:
        dev_array = mesh_utils.create_device_mesh(spec.shape, devices=devices)
    except (ValueError, AssertionError) as e:
        if devices[0].platform == "tpu":
            raise ValueError(
                f"no torus placement for mesh {parallel.axis_sizes()} over "
                f"{world} TPU devices: {e}"
            ) from e
        logging.getLogger("smg_tpu.parallel").debug(
            "mesh_utils placement failed (%s); using linear device order", e
        )
        dev_array = np.asarray(devices).reshape(spec.shape)
    return Mesh(dev_array, spec.axis_names)


def single_device_mesh() -> Mesh:
    return build_mesh(ParallelConfig())
