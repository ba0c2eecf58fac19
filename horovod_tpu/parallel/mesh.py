"""Multi-axis device mesh construction.

TPU-first design notes: ICI bandwidth dominates DCN by an order of magnitude,
so axes that carry the chattiest collectives must map to ICI neighbors.
Convention (innermost/fastest-varying axis last in the device ordering):

    ('pp', 'dp', 'fsdp', 'ep', 'sp', 'tp')

- ``tp`` innermost: per-layer activation psums every matmul — needs the
  tightest ICI loops.
- ``sp``/``ep`` next: ring permutes / alltoall per attention/MoE layer.
- ``dp``/``fsdp``: one gradient reduce-scatter+all-gather per step.
- ``pp`` outermost: point-to-point hand-offs once per microbatch — the only
  axis that tolerates DCN, which is why multi-slice deployments put the
  slice boundary on pp (or dp) — the hierarchical split the reference
  implements as NCCL-within-node + MPI-across († ``nccl_operations.cc``
  HOROVOD_HIERARCHICAL_ALLREDUCE).

``jax.sharding.Mesh`` over ``mesh_utils.create_device_mesh`` handles the
physical ICI topology mapping; on CPU test rigs the reshape order stands in
for it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from ..utils import logging as hvd_logging

log = hvd_logging.get_logger()

AXES = ("pp", "dp", "fsdp", "ep", "sp", "tp")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Sizes for each parallelism axis; product must equal device count."""

    dp: int = 1      # data parallel (batch)
    fsdp: int = 1    # sharded-parameter data parallel (ZeRO-3 style)
    tp: int = 1      # tensor (Megatron) parallel
    sp: int = 1      # sequence/context parallel (ring attention / Ulysses)
    pp: int = 1      # pipeline parallel
    ep: int = 1      # expert parallel (MoE)

    @property
    def total(self) -> int:
        return self.dp * self.fsdp * self.tp * self.sp * self.pp * self.ep

    def axis_sizes(self) -> dict[str, int]:
        return {"pp": self.pp, "dp": self.dp, "fsdp": self.fsdp,
                "ep": self.ep, "sp": self.sp, "tp": self.tp}

    @staticmethod
    def auto(n_devices: int) -> "MeshConfig":
        """Factorize ``n_devices`` across axes for a maximal exercise of
        every parallelism style (used by the multi-chip dry run):
        repeatedly assign the smallest prime factor to the axis that most
        needs >1 size, in priority order tp, dp, pp, sp, ep, fsdp.
        """
        factors = _prime_factors(n_devices)
        sizes = {"tp": 1, "dp": 1, "pp": 1, "sp": 1, "ep": 1, "fsdp": 1}
        order = ["tp", "dp", "pp", "sp", "ep", "fsdp"]
        i = 0
        for f in sorted(factors):
            # fill axes round-robin in priority order
            sizes[order[i % len(order)]] *= f
            i += 1
        return MeshConfig(**sizes)


def _prime_factors(n: int) -> list[int]:
    out = []
    d = 2
    while d * d <= n:
        while n % d == 0:
            out.append(d)
            n //= d
        d += 1
    if n > 1:
        out.append(n)
    return out


def build_mesh(config: MeshConfig,
               devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build the multi-axis mesh in ICI-friendly axis order."""
    devs = list(devices) if devices is not None else list(jax.devices())
    if config.total != len(devs):
        raise ValueError(
            f"mesh sizes {config.axis_sizes()} multiply to {config.total} "
            f"but {len(devs)} devices are available")
    shape = tuple(config.axis_sizes()[a] for a in AXES)
    if devices is None and len(devs) > 1:
        try:
            arr = mesh_utils.create_device_mesh(shape)
        except (ValueError, AssertionError) as e:
            log.warning(
                "create_device_mesh%s failed (%s); falling back to device "
                "enumeration order, which ignores the ICI topology", shape,
                e)
            arr = np.array(devs).reshape(shape)
    else:
        arr = np.array(devs).reshape(shape)
    return Mesh(arr, axis_names=AXES)


def data_axes() -> tuple[str, ...]:
    """Axes a global batch is sharded over (gradient-reduction axes)."""
    return ("dp", "fsdp")
