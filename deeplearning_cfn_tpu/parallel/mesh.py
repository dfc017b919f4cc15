"""Device-mesh construction and topology math.

Replaces the reference's cluster-shape plumbing: where the CFN template's
Parameters (worker count × GPUs/worker) plus the generated hostfile defined the
communicator world for Horovod/MPI and KVStore (SURVEY.md §4.1), here the
world is a :class:`jax.sharding.Mesh` over the slice's chips, and "topology"
is which logical axis (data/model/spatial) maps onto which physical ICI axes.
XLA then schedules collectives over ICI along those axes — the hostfile, the
SSH mesh, and the NCCL ring all collapse into this one object.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from ..config import MeshConfig

# Axis order matters: 'dcn_data' outermost (slice boundaries are the
# slowest links — only the one gradient allreduce hop should cross them),
# then 'data' so per-host batches stay contiguous (each host feeds only its
# local shard of the batch), then 'expert' (MoE all-to-alls are bigger than
# grad psums per hop, but batch shards ride it too), 'model' innermost so
# tensor-parallel collectives ride the shortest ICI hops.
AXIS_ORDER: Tuple[str, ...] = ("dcn_data", "pipe", "data", "expert",
                               "spatial", "seq", "model")
# Batch dim 0 shards over all of these jointly: the 'expert' axis carries
# batch shards outside MoE layers (GSPMD MoE — tokens are data-parallel
# everywhere except the expert einsums, where the stacked expert weights
# are sharded over 'expert' and the compiler inserts the dispatch
# all-to-all). With one slice / no MoE the extra axes are size 1 and the
# spec degenerates to plain DP.
BATCH_AXES: Tuple[str, ...] = ("dcn_data", "data", "expert")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Resolved logical mesh shape (all axes concrete, product == #devices)."""

    data: int
    model: int = 1
    spatial: int = 1
    dcn_data: int = 1
    expert: int = 1
    pipe: int = 1
    seq: int = 1

    @property
    def num_devices(self) -> int:
        return (self.data * self.model * self.spatial * self.dcn_data
                * self.expert * self.pipe * self.seq)

    def axis_sizes(self) -> Dict[str, int]:
        return {"dcn_data": self.dcn_data, "pipe": self.pipe,
                "data": self.data, "expert": self.expert,
                "spatial": self.spatial, "seq": self.seq,
                "model": self.model}

    @classmethod
    def resolve(cls, cfg: MeshConfig, num_devices: int) -> "MeshSpec":
        """Resolve ``data = -1`` ("all remaining devices") against a device
        count and validate divisibility — the topology math the reference did
        by hand via ``$DEEPLEARNING_WORKERS_COUNT × GPUs``."""
        model = cfg.model
        spatial = cfg.spatial
        expert = getattr(cfg, "expert", 1)
        pipe = getattr(cfg, "pipe", 1)
        seq = getattr(cfg, "seq", 1)
        slices = getattr(cfg, "num_slices", 1)
        if min(model, spatial, slices, expert, pipe, seq) < 1:
            raise ValueError(f"mesh axes must be >=1, got {cfg}")
        if num_devices % slices != 0:
            raise ValueError(
                f"num_slices={slices} does not divide device count "
                f"{num_devices}")
        per_slice = num_devices // slices
        fixed = model * spatial * expert * pipe * seq
        if per_slice % fixed != 0:
            raise ValueError(
                f"pipe*model*spatial*seq*expert={fixed} does not divide "
                f"per-slice device count {per_slice}"
            )
        data = cfg.data
        if data == -1:
            data = per_slice // fixed
        if data * fixed != per_slice:
            raise ValueError(
                f"mesh {pipe}x{data}x{expert}x{spatial}x{seq}x{model} != "
                f"{per_slice} devices/slice; set data=-1 to auto-size"
            )
        return cls(data=data, model=model, spatial=spatial,
                   dcn_data=slices, expert=expert, pipe=pipe, seq=seq)


def build_mesh(
    cfg: Optional[MeshConfig] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build the global :class:`Mesh` for this process.

    Uses ``mesh_utils.create_device_mesh`` so the logical axes map onto
    physically-contiguous ICI neighborhoods (nearest-neighbor torus links),
    keeping allreduce on ICI instead of hopping DCN.
    """
    cfg = cfg or MeshConfig()
    devices = list(devices if devices is not None else jax.devices())
    spec = MeshSpec.resolve(cfg, len(devices))
    shape = tuple(spec.axis_sizes()[a] for a in AXIS_ORDER)
    if spec.dcn_data > 1:
        # Multi-slice: per-axis ICI shape × per-axis DCN shape. The hybrid
        # constructor groups devices by their slice_index so only the
        # dcn_data axis crosses slice boundaries.
        if getattr(devices[0], "slice_index", None) is None:
            if getattr(devices[0], "platform", "") != "cpu":
                # Accelerator devices without slice topology info: a naive
                # reshape would silently route "intra-slice" collectives
                # over DCN. Refuse rather than degrade.
                raise ValueError(
                    f"num_slices={spec.dcn_data} needs devices with "
                    f"slice_index (multi-slice runtime); "
                    f"{devices[0].platform} devices expose none"
                )
            # Simulated CPU devices: contiguous blocks of the device list
            # stand in for slices.
            dev_array = np.asarray(devices).reshape(shape)
            return Mesh(dev_array, AXIS_ORDER)
        ici = tuple(1 if a == "dcn_data" else spec.axis_sizes()[a]
                    for a in AXIS_ORDER)
        dcn = tuple(spec.dcn_data if a == "dcn_data" else 1
                    for a in AXIS_ORDER)
        dev_array = mesh_utils.create_hybrid_device_mesh(
            ici, dcn, devices=devices)
        return Mesh(dev_array, AXIS_ORDER)
    try:
        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    except (ValueError, AssertionError, NotImplementedError):
        if getattr(devices[0], "platform", "") != "cpu":
            # On real chips a plain reshape would silently give up the
            # ICI-contiguous layout.
            raise
        # Host-simulated CPU meshes have no topology to honour.
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, AXIS_ORDER)


def data_axis_size(mesh: Mesh) -> int:
    """Total batch-sharding ways: the 'data' axis times the cross-slice
    'dcn_data' axis times the 'expert' axis (batch shards ride 'expert'
    outside MoE layers — see BATCH_AXES)."""
    return (mesh.shape["data"] * mesh.shape.get("dcn_data", 1)
            * mesh.shape.get("expert", 1))


def local_batch_size(global_batch: int, mesh: Mesh) -> int:
    """Per-process batch size: the global batch divided across the processes
    that feed the data axes. Each host feeds only its addressable shard —
    the TPU equivalent of Horovod's per-rank batch."""
    n_proc = jax.process_count()
    if global_batch % n_proc != 0:
        raise ValueError(
            f"global batch {global_batch} not divisible by process count {n_proc}"
        )
    if global_batch % data_axis_size(mesh) != 0:
        raise ValueError(
            f"global batch {global_batch} not divisible by data-axis size "
            f"{data_axis_size(mesh)}"
        )
    return global_batch // n_proc


def validate_batch(global_batch: int, mesh: Mesh) -> None:
    if global_batch % data_axis_size(mesh) != 0:
        raise ValueError(
            f"global batch {global_batch} must be divisible by the total "
            f"data-parallel ways ({data_axis_size(mesh)})"
        )


def describe(mesh: Mesh) -> str:
    """Human-readable topology line for logs — the rebuild's replacement for
    the reference printing the hostfile + `$DEEPLEARNING_WORKERS_COUNT`."""
    axes = ", ".join(f"{a}={s}" for a, s in mesh.shape.items())
    return (
        f"mesh[{axes}] over {mesh.devices.size} devices "
        f"({jax.process_count()} processes, "
        f"{len([d for d in mesh.devices.flat if d.process_index == jax.process_index()])} "
        f"local)"
    )


def slice_chip_count(slice_type: str) -> int:
    """Chips in a TPU slice type string like 'v5p-8' (the number suffix is
    the chip count for v5p/v4 naming)."""
    try:
        return int(slice_type.rsplit("-", 1)[1])
    except (IndexError, ValueError) as e:
        raise ValueError(f"cannot parse slice type {slice_type!r}") from e


def hosts_for_slice(slice_type: str, chips_per_host: int = 4) -> int:
    chips = slice_chip_count(slice_type)
    return max(1, math.ceil(chips / chips_per_host))
