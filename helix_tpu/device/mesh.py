"""Device-mesh construction for SPMD serving and training.

The reference expresses parallel layout as Docker Compose GPU ``device_ids``
plus vLLM's ``--tensor-parallel-size`` (``SURVEY.md`` §2.2 "Parallelism
strategies").  Here the layout is a first-class object: a ``MeshSpec`` names
logical axes (data / fsdp / tensor / sequence / expert) and a chip count per
axis; ``build_mesh`` realises it as a ``jax.sharding.Mesh`` over a contiguous
slice of devices.  Profiles (``helix_tpu.control.profile``) map model names to
MeshSpecs the way compose profiles map vLLM services to ``device_ids``
(``design/sample-profiles/8xH100-vllm.yaml`` in the reference).

Axis conventions (used by ``helix_tpu.parallel.sharding`` rules):
  - ``dp``   data parallel (across requests / batch)
  - ``fsdp`` fully-sharded data parallel (weights sharded over dp axis)
  - ``tp``   tensor parallel (heads / ffn sharded, collectives over ICI)
  - ``sp``   sequence/context parallel (ring attention for long context)
  - ``ep``   expert parallel (MoE)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_ORDER = ("dp", "fsdp", "pp", "ep", "sp", "tp")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Named-axis mesh layout over a number of chips.

    ``device_offset``/``num_devices`` let several models share one host's
    chips by claiming disjoint slices — the TPU equivalent of compose
    services pinned to disjoint GPU ``device_ids``
    (``api/pkg/runner/composeparse/parse.go:49-102``).
    """

    dp: int = 1
    fsdp: int = 1
    pp: int = 1
    ep: int = 1
    sp: int = 1
    tp: int = 1
    device_offset: int = 0

    @property
    def num_devices(self) -> int:
        return (
            self.dp * self.fsdp * self.pp * self.ep * self.sp * self.tp
        )

    def axis_sizes(self) -> dict[str, int]:
        return {
            "dp": self.dp,
            "fsdp": self.fsdp,
            "pp": self.pp,
            "ep": self.ep,
            "sp": self.sp,
            "tp": self.tp,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "MeshSpec":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: int(v) for k, v in d.items() if k in known})

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def slice_devices(
    spec: MeshSpec, devices: Optional[Sequence] = None
) -> list:
    """Pick the contiguous device slice this spec claims."""
    if devices is None:
        devices = jax.devices()
    lo, hi = spec.device_offset, spec.device_offset + spec.num_devices
    if hi > len(devices):
        raise ValueError(
            f"MeshSpec wants devices [{lo}, {hi}) but only "
            f"{len(devices)} devices are visible"
        )
    return list(devices)[lo:hi]


def build_mesh(spec: MeshSpec, devices: Optional[Sequence] = None) -> Mesh:
    """Realise a MeshSpec as a ``jax.sharding.Mesh``.

    Axis order puts ``tp`` innermost so tensor-parallel collectives ride the
    fastest ICI links (adjacent chips), and ``dp`` outermost so data-parallel
    gradient reduction can span DCN across hosts — the standard TPU layout
    recipe (scaling-book; contrast with the reference where NCCL topology is
    vLLM-internal, ``SURVEY.md`` §2.2).
    """
    devs = slice_devices(spec, devices)
    sizes = [spec.axis_sizes()[a] for a in AXIS_ORDER]
    arr = np.asarray(devs, dtype=object).reshape(sizes)
    return Mesh(arr, AXIS_ORDER)


def default_mesh_spec(
    num_devices: Optional[int] = None,
    max_tp: int = 8,
) -> MeshSpec:
    """Heuristic single-model layout: as much TP as divides the chip count
    (capped), remainder into dp — a sensible default for decoder LLM serving
    where TP over ICI minimises per-token latency."""
    if num_devices is None:
        num_devices = len(jax.devices())
    tp = math.gcd(num_devices, max_tp)
    return MeshSpec(tp=tp, dp=num_devices // tp)
