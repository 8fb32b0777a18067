"""TPU topology enumeration and HBM accounting.

This is the TPU-native replacement for the reference's GPU detection layer
(``api/pkg/gpudetect/gpudetect.go:77-177`` shells out to ``nvidia-smi`` /
``rocm-smi``; ``api/pkg/runner/gpuarch/canonical.go`` canonicalises
architectures).  Instead of parsing CSV from a vendor tool we ask the runtime
directly: ``jax.devices()`` enumerates chips and ``device.memory_stats()``
gives per-chip HBM totals/usage — the numbers the control plane's
compatibility checks and the engine's residency manager budget against.

Record shape deliberately mirrors the reference's ``types.GPUStatus``
(``api/pkg/types/runner.go:48-63``: vendor/arch/VRAM total-used-free/driver)
with ``vendor="tpu"`` and ``arch`` = chip generation, so heartbeat JSON stays
interchangeable.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional


# Canonical generation table: maps substrings of jax device_kind to the
# canonical architecture string used in profiles/compatibility, plus
# datasheet HBM capacity (bytes) used as a fallback when memory_stats() is
# unavailable (e.g. CPU simulation of a TPU mesh).
_TPU_GENERATIONS = (
    # (needle in device_kind.lower(), canonical arch, HBM bytes per chip)
    ("v6e", "v6e", 32 * 1024**3),
    ("v6", "v6e", 32 * 1024**3),
    ("v5p", "v5p", 95 * 1024**3),
    ("v5 lite", "v5e", 16 * 1024**3),
    ("v5lite", "v5e", 16 * 1024**3),
    ("v5e", "v5e", 16 * 1024**3),
    ("v5", "v5p", 95 * 1024**3),
    ("v4", "v4", 32 * 1024**3),
    ("v3", "v3", 32 * 1024**3),
    ("v2", "v2", 16 * 1024**3),
)


def tpu_generation(device_kind: str) -> str:
    """Canonicalise a jax ``device_kind`` string to a TPU generation.

    The analogue of the reference's compute-capability -> "hopper"/"ampere"
    mapping (``api/pkg/runner/gpuarch/canonical.go``).
    """
    kind = device_kind.lower()
    for needle, arch, _ in _TPU_GENERATIONS:
        if needle in kind:
            return arch
    return "unknown"


def _datasheet_hbm(device_kind: str) -> int:
    kind = device_kind.lower()
    for needle, _, hbm in _TPU_GENERATIONS:
        if needle in kind:
            return hbm
    return 0


@dataclasses.dataclass(frozen=True)
class AcceleratorStatus:
    """Per-chip status record, wire-compatible with the reference heartbeat.

    Mirrors ``types.GPUStatus`` (``api/pkg/types/runner.go:48-63``) so the
    control plane's compatibility filter needs only a new vendor branch.
    """

    index: int
    vendor: str                  # "tpu" | "cpu"
    arch: str                    # "v5e" | "v5p" | ... (gpuarch equivalent)
    device_kind: str             # raw jax device_kind
    total_memory_bytes: int      # HBM capacity
    used_memory_bytes: int       # HBM in use (live buffers)
    free_memory_bytes: int
    peak_memory_bytes: int = 0   # high-water mark, where the runtime says
    core_on_chip: int = 1
    process_index: int = 0
    coords: Optional[tuple] = None
    driver: str = ""             # libtpu/jax version string

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["coords"] = list(self.coords) if self.coords is not None else None
        return d


def _memory_stats(device) -> tuple[int, int, int]:
    """(total_bytes, used_bytes, peak_bytes) for a device; falls back to
    the datasheet capacity where the runtime reports nothing."""
    try:
        stats = device.memory_stats()
    except Exception:
        stats = None
    if stats:
        total = int(
            stats.get("bytes_limit")
            or stats.get("bytes_reservable_limit")
            or 0
        )
        used = int(stats.get("bytes_in_use", 0))
        if total:
            return total, used, int(stats.get("peak_bytes_in_use", 0))
    return _datasheet_hbm(getattr(device, "device_kind", "")), 0, 0


def detect_accelerators(devices: Optional[list] = None) -> list[AcceleratorStatus]:
    """Enumerate accelerators with HBM accounting.

    Replaces the reference's ``gpudetect.DetectGPUs`` (nvidia-smi CSV parse at
    ``gpudetect.go:77-123``) with a direct runtime query — no subprocess, no
    parsing, works identically under the CPU simulator used in tests.
    """
    import jax

    if devices is None:
        devices = jax.devices()
    driver = f"jax-{jax.__version__}"
    out = []
    for d in devices:
        kind = getattr(d, "device_kind", "cpu")
        platform = getattr(d, "platform", "cpu")
        is_tpu = (
            platform == "tpu"
            or "tpu" in kind.lower()
            or tpu_generation(kind) != "unknown"
        )
        total, used, peak = _memory_stats(d)
        coords = getattr(d, "coords", None)
        out.append(
            AcceleratorStatus(
                index=d.id,
                vendor="tpu" if is_tpu else platform,
                arch=tpu_generation(kind) if is_tpu else platform,
                device_kind=kind,
                total_memory_bytes=total,
                used_memory_bytes=used,
                free_memory_bytes=max(total - used, 0),
                peak_memory_bytes=peak,
                core_on_chip=getattr(d, "num_cores", 1) if not isinstance(getattr(d, "num_cores", 1), property) else 1,
                process_index=d.process_index,
                coords=tuple(coords) if coords is not None else None,
                driver=driver,
            )
        )
    return out


def total_hbm_bytes(devices: Optional[list] = None) -> int:
    """Aggregate HBM across visible chips (residency-manager budget)."""
    return sum(a.total_memory_bytes for a in detect_accelerators(devices))


