"""Device / place management.

Maps the reference's Place hierarchy (paddle/phi/common/place.h: CPUPlace,
GPUPlace(id), CustomPlace...) onto PJRT devices exposed through JAX. On TPU
there are no user-visible streams: XLA schedules; a Place is just a PJRT
device handle plus a stable string form ("tpu:0", "cpu:0").
"""
from __future__ import annotations

import dataclasses
import functools

import jax


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU. The one device test in
    the package: Pallas kernels compile through Mosaic (instead of the
    interpreter), the serving engine donates its KV pool and the
    ``"auto"`` kernel routes pick Pallas exactly when this holds."""
    return jax.default_backend() == "tpu"


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks, for utilization and roofline shares."""

    bf16_flops: float       # dense bf16 FLOP/s
    int8_ops: float         # dense int8 OP/s
    hbm_bytes_per_s: float
    source: str


# Keyed by ``jax.devices()[0].device_kind``. A device that is not listed
# has no utilization figure: ``device_peaks`` raises instead of guessing.
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}


def device_peaks(device_kind: str | None = None) -> DevicePeaks:
    kind = device_kind or jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peaks recorded for device_kind {kind!r}; add a "
            "row with its source to paddle_tpu.core.device.DEVICE_PEAKS "
            f"(known: {sorted(DEVICE_PEAKS)})"
        )
    return DEVICE_PEAKS[kind]


class Place:
    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    @property
    def jax_device(self):
        try:
            devs = jax.devices(self.device_type)
        except RuntimeError as e:
            raise RuntimeError(
                f"no {self.device_type!r} device is present for place "
                f"{self} (default backend: {jax.default_backend()!r})"
            ) from e
        return devs[self.device_id % len(devs)]

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __str__(self):
        return f"{self.device_type}:{self.device_id}"

    def __eq__(self, other):
        if isinstance(other, str):
            other = parse_device(other)
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))


def CPUPlace() -> Place:
    return Place("cpu", 0)


def TPUPlace(device_id: int = 0) -> Place:
    return Place("tpu", device_id)


@functools.cache
def _default_device_type() -> str:
    return "tpu" if on_tpu() else "cpu"


_current_place: Place | None = None


def parse_device(device: str) -> Place:
    if ":" in device:
        ty, _, idx = device.partition(":")
        return Place(ty, int(idx))
    return Place(device, 0)


def set_device(device: str) -> Place:
    global _current_place
    _current_place = parse_device(device)
    return _current_place


def get_device() -> str:
    return str(current_place())


def current_place() -> Place:
    if _current_place is not None:
        return _current_place
    return Place(_default_device_type(), 0)


def is_compiled_with_tpu() -> bool:
    return _default_device_type() == "tpu"


def device_count(device_type: str | None = None) -> int:
    return len(jax.devices(device_type or _default_device_type()))
