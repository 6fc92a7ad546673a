"""Place / device abstraction.

TPU-native analog of the reference's Place variant (platform/place.h:
CPUPlace/CUDAPlace/CUDAPinnedPlace) and DeviceContextPool
(platform/device_context.h:264). In JAX, devices are first-class and
streams/handles are managed by the runtime, so a Place reduces to a
device handle (or a set of them, for SPMD execution over a mesh — see
paddle_tpu.parallel.mesh for the multi-device story that replaces the
reference's ParallelExecutor places list).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax

from .errors import enforce


@dataclasses.dataclass(frozen=True)
class Place:
    """Device identity. platform/place.h analog."""

    platform: str  # 'tpu' | 'cpu' | 'gpu'
    device_id: int = 0

    def device(self) -> jax.Device:
        devs = [d for d in jax.devices() if d.platform == self.platform]
        if not devs and self.platform == "gpu":
            # CUDAPlace is API parity for migrated scripts: it names no
            # device this framework targets, so it maps to what is here
            devs = jax.devices()
        enforce(devs, f"{self!r}: this process has no {self.platform} "
                      f"device (jax sees {jax.devices()[0].platform}); "
                      f"use default_place() for whatever is here")
        return devs[self.device_id % len(devs)]

    def __repr__(self) -> str:  # mirrors e.g. "CUDAPlace(0)"
        return f"{self.platform.upper()}Place({self.device_id})"


def CPUPlace(device_id: int = 0) -> Place:
    return Place("cpu", device_id)


def TPUPlace(device_id: int = 0) -> Place:
    return Place("tpu", device_id)


def CUDAPlace(device_id: int = 0) -> Place:  # API parity; resolves to gpu
    return Place("gpu", device_id)


def default_place() -> Place:
    """Best available place: TPU > GPU > CPU (InitDevices analog)."""
    d = jax.devices()[0]
    return Place(d.platform, 0)


def available_places(platform: Optional[str] = None) -> List[Place]:
    out = []
    for i, d in enumerate(jax.devices()):
        p = d.platform
        if platform is None or p == platform:
            out.append(Place(p, i))
    return out


def device_count() -> int:
    return jax.device_count()
