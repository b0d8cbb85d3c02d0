"""Platform layer: places, device contexts, device pool.

Reference: ``paddle/fluid/platform/place.h:25-80`` (tagged device
addresses), ``device_context.h:42-200`` (per-device handles + the
singleton ``DeviceContextPool``), ``init.cc:76-92`` (device discovery).

TPU-native shape: JAX/PJRT owns streams, allocators and kernels, so a
DeviceContext here wraps the ``jax.Device`` (exposing the PJRT client and
platform metadata) rather than cuBLAS/cuDNN handles; the pool is keyed by
Place exactly like the reference.  Everything compute-related still flows
through the Executor — this module is the device-addressing API surface
(who am I running on, how many chips, memory stats).
"""
from __future__ import annotations

from typing import Dict, List, Union

import jax


class CPUPlace:
    """Host-device tag (place.h:36)."""

    def __eq__(self, other):
        return isinstance(other, CPUPlace)

    def __hash__(self):
        return hash("cpu")

    def __repr__(self):
        return "CPUPlace"


class TPUPlace:
    """TPU device tag (the CUDAPlace analogue; place.h:51)."""

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __eq__(self, other):
        return isinstance(other, TPUPlace) and other.device_id == self.device_id

    def __hash__(self):
        return hash(("tpu", self.device_id))

    def __repr__(self):
        return f"TPUPlace({self.device_id})"


class CUDAPinnedPlace:
    """Pinned-host tag (place.h:45).  On TPU, host staging buffers are
    managed by the runtime/PJRT, so this is a compat tag that behaves
    like CPUPlace for placement decisions."""

    def __eq__(self, other):
        return isinstance(other, CUDAPinnedPlace)

    def __hash__(self):
        return hash("pinned")

    def __repr__(self):
        return "CUDAPinnedPlace"


CUDAPlace = TPUPlace  # reference-compat alias
Place = Union[CPUPlace, TPUPlace, CUDAPinnedPlace]


def is_tpu_place(p) -> bool:
    return isinstance(p, TPUPlace)


class DeviceContext:
    """Per-device context (device_context.h:42): wraps the jax.Device and
    its PJRT platform metadata."""

    def __init__(self, place: Place):
        self.place = place
        if isinstance(place, TPUPlace):
            self.device = tpu_device(place)
        else:
            self.device = jax.devices("cpu")[0] if _has_cpu() else None

    @property
    def platform(self) -> str:
        return self.device.platform if self.device is not None else "cpu"

    def memory_stats(self) -> dict:
        """HBM stats from PJRT (gpu_info.cc capability)."""
        if self.device is None or not hasattr(self.device, "memory_stats"):
            return {}
        try:
            return dict(self.device.memory_stats() or {})
        except Exception:
            return {}

    def synchronize(self) -> None:
        """Wait for outstanding work (the stream Wait analogue)."""
        jax.effects_barrier()

    def __repr__(self):
        return f"DeviceContext({self.place!r}, {self.platform})"


def tpu_device(place: TPUPlace):
    """The ``jax.Device`` a :class:`TPUPlace` names, or an error: a TPU
    place must never resolve to whatever backend happens to be the
    default (a CPU run "on TPUPlace" would pass without a word)."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(
            f"{place!r} requested but JAX found no TPU: the default "
            f"backend is {devices[0].platform!r} "
            f"({len(devices)} device(s)); use CPUPlace()/None to run on it")
    if place.device_id >= len(devices):
        raise ValueError(
            f"{place!r}: only {len(devices)} device(s) visible")
    return devices[place.device_id]


def _has_cpu() -> bool:
    try:
        return bool(jax.devices("cpu"))
    except RuntimeError:
        return False


class DeviceContextPool:
    """Singleton Place→DeviceContext map (device_context.h:200)."""

    _instance: "DeviceContextPool" = None

    def __init__(self):
        self._ctxs: Dict[Place, DeviceContext] = {}

    @classmethod
    def instance(cls) -> "DeviceContextPool":
        if cls._instance is None:
            cls._instance = DeviceContextPool()
        return cls._instance

    def get(self, place: Place) -> DeviceContext:
        if place not in self._ctxs:
            self._ctxs[place] = DeviceContext(place)
        return self._ctxs[place]


# ---------------------------------------------------------------------------
# Platform peak table (observability/perf.py rooflines)
# ---------------------------------------------------------------------------
# device_kind substring (lowercased, spaces stripped) → (dense bf16 peak
# FLOP/s, HBM bandwidth bytes/s).  Vendor datasheet numbers for TPU
# generations; the "cpu" row is a NOMINAL host envelope (labeled
# nominal=True in platform_peaks) so rooflines still compute on the CPU
# backend dev loop — positions there are relative, not absolute.
PLATFORM_PEAKS: Dict[str, tuple] = {
    "v6": (918e12, 1640e9),       # Trillium
    "v5p": (459e12, 2765e9),
    "v5e": (197e12, 819e9),
    "v5lite": (197e12, 819e9),    # "TPU v5 lite" device_kind spelling
    "v4": (275e12, 1228e9),
    "v3": (123e12, 900e9),
    "v2": (46e12, 700e9),
}
_CPU_NOMINAL_PEAKS = (0.5e12, 50e9)


def platform_peaks(device=None) -> dict:
    """Peak FLOP/s + HBM bytes/s for ``device`` (default: first local
    device) from :data:`PLATFORM_PEAKS`; ``{"flops": None, ...}`` when
    the device kind is unknown (rooflines then report intensity only)."""
    if device is None:
        devs = jax.local_devices()
        if not devs:
            return {"device_kind": "none", "platform": "none",
                    "flops": None, "hbm_bytes_per_s": None}
        device = devs[0]
    kind = str(getattr(device, "device_kind", "") or "")
    plat = str(getattr(device, "platform", "") or "")
    norm = kind.lower().replace(" ", "").replace("-", "")
    out = {"device_kind": kind, "platform": plat,
           "flops": None, "hbm_bytes_per_s": None, "nominal": False}
    for tag, (fl, bw) in PLATFORM_PEAKS.items():
        if tag in norm:
            out["flops"], out["hbm_bytes_per_s"] = fl, bw
            return out
    if plat == "cpu":
        out["flops"], out["hbm_bytes_per_s"] = _CPU_NOMINAL_PEAKS
        out["nominal"] = True
    return out


def device_inventory() -> dict:
    """Hardware card for /statusz: platform, device kind/count, and the
    per-device memory limit — so fleet dashboards can label perf series
    by what they ran on.  Never raises (an uninitializable backend
    reports as an error field)."""
    try:
        devs = jax.local_devices()
    except Exception as e:  # pragma: no cover - backend init failure
        return {"error": repr(e)[:200]}
    out = {"platform": devs[0].platform if devs else "none",
           "device_count": len(jax.devices()),
           "local_device_count": len(devs),
           "devices": []}
    for d in devs:
        rec = {"id": d.id, "kind": str(getattr(d, "device_kind", "")),
               "process_index": getattr(d, "process_index", 0)}
        try:
            ms = d.memory_stats() if hasattr(d, "memory_stats") else None
        except Exception:
            ms = None
        rec["memory_limit_bytes"] = (ms or {}).get("bytes_limit")
        out["devices"].append(rec)
    return out


def device_count() -> int:
    """Visible accelerator count (init.cc device discovery)."""
    return len(jax.devices())


def tpu_places(device_ids: List[int] = None) -> List[TPUPlace]:
    ids = device_ids if device_ids is not None else range(len(jax.devices()))
    return [TPUPlace(i) for i in ids]


def pallas_interpret() -> bool:
    """THE decision whether a Pallas kernel is interpreted: compiled by
    Mosaic when JAX's default backend is a TPU, interpreted everywhere
    else (the CPU test mesh).  Every kernel's ``interpret=None`` default
    resolves here, so "ran on a TPU" can never mean "was interpreted"."""
    return jax.default_backend() != "tpu"
