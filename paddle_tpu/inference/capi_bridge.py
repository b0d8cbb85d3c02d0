"""Marshal layer for the native C inference API (native/paddle_tpu_capi.cc).

The embedded interpreter calls ONLY these three functions, passing plain
Python ints/strs/bytes — no numpy C-API or ctypes on the C side, so the
native library compiles against Python.h alone.  Reference role: the
glue the legacy capi's gradient_machine.cpp plays between C structs and
the C++ core (paddle/legacy/capi/gradient_machine.cpp), redesigned as a
bytes-protocol bridge.

Wire format per tensor: (name:str, dtype:str, shape:tuple[int], data:bytes).
"""
from __future__ import annotations

import threading
from typing import List, Tuple

import numpy as np

_DTYPES = {
    "float32": np.float32,
    "int64": np.int64,
    "int32": np.int32,
    "float64": np.float64,
    "uint8": np.uint8,
}

class HandleRegistry:
    """Thread-safe int-handle table; shared by the C-API bridges (this
    one and paddle_tpu/train/capi_bridge.py)."""

    def __init__(self):
        self._handles = {}
        self._next = 1
        self._lock = threading.Lock()

    def add(self, obj) -> int:
        with self._lock:
            h = self._next
            self._next += 1
            self._handles[h] = obj
            return h

    def get(self, h: int):
        with self._lock:
            return self._handles[h]

    def pop(self, h: int) -> None:
        with self._lock:
            self._handles.pop(h, None)


_registry = HandleRegistry()


def _np_dtype(name: str):
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(_DTYPES[name])


def create(model_dir: str) -> int:
    from .predictor import AnalysisConfig, create_predictor

    pred = create_predictor(AnalysisConfig(model_dir))
    return _registry.add(pred)


def clone(handle: int) -> int:
    return _registry.add(_registry.get(handle).clone())


def feed_names(handle: int) -> List[str]:
    return _registry.get(handle).feed_names


def fetch_count(handle: int) -> int:
    return len(_registry.get(handle).fetch_names)


def run(handle: int,
        inputs: List[Tuple[str, str, tuple, bytes]]
        ) -> List[Tuple[str, tuple, bytes]]:
    pred = _registry.get(handle)
    feed = {}
    for name, dtype, shape, data in inputs:
        feed[name] = np.frombuffer(data, dtype=_np_dtype(dtype)).reshape(shape)
    outs = pred.run(feed)
    wire = []
    for o in outs:
        a = np.ascontiguousarray(np.asarray(o))
        dt = str(a.dtype)
        wire.append((dt, tuple(int(d) for d in a.shape), a.tobytes()))
    return wire


def destroy(handle: int) -> None:
    _registry.pop(handle)
