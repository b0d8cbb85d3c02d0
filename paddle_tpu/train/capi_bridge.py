"""Marshal layer for the native C TRAINER API (native/paddle_tpu_capi.cc
pt_trainer_*) — train-from-native without authoring Python.

Same bytes-only wire protocol as the inference bridge
(paddle_tpu/inference/capi_bridge.py): the embedded interpreter passes
plain ints/strs/bytes tuples, so the C side compiles against Python.h
alone.  Reference role: the train-from-saved-program capability of
paddle/fluid/train/demo/demo_trainer.cc:1 (load ProgramDescs, run
startup, loop executor.Run, read the loss tensor) — redesigned over the
paddle_tpu Executor and the save_train_model layout (io.py).
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

from ..inference.capi_bridge import HandleRegistry, _np_dtype

_registry = HandleRegistry()


class _NativeTrainer:
    def __init__(self, model_dir: str):
        from .. import io
        from ..core.executor import Executor, Scope, scope_guard

        self.scope = Scope()
        self.exe = Executor()
        with scope_guard(self.scope):
            main, startup, feeds, loss = io.load_train_model(
                model_dir, self.exe)
            # startup creates every persistable (params, optimizer
            # moments, LR counters); the saved state then overwrites it,
            # so a freshly-saved model warm-starts and a checkpointed
            # one resumes exactly
            self.exe.run(startup)
            io.load_persistables(self.exe, model_dir, main)
        self.main = main
        self.startup = startup
        self.feed_names = list(feeds)
        self.loss_name = loss

    def step(self, feed: dict) -> np.ndarray:
        from ..core.executor import scope_guard

        with scope_guard(self.scope):
            (loss,) = self.exe.run(self.main, feed=feed,
                                   fetch_list=[self.loss_name], sync=True)
        return np.asarray(loss)

    def save(self, dirname: str) -> None:
        from .. import io
        from ..core.executor import scope_guard

        with scope_guard(self.scope):
            # the original startup travels with every checkpoint: load
            # runs it first (creating every persistable and the RNG
            # machinery) and the saved state then overwrites it, so the
            # checkpoint resumes exactly
            io.save_train_model(dirname, self.feed_names, self.loss_name,
                                self.exe, main_program=self.main,
                                startup_program=self.startup)


def create(model_dir: str) -> int:
    return _registry.add(_NativeTrainer(model_dir))


def feed_names(handle: int) -> List[str]:
    return _registry.get(handle).feed_names


def step(handle: int,
         inputs: List[Tuple[str, str, tuple, bytes]]
         ) -> Tuple[str, tuple, bytes]:
    t = _registry.get(handle)
    feed = {}
    for name, dtype, shape, data in inputs:
        feed[name] = np.frombuffer(
            data, dtype=_np_dtype(dtype)).reshape(shape)
    loss = np.ascontiguousarray(t.step(feed))
    return (str(loss.dtype), tuple(int(d) for d in loss.shape),
            loss.tobytes())


def save(handle: int, dirname: str) -> None:
    _registry.get(handle).save(dirname)


def destroy(handle: int) -> None:
    _registry.pop(handle)
