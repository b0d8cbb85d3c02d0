"""Native C inference API end-to-end: build libpaddle_tpu_capi.so + the
pure-C smoke binary, save a trained mnist inference model, and run the
binary — a C caller that never imports Python itself (reference
capability: paddle/legacy/capi/capi.h deployment,
inference/api/paddle_inference_api.h:211 CreatePaddlePredictor)."""
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(REPO, "native")


def _save_mnist(tmpdir):
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.executor import Executor, Scope, scope_guard
    from paddle_tpu.core.program import Program, program_guard
    from paddle_tpu.models import mnist

    prog, startup = Program(), Program()
    prog.random_seed = 3
    with program_guard(prog, startup), unique_name.guard():
        images = fluid.layers.data("pixel", [1, 28, 28])
        label = fluid.layers.data("label", [1], dtype="int64")
        predict = mnist.cnn_model(images)
        cost = fluid.layers.mean(fluid.layers.cross_entropy(predict, label))
        fluid.optimizer.Adam(1e-3).minimize(cost)
    scope, exe = Scope(), Executor()
    rng = np.random.RandomState(0)
    with scope_guard(scope):
        exe.run(startup)
        feed = {"pixel": rng.randn(16, 1, 28, 28).astype("float32"),
                "label": rng.randint(0, 10, (16, 1)).astype("int64")}
        exe.run(prog, feed=feed, fetch_list=[cost.name], sync=True)
        fluid.io.save_inference_model(tmpdir, ["pixel"], [predict], exe,
                                      main_program=prog)


@pytest.mark.skipif(shutil.which("make") is None or shutil.which("cc") is None,
                    reason="no C toolchain")
def test_capi_mnist_end_to_end(tmp_path):
    model_dir = str(tmp_path / "mnist_infer")
    _save_mnist(model_dir)

    r = subprocess.run(["make", "libpaddle_tpu_capi.so", "test_capi_mnist"],
                       cwd=NATIVE, capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-800:]

    env = dict(os.environ)
    site = os.path.dirname(os.path.dirname(np.__file__))
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, site, env.get("PYTHONPATH", "")])
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([os.path.join(NATIVE, "test_capi_mnist"), model_dir],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, (r.stdout[-400:], r.stderr[-800:])
    assert "OK: mnist inference via C API" in r.stdout
