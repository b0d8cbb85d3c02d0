"""Platform layer + flags system (reference platform/place.h,
device_context.h:200 pool, and the gflags env bootstrap
python/paddle/fluid/__init__.py:112-132)."""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import unique_name
from paddle_tpu.core.executor import Executor, Scope
from paddle_tpu.core.program import Program, program_guard


def test_places_and_pool():
    p0 = fluid.TPUPlace(0)
    assert p0 == fluid.TPUPlace(0) and p0 != fluid.CPUPlace()
    assert fluid.CUDAPlace is fluid.TPUPlace  # compat alias
    pool = fluid.DeviceContextPool.instance()
    ctx = pool.get(fluid.CPUPlace())
    assert pool.get(fluid.CPUPlace()) is ctx  # keyed by place
    assert ctx.platform == "cpu"
    ctx.synchronize()
    assert fluid.device_count() >= 1
    assert len(fluid.tpu_places()) == fluid.device_count()


def test_tpu_place_never_resolves_to_another_backend():
    """A TPUPlace names a TPU or raises: on this CPU mesh both the
    device context and the Executor refuse it with a message naming the
    platform found, while None / CPUPlace keep running on the default
    backend."""
    with pytest.raises(RuntimeError, match="no TPU.*'cpu'"):
        fluid.DeviceContextPool.instance().get(fluid.TPUPlace(0))
    with pytest.raises(RuntimeError, match="no TPU.*'cpu'"):
        fluid.Executor(fluid.TPUPlace())
    assert fluid.Executor(fluid.CPUPlace()).place == fluid.CPUPlace()
    assert fluid.Executor().place is None


def test_flags_env_types_and_api():
    assert fluid.get_flags("check_nan_inf") is False
    assert fluid.get_flags("FLAGS_benchmark") is False
    multi = fluid.get_flags(["check_nan_inf", "rpc_deadline"])
    assert multi == {"check_nan_inf": False, "rpc_deadline": 120.0}
    fluid.set_flags({"FLAGS_rpc_deadline": "60"})
    assert fluid.get_flags("rpc_deadline") == 60.0
    fluid.set_flags({"rpc_deadline": 120.0})
    with pytest.raises(KeyError):
        fluid.get_flags("no_such_flag")
    with pytest.raises(KeyError):
        fluid.set_flags({"no_such_flag": 1})


@pytest.mark.parametrize("name", [
    # a DecodeEngine is set up by its constructor's arguments alone
    "decode_max_slots", "decode_max_queue", "decode_block_tokens",
    "decode_prefill_buckets", "decode_kv_dtype", "decode_prefix_cache",
    "decode_overcommit",
    # accepted for parity, read by nothing
    "eager_delete_tensor_gb", "fraction_of_gpu_memory_to_use",
    "cpu_deterministic", "paddle_num_threads"])
def test_a_removed_flag_is_a_name_the_registry_never_had(name):
    with pytest.raises(KeyError):
        fluid.get_flags(name)
    with pytest.raises(KeyError):
        fluid.set_flags({"FLAGS_" + name: 1})


def test_check_nan_inf_flag_catches_bad_values():
    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        x = fluid.layers.data("x", [2])
        y = fluid.layers.log(x)  # log of a negative → NaN
    exe = Executor()
    scope = Scope()
    exe.run(startup, scope=scope)
    bad = np.array([[-1.0, 2.0]], "float32")
    # off: NaN flows silently (reference default)
    (out,) = exe.run(prog, feed={"x": bad}, fetch_list=[y], scope=scope)
    assert np.isnan(out).any()
    fluid.set_flags({"check_nan_inf": True})
    try:
        with pytest.raises(FloatingPointError, match="NaN/Inf"):
            exe.run(prog, feed={"x": bad}, fetch_list=[y], scope=scope)
        ok = np.array([[1.0, 2.0]], "float32")
        exe.run(prog, feed={"x": ok}, fetch_list=[y], scope=scope)
    finally:
        fluid.set_flags({"check_nan_inf": False})
def test_check_nan_inf_bf16():
    import numpy as np
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.executor import Executor, Scope
    from paddle_tpu.core.program import Program, program_guard
    import pytest
    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        x = fluid.layers.data("x", [2], dtype="bfloat16")
        y = fluid.layers.log(x)
    exe = Executor(); scope = Scope(); exe.run(startup, scope=scope)
    fluid.set_flags({"check_nan_inf": True})
    try:
        with pytest.raises(FloatingPointError):
            exe.run(prog, feed={"x": np.array([[-1.0, 2.0]], "float32")},
                    fetch_list=[y], scope=scope)
    finally:
        fluid.set_flags({"check_nan_inf": False})


def test_contrib_introspection_tools():
    import paddle_tpu as fluid
    from paddle_tpu.contrib import memory_usage, op_freq_statistic
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.program import Program, program_guard

    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        x = fluid.layers.data("x", [16])
        h = fluid.layers.fc(x, 8, act="relu")
        fluid.layers.fc(h, 2)
    lo, hi, unit = memory_usage(prog, batch_size=32)
    assert 0 < lo < hi and unit in ("B", "KB", "MB", "GB", "TB")
    uni, adj = op_freq_statistic(prog)
    assert uni.get("mul", 0) == 2 and uni.get("relu", 0) == 1
    assert any("->" in k for k in adj)


def test_tools_kube_gen_job_and_timeline(tmp_path):
    """tools/ parity (SURVEY §2.12): the k8s job generator emits the
    PADDLE_* env contract + registry wiring; timeline.py merges span
    dumps with per-input pids."""
    import json
    import os
    import subprocess
    import sys

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(here, "tools", "kube_gen_job.py"),
         "--jobname", "t", "--image", "img", "--entry", "python x.py",
         "--registry", "reg:7000", "--outdir", str(tmp_path)],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    ps = json.load(open(tmp_path / "pserver.yaml"))
    tn = json.load(open(tmp_path / "trainer.yaml"))
    svc = json.load(open(tmp_path / "service.yaml"))
    envs = {e["name"]: e["value"] for e in
            ps["spec"]["template"]["spec"]["containers"][0]["env"]}
    assert envs["PADDLE_TRAINING_ROLE"] == "PSERVER"
    assert envs["FLAGS_pserver_registry"] == "reg:7000"
    # identity + DNS mechanics: Indexed jobs, headless service subdomain,
    # shell-exported per-pod identity (kubelet can't expand
    # JOB_COMPLETION_INDEX in user env)
    assert ps["spec"]["completionMode"] == "Indexed"
    assert tn["spec"]["completionMode"] == "Indexed"
    assert svc["spec"]["clusterIP"] == "None"
    assert ps["spec"]["template"]["spec"]["subdomain"] == "t-svc"
    ps_cmd = ps["spec"]["template"]["spec"]["containers"][0]["command"][2]
    assert "PADDLE_CURRENT_ENDPOINT=" in ps_cmd
    assert "$JOB_COMPLETION_INDEX" in ps_cmd
    tn_cmd = tn["spec"]["template"]["spec"]["containers"][0]["command"][2]
    assert "PADDLE_TRAINER_ID=" in tn_cmd
    tn_envs = {e["name"]: e["value"] for e in
               tn["spec"]["template"]["spec"]["containers"][0]["env"]}
    assert "t-trainer-0.t-svc:" in tn_envs["PADDLE_TRAINER_ENDPOINTS"]

    a, b = tmp_path / "a.json", tmp_path / "b.json"
    json.dump({"traceEvents": [{"name": "x", "ph": "X", "ts": 0,
                                "dur": 5, "tid": 1}]}, open(a, "w"))
    json.dump({"traceEvents": [{"name": "y", "ph": "X", "ts": 1,
                                "dur": 2, "tid": 1}]}, open(b, "w"))
    out = tmp_path / "tl.json"
    r = subprocess.run(
        [sys.executable, os.path.join(here, "tools", "timeline.py"),
         "--profile_path", f"{a},{b}", "--timeline_path", str(out)],
        capture_output=True, text=True)
    assert r.returncode == 0, r.stderr
    tl = json.load(open(out))
    assert {e.get("pid") for e in tl["traceEvents"]} == {0, 1}


def test_reference_top_level_compat_names():
    """The reference fluid top-level __all__ resolves completely,
    including the traps: ``fluid.annotations`` must be the module (not
    the __future__ _Feature the import system short-circuits to), and
    learning_rate_decay is the scheduler module under its reference
    spelling."""
    import warnings

    import paddle_tpu as fluid

    assert callable(fluid.annotations.deprecated)

    @fluid.annotations.deprecated("1.0", "new_api")
    def legacy():
        return 7

    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert legacy() == 7
        assert any(issubclass(x.category, DeprecationWarning) for x in w)

    assert fluid.learning_rate_decay.exponential_decay is \
        fluid.layers.learning_rate_scheduler.exponential_decay
    assert fluid.LoDTensorArray is list
    assert fluid.CUDAPinnedPlace() == fluid.CUDAPinnedPlace()
    assert fluid.CUDAPinnedPlace() != fluid.CPUPlace()
