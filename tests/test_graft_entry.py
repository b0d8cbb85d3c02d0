"""Driver contact points (__graft_entry__.py): entry() must stay
jittable, and the dryrun parent must never touch the JAX backend (one
process per chip: a parent that initialized a backend would hold the
device)."""
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def test_entry_returns_jittable_forward():
    import jax

    import __graft_entry__ as g

    fn, args = g.entry()
    loss = jax.jit(fn)(*args)
    assert np.isfinite(float(np.asarray(loss)))


def test_dryrun_parent_never_initializes_jax_backend():
    """Importing the module and taking the dryrun's parent path must not
    create a JAX backend in the parent process — checked in a clean
    subprocess by stubbing the child re-exec."""
    code = r"""
import sys, types
import __graft_entry__ as g
# importing jax is harmless; BACKEND INITIALIZATION is what claims the
# device
from jax._src import xla_bridge as xb
assert not xb._backends, "a JAX backend is already initialized"

# intercept the child spawn: the parent must reach Popen without ever
# initializing a backend
import subprocess
calls = {}
class FakeProc:
    returncode = 0
    stdout = iter(())
    def poll(self):
        return 0
    def wait(self, timeout=None):
        return 0
real_popen = subprocess.Popen
def fake_popen(cmd, **kw):
    calls["cmd"] = cmd
    assert "_PADDLE_TPU_DRYRUN_REEXEC" in kw["env"]
    assert kw["env"]["JAX_PLATFORMS"] == "cpu"
    return FakeProc()
subprocess.Popen = fake_popen
try:
    g.dryrun_multichip(8)
finally:
    subprocess.Popen = real_popen
assert "cmd" in calls, "parent never spawned the CPU-mesh child"
assert not xb._backends, "dryrun parent initialized a JAX backend"
print("PARENT_CLEAN")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("_PADDLE_TPU_DRYRUN_REEXEC", None)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, (r.stdout[-400:], r.stderr[-800:])
    assert "PARENT_CLEAN" in r.stdout
