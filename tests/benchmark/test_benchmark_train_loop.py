"""The train driver's measured loop on the CPU with a tiny Transformer:
with one call in flight or two, every step dispatched in the window is an
operation, every one is waited for after the window closes, and a call that
raises fails its own steps and no other's."""
import argparse
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402

TINY_TF = dict(kind="train", d_model=32, n_head=4, d_ffn=64, n_layer=2,
               src_vocab=89, tgt_vocab=89, dropout=0.1, warmup_steps=40,
               dtype="float32", attention_impl="auto", program_seed=5)
K = 3


def cell(tmp_path, **over):
    mix = dict(loop="steps", batch_per_chip=4, src_len=12, tgt_len=12,
               call="run_steps", steps_per_fetch=K, mesh=None,
               sample_sequences=4)
    mix.update(over)
    return types.SimpleNamespace(
        name="tiny_train", config=TINY_TF, mix=mix, chips=1,
        root=str(tmp_path))


@pytest.fixture(scope="module")
def train():
    return harness.load_module(
        os.path.join(REPO, "benchmark", "drivers", "train.py"),
        "bench_train_driver_under_test")


@pytest.fixture(scope="module")
def log():
    return harness.CompileLog()


def run(train, log, c, seconds=0.3):
    import jax
    args = argparse.Namespace(seed=3000000011, seconds=seconds, trace=0)
    return train.run(c, args, log, 0.0, jax.devices()[:1])


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_every_step_sent_in_the_window_is_counted_once_and_waited_for(
        train, log, tmp_path, depth):
    c = cell(tmp_path, calls_in_flight=depth)
    train.validate(c, 0.3)
    out = run(train, log, c)
    acct = out["acct"]
    assert acct.failed == 0 and acct.attempted >= depth * K
    assert acct.attempted % K == 0
    rate = out["values"]["train_tokens_per_s"]
    assert rate > 0
    failing = [l for l in out["checks"].lines()
               if "FAIL" in l and "lower at the end" not in l]
    assert not failing, failing
    # where the run's time went, for the ``bench time:`` line of a --trace 0 run
    assert [n for n, _ in out["phases"].phases] == [
        "setup", "window", "report", "reference_check"]
    assert out["phases"].line().startswith("bench time: ")


@pytest.mark.parametrize("depth", [0, 5])
def test_a_depth_outside_one_to_four_is_a_configuration_error(
        train, tmp_path, depth):
    with pytest.raises(harness.ConfigurationError, match="calls_in_flight"):
        train.validate(cell(tmp_path, calls_in_flight=depth), 1.0)


@pytest.mark.parametrize("depth", [1, 2])
def test_a_call_that_raises_fails_its_own_steps_only(
        train, log, tmp_path, monkeypatch, depth):
    import paddle_tpu as fluid
    real = fluid.Executor.run_steps
    calls = {"n": 0}

    def flaky(self, *a, **kw):
        calls["n"] += 1
        if calls["n"] == 4:                 # two warm calls, then the second
            raise RuntimeError("injected")  # call of the window
        return real(self, *a, **kw)

    monkeypatch.setattr(fluid.Executor, "run_steps", flaky)
    out = run(train, log, cell(tmp_path, calls_in_flight=depth), seconds=5.0)
    acct = out["acct"]
    assert acct.attempted == 2 * K
    assert acct.failed == K and acct.by_class["error"] == K
    assert not out["checks"].ok or acct.failed
