"""The plain references against the system at a small size on the CPU, where
float32 arithmetic is exact enough to hold them close: logits of the served LM
(full forward, and prefill then decoding through the paged cache), and the
trained Transformer's loss."""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402
from benchmark.reference import tlm, transformer_base  # noqa: E402

TINY_LM = dict(vocab=211, d_model=48, n_head=4, d_ffn=96, n_layer=3,
               max_seq_len=48, dtype="float32", kv_dtype="float32",
               attn_impl="xla")
# float32 on both sides, sums taken in another order: a few units in the
# seventh digit of logits whose scale is about 4
LOGIT_ATOL = 2e-5


@pytest.fixture(scope="module")
def serve():
    return harness.load_module(
        os.path.join(REPO, "benchmark", "drivers", "serve.py"),
        "bench_serve_driver_for_reference")


@pytest.fixture(scope="module")
def lm(serve):
    import jax.numpy as jnp
    from paddle_tpu.decode import LMConfig, TransformerLM
    params = serve.make_params(TINY_LM)
    model = TransformerLM(LMConfig(**{k: TINY_LM[k] for k in (
        "vocab", "d_model", "n_head", "d_ffn", "n_layer", "max_seq_len")}))
    return model, params, jnp


def test_the_lm_reference_agrees_with_the_systems_full_forward(lm):
    model, params, jnp = lm
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 211, size=(3, 40)), jnp.int32)
    lengths = jnp.asarray([40, 17, 29], jnp.int32)
    got = np.asarray(model.full_logits(model.param_list(params), tokens, lengths))
    want = np.asarray(tlm.forward(params, TINY_LM, tokens, lengths))
    for b, n in enumerate([40, 17, 29]):
        assert np.abs(got[b, :n] - want[b, :n]).max() <= LOGIT_ATOL
    assert np.abs(want).max() > 1.0          # the comparison is not of zeros


def test_the_lm_reference_has_its_own_positions_and_layer_norm():
    """Nothing is borrowed from the program: the table and the norm are
    checked against their definitions."""
    table = tlm.positions(5, 8)
    assert table[0].tolist() == [0, 1, 0, 1, 0, 1, 0, 1]
    assert table[3, 0] == pytest.approx(np.sin(3.0), abs=1e-6)
    assert table[3, 3] == pytest.approx(np.cos(3.0 / 10000 ** 0.25), abs=1e-6)
    x = np.array([[1.0, 2.0, 3.0, 6.0]], np.float32)
    y = np.asarray(tlm.layer_norm(x, 2.0, 0.5))
    want = (x - 3.0) / np.sqrt(3.5 + tlm.LN_EPS) * 2.0 + 0.5
    assert np.allclose(y, want, atol=1e-6)


def test_prefill_then_decoding_through_the_cache_agrees_with_the_reference(
        serve, lm):
    """The harness's own comparison (``check_sample``), here where every
    engine token must be the reference's argmax exactly or by a rounding tie."""
    from benchmark import loadgen
    model, params, jnp = lm
    mix = {"loop": "closed", "callers": 3, "lead_s": 0.0,
           "prompt_tokens": {"dist": "lognormal", "median": 10, "sigma": 0.6,
                             "min": 3, "max": 24},
           "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                             "min": 4, "max": 16},
           "cycle_seed": 1, "request_block": 6, "cycle_blocks": 2,
           "max_requests": 12, "drain_timeout_s": 60.0,
           "engine": {"max_slots": 2, "max_queue": 8, "block_tokens": 16,
                      "num_blocks": 7, "prefill_buckets": [8, 24]}}
    loadgen.validate_serve_mix(mix, TINY_LM, 1.0)
    engine, server, client = serve.build_server(TINY_LM, mix, params)
    try:
        result = loadgen.run_load(client, serve.MODEL, mix,
                                  loadgen.build_requests(mix, 211, 4, 1.0), 1.0)
    finally:
        server.stop()
    assert all(r.failure is None for r in result.sent)
    checks = harness.Checks()
    serve.check_sample(checks, TINY_LM, params, result, seed=1)
    assert checks.ok, checks.lines()
    # and directly: teacher-forced reference rows pick the engine's tokens
    r = max(result.sent, key=lambda q: len(q.tokens))
    seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
    logits = np.asarray(tlm.forward(
        params, TINY_LM, jnp.asarray(seq[None], jnp.int32),
        jnp.asarray([seq.size], jnp.int32)))[0]
    rows = logits[r.prompt.size - 1: r.prompt.size - 1 + len(r.tokens)]
    picked = rows[np.arange(len(r.tokens)), r.tokens]
    assert np.all(rows.max(axis=-1) - picked <= LOGIT_ATOL)


TINY_TF = dict(d_model=32, n_head=4, d_ffn=64, n_layer=2, src_vocab=89,
               tgt_vocab=89, dropout=0.1, warmup_steps=4000, dtype="float32",
               attention_impl="auto", program_seed=5)


def test_the_transformer_reference_agrees_with_the_systems_forward_loss():
    import jax.numpy as jnp
    import paddle_tpu as fluid
    from paddle_tpu.core.executor import Scope
    train = harness.load_module(
        os.path.join(REPO, "benchmark", "drivers", "train.py"),
        "bench_train_driver_for_reference")
    (prog, startup, (_, loss, _)), evalp = train.build_programs(TINY_TF, 12)
    scope = Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    feed = train.make_feed(np.random.default_rng(3), 2, 6, 12, 89)
    exe.run_steps(prog, feed=feed, fetch_list=[loss], scope=scope)   # train a little
    checks = harness.Checks()
    train.check_reference(checks, exe, scope, evalp, TINY_TF, feed, n_seq=4)
    assert checks.ok, checks.lines()
    # tighter than the chip's tolerance: float32 on both sides
    prog_e, _, (_, eloss, _) = evalp
    one = {k: np.asarray(v[1]) for k, v in feed.items()}
    (got,) = exe.run(prog_e, feed=one, fetch_list=[eloss], scope=scope)
    params = train.reference_params(scope, prog_e, TINY_TF)
    assert len([k for k in params if k.endswith(".b")]) == 8
    want = transformer_base.loss(
        {k: jnp.asarray(np.asarray(v), jnp.float32) for k, v in params.items()},
        TINY_TF, jnp.asarray(one["src_ids"], jnp.int32),
        jnp.asarray(one["tgt_ids"], jnp.int32),
        jnp.asarray(one["lbl_ids"], jnp.int32))
    assert abs(float(got) - float(want)) <= 1e-4 * abs(float(want))
    exe.close()
