"""Failure accounting that cannot depend on timing, on the CPU with a tiny
``TransformerLM`` behind the real ``DecodeServer``: a mix whose own numbers
allow shedding is refused before any request; requests in flight when the
window closes are drained and counted once; a forced ``Overloaded`` is
counted under ``shed``; a token mismatch changes ``correct`` and never
``failed``; the same seed gives the same request list."""
import copy
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import harness, loadgen  # noqa: E402

TINY = dict(vocab=97, d_model=32, n_head=2, d_ffn=64, n_layer=2,
            max_seq_len=64, dtype="float32", kv_dtype="float32",
            attn_impl="xla")
ENGINE = dict(max_slots=4, max_queue=8, block_tokens=16, num_blocks=17,
              prefill_buckets=[8, 16, 32])


def mix(loop, **over):
    m = {"loop": loop, "callers": 8, "rate_per_s": 25.0, "lead_s": 0.5,
         "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.7,
                           "min": 4, "max": 32},
         "output_tokens": {"dist": "lognormal", "median": 8, "sigma": 0.6,
                           "min": 2, "max": 16},
         "cycle_seed": 3, "request_block": 8, "cycle_blocks": 6,
         "max_requests": 4800, "drain_timeout_s": 60.0,
         "engine": dict(ENGINE)}
    if loop == "open":
        m["engine"]["max_queue"] = 512
    m.update(over)
    return m


@pytest.fixture(scope="module")
def serve():
    return harness.load_module(
        os.path.join(REPO, "benchmark", "drivers", "serve.py"),
        "bench_serve_driver_under_test")


@pytest.fixture(scope="module")
def params(serve):
    return serve.make_params(TINY)


@pytest.mark.parametrize("change, says", [
    (dict(callers=9), "closed-loop callers exceed"),
    (dict(loop="open", engine=dict(ENGINE, max_queue=20)), "max_queue is"),
    (dict(prompt_tokens={"dist": "lognormal", "median": 12, "sigma": 0.7,
                         "min": 4, "max": 33}), "prefill ladder"),
    (dict(output_tokens={"dist": "lognormal", "median": 8, "sigma": 0.6,
                         "min": 2, "max": 40}), "exceeds the context"),
    (dict(engine={"max_slots": 4}), "admission sizes"),
    (dict(engine=dict(ENGINE, prefill_buckets=[8, 128])), "past the context"),
    (dict(loop="poisson"), "'closed' or 'open'"),
])
def test_a_mix_that_allows_shed_or_too_long_is_refused_before_any_request(
        change, says):
    bad = mix(change.pop("loop", "closed"), **change)
    with pytest.raises(harness.ConfigurationError, match=says):
        loadgen.validate_serve_mix(bad, TINY, seconds=2.0)


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_a_sound_mix_passes(loop):
    loadgen.validate_serve_mix(mix(loop), TINY, seconds=2.0)


def test_the_closed_loop_bounds():
    """callers <= max_queue (all but one caller can wait at once before any
    slot is filled) and callers <= max_slots + max_queue // 2."""
    loadgen.validate_serve_mix(mix("closed", callers=8), TINY, 2.0)   # 8 and 4 + 8 // 2
    roomy = dict(ENGINE, max_slots=16)                 # 16 + 8 // 2 = 20 >= 9 ...
    with pytest.raises(harness.ConfigurationError, match="max_queue = 8"):
        loadgen.validate_serve_mix(                    # ... but 9 > max_queue
            mix("closed", callers=9, engine=roomy), TINY, 2.0)


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_the_same_seed_gives_the_same_requests(loop):
    seed = 2 ** 31 + 7
    a = loadgen.build_requests(mix(loop), 97, seed, 2.0)
    b = loadgen.build_requests(mix(loop), 97, seed, 2.0)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.max_new == y.max_new and x.due_s == y.due_s
        assert np.array_equal(x.prompt, y.prompt)


def test_another_seed_sends_the_same_sizes_and_gaps_in_another_order():
    a = loadgen.build_requests(mix("open"), 97, 1, 4.0)
    b = loadgen.build_requests(mix("open"), 97, 2, 4.0)
    # the same cycle, entered at another point: b's window is a's, rotated
    wa = [(r.prompt.size, r.max_new) for r in a if r.due_s >= 0]
    wb = [(r.prompt.size, r.max_new) for r in b if r.due_s >= 0]
    assert any(wa[k:] + wa[:k] == wb for k in range(1, len(wa)))
    assert len(a) == len(b) == 12 + 100          # 0.5 s lead-in + 4 s at 25/s
    size = lambda rs: sorted((r.prompt.size, r.due_s < 0) for r in rs)  # noqa: E731
    assert size(a) == size(b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [r.prompt.size for r in a] != [r.prompt.size for r in b]
    gaps = lambda rs: np.sort(np.diff(  # noqa: E731
        [0.0] + [r.due_s for r in rs if r.due_s >= 0]))
    assert np.allclose(gaps(a), gaps(b), atol=1e-9)   # one fixed multiset
    assert all(0 <= r.due_s < 4.0 for r in a if r.due_s >= 0)
    assert all(r.prompt.size + r.max_new <= 64 for r in a)


@pytest.fixture(scope="module")
def closed_run(serve, params):
    """One short closed-loop run against the real server."""
    the_mix = mix("closed")
    engine, server, client = serve.build_server(TINY, the_mix, params)
    try:
        serve.warm_up(client, TINY, the_mix)
        events = []
        result = loadgen.run_load(
            client, serve.MODEL, the_mix,
            loadgen.build_requests(the_mix, 97, 5, 1.5), 1.5,
            on_window=events.append)
        z = engine.decodez()
    finally:
        server.stop()
    return result, z, events


def test_requests_in_flight_at_the_close_are_drained_and_counted_once(closed_run):
    result, z, events = closed_run
    assert events == ["open", "close"]
    acct = harness.Accounting()
    loadgen.account(result, acct)
    ids = [r.idx for r in result.sent]
    assert len(ids) == len(set(ids)) == acct.attempted + acct.outside
    assert acct.outside > 0 and acct.attempted > 0      # lead-in and window
    assert acct.failed == 0 and acct.failed_outside == 0, acct.examples
    assert all(r.done and r.finish == "length" and len(r.tokens) == r.max_new
               for r in result.sent)
    in_flight = [r for r in result.sent
                 if r.t_send < result.w1 < r.t_tokens[-1]]
    assert in_flight, "no request was in flight when the window closed"
    assert all(result.in_window(r) for r in in_flight)
    assert max(r.t_send for r in result.sent) < result.w1   # nothing sent after
    assert z["joins"] == z["leaves"] and z["shed"] == 0
    # rates count tokens by arrival inside the window, not by sender
    inside = sum(1 for r in result.sent for t in r.t_tokens
                 if result.w0 <= t < result.w1)
    prompts = sum(r.prompt.size for r in result.sent
                  if result.w0 <= r.t_tokens[0] < result.w1)
    assert loadgen.served_tokens(result) == inside + prompts
    ttft, tbt = loadgen.latency_samples(result)
    assert len(ttft) == acct.attempted
    assert len(tbt) == sum(len(r.tokens) - 1 for r in result.sent
                           if result.in_window(r))


def test_a_token_mismatch_changes_correct_and_never_failed(serve, params,
                                                           closed_run):
    result, _, _ = closed_run
    good = harness.Checks()
    serve.check_sample(good, TINY, params, result, seed=3)
    assert good.ok, good.lines()
    spoiled = copy.copy(result)
    spoiled.sent = copy.deepcopy(result.sent)
    for r in spoiled.sent:
        r.tokens = [(t + 1) % 97 for t in r.tokens]
    bad = harness.Checks()
    serve.check_sample(bad, TINY, params, spoiled, seed=3)
    assert not bad.ok
    acct = harness.Accounting()
    loadgen.account(spoiled, acct)
    assert acct.failed == 0


def test_a_forced_overloaded_is_counted_under_shed(serve, params):
    """One slot and a queue of one, with six callers, validation bypassed:
    the engine's own typed Overloaded comes back over the wire."""
    the_mix = mix("closed", callers=6, lead_s=0.0,
                  engine=dict(ENGINE, max_slots=1, max_queue=1))
    with pytest.raises(harness.ConfigurationError):
        loadgen.validate_serve_mix(the_mix, TINY, 1.0)
    engine, server, client = serve.build_server(TINY, the_mix, params)
    try:
        result = loadgen.run_load(
            client, serve.MODEL, the_mix,
            loadgen.build_requests(the_mix, 97, 9, 1.0), 1.0)
        z = engine.decodez()
    finally:
        server.stop()
    acct = harness.Accounting()
    loadgen.account(result, acct)
    assert acct.by_class["shed"] > 0
    assert acct.by_class["shed"] == z["shed"] == acct.failed
    assert "shed=" in acct.line() and "Overloaded" in acct.examples[0]
    assert acct.attempted == len(result.sent)


def test_classes_of_failure():
    from paddle_tpu.serving.batcher import Draining, Overloaded, RequestTooLong
    assert loadgen.classify(Overloaded("lm", 3, 2)) == "shed"
    assert loadgen.classify(Draining("lm", "e")) == "shed"
    assert loadgen.classify(RequestTooLong("lm", "prompt", 9, 8)) == "too_long"
    assert loadgen.classify(TimeoutError()) == "timeout"
    assert loadgen.classify(ConnectionError("x")) == "error"
    acct = harness.Accounting()
    with pytest.raises(ValueError):
        acct.record(True, "mismatch")
    acct.record(True, "short", "3 of 8 tokens")
    acct.record(False, "error", "lead-in")
    assert (acct.attempted, acct.failed, acct.failed_outside) == (1, 1, 1)
    assert json.dumps(acct.by_class)


def _stream(times):
    r = loadgen.Request(0, np.zeros(4, np.int32), len(times))
    r.t_send, r.t_tokens, r.tokens = times[0] - 0.5, list(times), [1] * len(times)
    return r


def test_the_window_gap_median_counts_every_gap_that_ends_in_the_window():
    """Whichever request a stream belongs to (the lead-in's too): a gap counts
    where its later token arrives inside the window, as served_tokens counts."""
    early = _stream([8.0, 9.5, 10.5, 12.0])        # gaps 1500 | 1000, 1500 in
    late = _stream([11.0, 13.0, 19.5, 21.0])       # gaps 2000, 6500 in | 1500
    result = loadgen.LoadResult(10.0, 20.0, [early, late], [])
    # inside: 1000, 1500, 2000, 6500
    assert loadgen.window_gap_p50_ms(result) == pytest.approx(1750.0)
    assert loadgen.window_gap_p50_ms(
        loadgen.LoadResult(30.0, 40.0, [early, late], [])) is None
    silent = loadgen.Request(1, np.zeros(4, np.int32), 4)   # never answered
    silent.t_send = 11.0
    assert loadgen.window_gap_p50_ms(
        loadgen.LoadResult(10.0, 20.0, [early, late, silent], [])) \
        == pytest.approx(1750.0)


@pytest.mark.parametrize("pause_s", [0.0, 1.5, 4.5])
def test_a_pause_of_the_machine_moves_the_rate_and_not_the_gap_median(pause_s):
    """64 streams, a token each 25 ms for 45 s; the whole machine stands still
    once for ``pause_s``: the window's rate loses the pause, each stream has
    ONE long gap of 1,800, and the median does not move."""
    step, w0, w1 = 0.025, 1.0, 46.0

    def run(pause):
        streams = []
        for s in range(64):
            t, ts = 0.5 + 1e-4 * s, []
            while t < w1 + 1.0:
                ts.append(t)
                t += step + (pause if abs(t - 20.0) < step / 2 else 0.0)
            streams.append(_stream(ts))
        return loadgen.LoadResult(w0, w1, streams, [])

    calm, paused = run(0.0), run(pause_s)
    assert loadgen.window_gap_p50_ms(paused) == pytest.approx(
        loadgen.window_gap_p50_ms(calm), rel=1e-6) == pytest.approx(25.0)
    lost = 1.0 - loadgen.served_tokens(paused) / loadgen.served_tokens(calm)
    assert lost == pytest.approx(pause_s / 45.0, abs=2e-3)
