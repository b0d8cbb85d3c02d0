"""The runtime's one span primitive (``observability.trace.span``): spans of
the decode engine's thread and the executor's dispatch in the profiler's own
file, a name for every compiled program, and the two counters at the same
boundaries (``decode.<model>.queue_ms``, ``executor.build_ms``)."""
import glob
import math
import os
import re
import time

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import observability as obs
from paddle_tpu import profiler
from paddle_tpu.core import unique_name
from paddle_tpu.core.executor import Executor, Scope, _program_name
from paddle_tpu.core.program import Program, program_guard
from paddle_tpu.decode import (DecodeEngine, LMConfig, SamplingParams,
                               TransformerLM)
from paddle_tpu.observability import trace

TINY = LMConfig(vocab=48, d_model=32, n_head=2, d_ffn=48, n_layer=2,
                max_seq_len=32)
PROMPTS = (5, 12, 7, 16)        # both rungs of the ladder (8, 16)


def _engine(name="spans"):     # not "lm": counters are per name, process-wide
    lm = TransformerLM(TINY)
    return DecodeEngine(lm, lm.init_params(seed=5), name=name, max_slots=3,
                        block_tokens=4, prefill_buckets=(8, 16))


def _serve(engine, prompts=PROMPTS, new_tokens=4):
    rng = np.random.RandomState(0)
    handles = [engine.submit(rng.randint(1, TINY.vocab, size=n),
                             SamplingParams(max_new_tokens=new_tokens))
               for n in prompts]
    for h in handles:
        assert len(h.result(timeout=120)["tokens"]) == new_tokens
    assert engine.drain(timeout=60)


def _tiny_trainer():
    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        x = fluid.layers.data("x", [4])
        y = fluid.layers.fc(x, size=3)
        loss = fluid.layers.mean(y)
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return prog, startup, loss


def _read(xplane):
    """``{"spans": {thread: [(name, start, end, args)...]}, "modules":
    set}`` of one trace: every ``::`` or ``user_`` host event by thread
    line, and the names of the compiled programs that ran."""
    data = jax.profiler.ProfileData.from_file(xplane)
    spans, modules, thread = {}, set(), 0
    for plane in data.planes:
        for line in plane.lines:
            thread += 1
            for e in line.events:
                stats = dict(e.stats)
                if "hlo_module" in stats:
                    modules.add(stats["hlo_module"])
                if "::" in e.name or e.name.startswith("user_"):
                    spans.setdefault(thread, []).append(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns,
                         stats))
    return {"spans": spans, "modules": modules}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One ``jax.profiler`` session on the CPU around four requests through
    a tiny engine, two ``run_steps`` calls and a user ``RecordEvent``."""
    out = str(tmp_path_factory.mktemp("trace"))
    prog, startup, loss = _tiny_trainer()
    scope, exe = Scope(), Executor()
    exe.run(startup, scope=scope)
    engine = _engine()
    feed = {"x": np.ones((3, 2, 4), "float32")}
    jax.profiler.start_trace(out)
    try:
        _serve(engine, PROMPTS[:2])
        # the engine's thread blocks for work between the two batches,
        # inside the session: wait until it is seen waiting on its lock
        deadline = time.monotonic() + 60
        while not engine._lock._waiters and time.monotonic() < deadline:
            time.sleep(0.005)
        assert engine._lock._waiters
        _serve(engine, PROMPTS[2:])
        with profiler.RecordEvent("user_train_loop", calls=2):
            for _ in range(2):
                exe.run_steps(prog, feed=feed, fetch_list=[loss], scope=scope)
    finally:
        jax.profiler.stop_trace()
        engine.close()
    (xplane,) = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                       "*.xplane.pb"))
    return _read(xplane)


def _children(spans, parent):
    _, lo, hi, _ = parent
    return sorted((s for s in spans if s is not parent
                   and lo <= s[1] and s[2] <= hi), key=lambda s: s[1])


def _thread_with(traced, name):
    (spans,) = [s for s in traced["spans"].values()
                if any(n == name for n, *_ in s)]
    return spans


def test_a_decode_step_holds_its_children_in_order_on_one_thread(traced):
    spans = _thread_with(traced, "decode::step")   # one line has them all
    steps = sorted((s for s in spans if s[0] == "decode::step"),
                   key=lambda s: s[1])
    assert len(steps) >= 3
    at_once = [True]          # nothing is pending before the first step
    for step in steps:
        kids = [k for k in _children(spans, step)
                if k[0].startswith("decode::step.")
                or k[0] == "executor::dispatch"]
        names = [k[0] for k in kids]
        # the step before's tokens go out between this step's dispatch
        # and its wait (a batch's first step has none to hand out), and
        # a batch's last step hands its own out after booking them
        assert names[:3] == ["decode::step.retire", "decode::step.feed",
                             "executor::dispatch"]
        assert names[3:] in (
            ["decode::step.wait", "decode::step.book"],
            ["decode::step.emit", "decode::step.wait", "decode::step.book"],
            ["decode::step.wait", "decode::step.book", "decode::step.emit"],
            ["decode::step.emit", "decode::step.wait", "decode::step.book",
             "decode::step.emit"])
        # ... behind the dispatch exactly when the step before left them
        assert (names[3] == "decode::step.emit") == (not at_once[-1])
        at_once.append(names[-1] == "decode::step.emit")
        for a, b in zip(kids, kids[1:]):
            assert a[2] <= b[1]                    # never overlapping
        assert step[3]["live"] >= 1
        (disp,) = [k for k in kids if k[0] == "executor::dispatch"]
        assert disp[3]["key"] == "decode/spans/step"
        (call,) = [k for k in _children(spans, step)
                   if k[0] == "executor::run_callable"]
        assert call[3]["key"] == "decode/spans/step"
        inner = [k[0] for k in _children(spans, call)
                 if k[0].startswith("executor::")]
        assert inner in (["executor::feed", "executor::dispatch"],
                         ["executor::feed", "executor::lower",
                          "executor::dispatch"])      # the first: a miss
    # each of the two batches ended with a step that no step followed; a
    # stream's three steps cannot all be such
    assert sum(at_once[1:]) >= 2 and at_once[-1]
    assert not all(at_once)


def test_a_prefill_span_carries_its_request_and_its_queue_wait(traced):
    spans = _thread_with(traced, "decode::step")
    prefills = [s for s in spans if s[0] == "decode::prefill"]
    assert sorted(p[3]["rid"] for p in prefills) == sorted(
        {p[3]["rid"] for p in prefills}) and len(prefills) == len(PROMPTS)
    assert sorted(p[3]["prompt"] for p in prefills) == sorted(PROMPTS)
    for p in prefills:
        assert p[3]["bucket"] == (8 if p[3]["prompt"] <= 8 else 16)
        assert math.isfinite(p[3]["queue_ms"]) and p[3]["queue_ms"] >= 0
        kids = [k[0] for k in _children(spans, p)
                if k[0].startswith("decode::prefill.")
                or k[0] == "executor::dispatch"]
        assert kids == ["decode::prefill.feed", "executor::dispatch",
                        "decode::prefill.wait", "decode::prefill.emit"]
    admits = [s for s in spans if s[0] == "decode::admit"]
    assert sum(a[3]["admitted"] for a in admits) == len(PROMPTS)
    assert all(a[3]["pending"] >= a[3]["admitted"] for a in admits)
    # top-level spans of the engine's thread never overlap one another
    top = sorted((s for s in spans if re.fullmatch(
        r"decode::(wait_work|admit|prefill|step)", s[0])), key=lambda s: s[1])
    for a, b in zip(top, top[1:]):
        assert a[2] <= b[1]
    assert any(s[0] == "decode::wait_work" for s in top)


def test_run_steps_and_a_record_event_land_in_the_same_file(traced):
    spans = _thread_with(traced, "executor::run_steps")
    (user,) = [s for s in spans if s[0] == "user_train_loop"]
    assert user[3]["calls"] == 2
    calls = [s for s in spans if s[0] == "executor::run_steps"]
    assert len(calls) == 2 and all(
        user[1] <= c[1] and c[2] <= user[2] for c in calls)
    first, second = (
        [k[0] for k in _children(spans, c) if k[0].startswith("executor::")]
        for c in calls)
    assert first == ["executor::feed", "executor::lower",
                     "executor::dispatch", "executor::fetch"]
    assert second == ["executor::feed", "executor::dispatch",
                      "executor::fetch"]           # a hit lowers nothing


def test_every_compiled_program_has_its_own_name(traced):
    assert _program_name("decode/lm/step") == "fn_decode_lm_step"
    assert _program_name("decode/lm/prefill/128") == "fn_decode_lm_prefill_128"
    assert _program_name("decode/m-1/beam_prefill/8") == \
        "fn_decode_m_1_beam_prefill_8"
    mine = {m for m in traced["modules"] if "decode" in m}
    assert mine == {"jit_fn_decode_spans_step",
                    "jit_fn_decode_spans_prefill_8",
                    "jit_fn_decode_spans_prefill_16"}
    # what the benchmark's decode_step_ms/prefill_ms readers select by
    assert all(re.search("^jit_fn", m) for m in mine)


def test_the_executor_cache_holds_the_callable_under_its_key_name():
    engine = _engine("named")
    try:
        _serve(engine, prompts=(5,), new_tokens=2)
        names = {k[1]: e.jitted.__name__
                 for k, e in engine._exe._cache.items()
                 if isinstance(k, tuple) and k[0] == "callable"}
    finally:
        engine.close()
    assert names == {"decode/named/step": "fn_decode_named_step",
                     "decode/named/prefill/8": "fn_decode_named_prefill_8"}


def test_a_span_leaves_nothing_behind_without_a_listener():
    assert not profiler.is_profiler_enabled()
    events, ring = profiler.events(), trace.total_spans_recorded()
    with trace.span("decode::step", live=1) as sp:
        sp.annotate(admitted=0)
        with trace.span("decode::step.feed"):
            pass
    assert profiler.events() == events
    assert trace.total_spans_recorded() == ring
    assert not hasattr(trace, "emit") and not hasattr(trace, "enabled")


def test_a_span_is_filed_under_runtime_while_the_profiler_is_armed(capsys):
    profiler.reset_profiler()
    profiler.start_profiler("All")
    try:
        with trace.span("decode::step", live=2):
            pass
        with profiler.RecordEvent("user_step"):
            pass
    finally:
        profiler.stop_profiler()
    capsys.readouterr()
    by_name = {e["name"]: e for e in profiler.events()}
    assert by_name["runtime::decode::step"]["cat"] == "runtime"
    assert by_name["user_step"]["cat"] == "op"
    profiler.reset_profiler()


def test_queue_ms_counts_one_observation_per_prefill():
    engine = _engine("queued")
    try:
        _serve(engine)
        z = engine.decodez()
        assert engine.stats.queue_ms.count == engine.stats.prefills.value \
            == z["prefills"] == len(PROMPTS)
        assert z["queue_p50_ms"] >= 0 and z["queue_p99_ms"] >= z["queue_p50_ms"]
    finally:
        engine.close()


def test_build_ms_grows_on_a_miss_and_not_on_a_hit():
    prog, startup, loss = _tiny_trainer()
    scope, exe = Scope(), Executor()
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((2, 4), "float32")}

    def build_ms():
        return obs.snapshot().get("executor.build_ms", 0)

    b0 = build_ms()
    exe.run(prog, feed=feed, fetch_list=[loss], scope=scope)       # a miss
    b1 = build_ms()
    assert b1 > b0
    exe.run(prog, feed=feed, fetch_list=[loss], scope=scope)       # a hit
    assert build_ms() == b1
    exe.run_steps(prog, feed={"x": np.ones((3, 2, 4), "float32")},
                  fetch_list=[loss], scope=scope)                  # a miss
    b2 = build_ms()
    assert b2 > b1

    def build():
        return lambda feed, state, const: ([feed[0] + 1], [])

    exe.run_callable("t/build_ms", build, [np.zeros((2,), "float32")])
    b3 = build_ms()
    assert b3 > b2                                                 # a miss
    exe.run_callable("t/build_ms", build, [np.zeros((2,), "float32")])
    assert build_ms() == b3                                        # a hit
