"""The runtime's one span primitive (``observability.trace.span``): spans of
the decode engine's thread and the executor's dispatch in the profiler's own
file, a name for every compiled program, and the two counters at the same
boundaries (``decode.<model>.queue_ms``, ``executor.build_ms``); and the CPU
time a ``cpu_span`` carries while a profiler listens (``cpu_ns``), every
assertion on it one-sided in the direction a loaded machine pushes."""
import glob
import math
import os
import re
import threading
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


def _spin(seconds, clock=time.perf_counter):
    end = clock() + seconds
    while clock() < end:
        pass


def _spin_until(stop):
    while not stop.is_set():
        pass


class _ClockedEvent(profiler.RecordEvent):
    cpu_clock = True


@pytest.fixture(scope="module")
def clocked(tmp_path_factory):
    """One session round a span that sleeps, one that spins, one that spins
    while a second thread spins for the interpreter too, a nest, a plain
    span, and one opened before the session began; with the CPU time the
    test stamped round the lone spin itself."""
    out = str(tmp_path_factory.mktemp("clocked"))
    stop = threading.Event()
    rival = threading.Thread(target=_spin_until, args=(stop,), daemon=True)
    early = _ClockedEvent("user_early")
    early.__enter__()
    jax.profiler.start_trace(out)
    try:
        early.__exit__(None, None, None)
        with _ClockedEvent("user_sleep"):
            time.sleep(0.05)
        with trace.cpu_span("clock::spin"):
            c0 = time.thread_time_ns()
            _spin(0.03, time.thread_time)
            mine = time.thread_time_ns() - c0
        with trace.cpu_span("clock::outer"):
            _spin(0.005)
            with trace.cpu_span("clock::inner"):
                _spin(0.01)
            with trace.span("clock::plain"), profiler.RecordEvent("user_plain"):
                pass
        rival.start()
        with _ClockedEvent("user_contended"):
            _spin(0.2)
    finally:
        stop.set()
        jax.profiler.stop_trace()
        rival.join(timeout=30)
    assert not rival.is_alive()
    (xplane,) = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                       "*.xplane.pb"))
    by_name = {s[0]: s for spans in _read(xplane)["spans"].values()
               for s in spans}
    return by_name, mine


def test_a_sleeping_span_was_off_the_cpu_and_a_spinning_one_on_it(clocked):
    by_name, mine = clocked
    _, lo, hi, args = by_name["user_sleep"]
    assert hi - lo >= 50e6 and args["cpu_ns"] < (hi - lo) / 2
    _, lo, hi, args = by_name["clock::spin"]
    # at least what the test stamped inside it, and no more than it lasted
    assert mine >= 30e6
    assert mine - 1e6 <= args["cpu_ns"] <= hi - lo + 1e6


def test_a_thread_that_queues_for_the_interpreter_reads_as_off_the_cpu(
        clocked):
    _, lo, hi, args = clocked[0]["user_contended"]
    assert hi - lo >= 0.2e9
    assert (hi - lo) - args["cpu_ns"] >= (hi - lo) / 5


def test_a_nested_span_carries_no_more_cpu_time_than_its_parent(clocked):
    by_name, _ = clocked
    (_, olo, ohi, outer), (_, ilo, ihi, inner) = (
        by_name["clock::outer"], by_name["clock::inner"])
    assert olo <= ilo and ihi <= ohi
    assert 0 < inner["cpu_ns"] <= outer["cpu_ns"] + 1e6


def test_a_plain_span_and_one_opened_before_the_session_carry_none(clocked):
    by_name, _ = clocked
    assert "cpu_ns" not in by_name["clock::plain"][3]
    assert "cpu_ns" not in by_name["user_plain"][3]
    assert "cpu_ns" not in by_name.get("user_early", (0, 0, 0, {}))[3]


def test_the_launch_spans_and_their_waits_carry_their_threads_cpu_time(
        traced):
    spans = _thread_with(traced, "decode::step")
    clocked = {"decode::step", "decode::step.wait", "decode::prefill",
               "decode::prefill.wait"}
    assert {s[0] for s in spans if "cpu_ns" in s[3]} == clocked
    for name, lo, hi, args in spans:
        if name in clocked:
            assert 0 <= args["cpu_ns"] <= hi - lo + 1e6
    for launch in (s for s in spans
                   if s[0] in ("decode::step", "decode::prefill")):
        (wait,) = [k for k in _children(spans, launch)
                   if k[0] == launch[0] + ".wait"]
        assert wait[3]["cpu_ns"] <= launch[3]["cpu_ns"] + 1e6


def test_no_cpu_clock_is_read_without_a_listener(monkeypatch):
    assert not jax.profiler.TraceAnnotation.is_enabled()
    calls = []
    for clock in ("thread_time_ns", "process_time_ns"):
        real = getattr(time, clock)
        monkeypatch.setattr(time, clock, lambda real=real, clock=clock: (
            calls.append(clock), real())[1])
    engine = _engine("unheard")
    try:
        _serve(engine, prompts=(5, 12), new_tokens=3)
    finally:
        engine.close()
    with _ClockedEvent("user_step"), trace.cpu_span("decode::step") as sp:
        sp.annotate(live=0)
    assert calls == []


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
