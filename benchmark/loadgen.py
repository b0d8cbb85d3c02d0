"""One general load generator for a served model: request lists from a mix
file and a seed, a closed and an open loop, and the per-request record the
failure accounting and the latency metrics are computed from.

What a mix may ask for is checked against the engine's own admission sizes
before anything is sent (:func:`validate_serve_mix`): a mix whose numbers
allow the server to shed or to refuse a request as too long is a
configuration error, so ``shed`` and ``too_long`` cannot happen to a healthy
server, however the threads interleave.

Every seed sends the same lengths and arrival gaps in another cyclic order:
they are the stratified quantiles of the mix's distributions in an order the
mix fixes, and the seed chooses where the cycle starts, so runs of different
seeds do the same work.  Token ids come from the seed.
"""
from __future__ import annotations

import math
import threading
import time
from statistics import NormalDist
from typing import Callable, List, Optional

import numpy as np

from benchmark.harness import Accounting, ConfigurationError, percentile


class Request:
    __slots__ = ("idx", "prompt", "max_new", "due_s", "t_send", "t_tokens",
                 "tokens", "finish", "failure", "detail", "done")

    def __init__(self, idx: int, prompt: np.ndarray, max_new: int,
                 due_s: Optional[float] = None):
        self.idx = idx
        self.prompt = prompt
        self.max_new = int(max_new)
        self.due_s = due_s          # open loop: seconds from window start
        self.t_send: Optional[float] = None
        self.t_tokens: List[float] = []
        self.tokens: List[int] = []
        self.finish: Optional[str] = None
        self.failure: Optional[str] = None
        self.detail = ""
        self.done = False


def blocks_for(tokens: int, block_tokens: int) -> int:
    return -(-int(tokens) // int(block_tokens))


def _lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles of a clipped log-normal, as whole numbers."""
    if dist.get("dist") != "lognormal":
        raise ConfigurationError(f"length distribution {dist!r}: only "
                                 "'lognormal' is known")
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    return np.clip(np.rint(raw), int(dist["min"]), int(dist["max"])).astype(int)


def _window_count(mix: dict, seconds: float) -> int:
    return int(math.floor(float(mix["rate_per_s"]) * seconds + 1e-9))


def validate_serve_mix(mix: dict, config: dict, seconds: float) -> None:
    """Rule 3 of ISSUE 24: the mix's own numbers must make ``shed`` and
    ``too_long`` impossible for a healthy server."""
    eng = mix.get("engine")
    need = ("max_slots", "max_queue", "prefill_buckets", "num_blocks",
            "block_tokens")
    if not isinstance(eng, dict) or any(k not in eng for k in need):
        raise ConfigurationError(
            f"the mix must write the engine's admission sizes {need} itself; "
            "none is left to a flag default")
    ctx = int(config["max_seq_len"])
    ladder = sorted(int(b) for b in eng["prefill_buckets"])
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    if not ladder or ladder[-1] > ctx:
        raise ConfigurationError(
            f"prefill ladder {ladder} reaches past the context {ctx}")
    if int(p["max"]) > ladder[-1] or int(p["min"]) < 1:
        raise ConfigurationError(
            f"prompts of {p['min']}..{p['max']} tokens do not lie on the "
            f"prefill ladder {ladder}: the engine would answer RequestTooLong")
    if int(p["max"]) + int(o["max"]) > ctx or int(o["min"]) < 1:
        raise ConfigurationError(
            f"prompt {p['max']} + output {o['max']} exceeds the context "
            f"{ctx}: the engine would answer RequestTooLong")
    if blocks_for(int(p["max"]) + int(o["max"]), eng["block_tokens"]) \
            > int(eng["num_blocks"]) - 1:
        raise ConfigurationError("one request needs more blocks than the pool")
    loop = mix.get("loop")
    if loop == "closed":
        # The engine refuses a submit when max_queue requests already wait.
        # Slots fill only between dispatches, so while a prefill runs (and
        # at the start, when every caller sends at once) all the other
        # callers can be waiting together: callers - 1 must stay under
        # max_queue.  ISSUE 24's bound, max_slots + max_queue // 2, holds once
        # the slots are full; it is kept beside the one that always holds.
        callers = int(mix["callers"])
        bound = int(eng["max_slots"]) + int(eng["max_queue"]) // 2
        if callers > int(eng["max_queue"]) or callers > bound:
            raise ConfigurationError(
                f"{callers} closed-loop callers exceed max_queue = "
                f"{eng['max_queue']} or max_slots + max_queue // 2 = {bound}: "
                "the queue bound could be reached and the engine would shed "
                "by a race")
    elif loop == "open":
        total = _window_count(mix, seconds) + \
            _window_count(mix, float(mix.get("lead_s", 0.0)))
        if int(eng["max_queue"]) < total:
            raise ConfigurationError(
                f"the window sends {total} requests and max_queue is "
                f"{eng['max_queue']}: the queue must be able to delay every "
                "one of them and refuse none")
    else:
        raise ConfigurationError(f"serve mix loop {loop!r}: 'closed' or 'open'")


def _cycle(mix: dict, n: int, block: int, rng) -> tuple:
    """``n`` (prompt, output) lengths in the mix's own fixed order: blocks of
    ``block`` requests, each block the stratified quantiles of both
    distributions in an order drawn from ``rng``, so that any stretch of the
    cycle holds a fair sample of both."""
    plen, olen = [], []
    for at in range(0, n, block):
        m = min(block, n - at)
        plen.extend(rng.permutation(_lengths(mix["prompt_tokens"], m)))
        olen.extend(rng.permutation(_lengths(mix["output_tokens"], m)))
    return np.asarray(plen), np.asarray(olen)


def build_requests(mix: dict, vocab: int, seed: int, seconds: float
                   ) -> List[Request]:
    """The run's requests, in sending order.

    The mix fixes one cycle of requests — lengths and, in an open loop, the
    gaps between arrivals — from its own ``cycle_seed``; ``--seed`` chooses
    where in the cycle the run starts and draws the token ids.  So every seed
    sends the same sizes and arrivals in another (cyclic) order, bursts
    included, and what differs from run to run is the system, not the load.
    Open loop: the lead-in's cycle (negative ``due_s``), then the window's.
    Closed loop: the cycle repeated, longer than the callers can reach."""
    fixed = np.random.default_rng(int(mix["cycle_seed"]))
    rng = np.random.default_rng(int(seed))
    block = int(mix.get("request_block", 16))

    def tokens(n):
        return rng.integers(0, vocab, size=int(n)).astype(np.int32)

    out: List[Request] = []
    if mix["loop"] == "open":
        rate = float(mix["rate_per_s"])
        lead = float(mix.get("lead_s", 0.0))
        for n, t0 in ((_window_count(mix, lead), -lead),
                      (_window_count(mix, seconds), 0.0)):
            if n <= 0:
                continue
            gaps = fixed.permutation(
                -np.log(1.0 - (np.arange(n) + 0.5) / n) / rate)
            plen, olen = _cycle(mix, n, block, fixed)
            turn = int(rng.integers(n))
            dues = t0 + np.cumsum(np.roll(gaps, -turn))
            for i, (p, o) in enumerate(zip(np.roll(plen, -turn),
                                           np.roll(olen, -turn))):
                out.append(Request(len(out), tokens(p), int(o), float(dues[i])))
        if out and out[-1].due_s >= seconds:
            raise ConfigurationError("an arrival falls past the window")
        return out
    n = block * int(mix["cycle_blocks"])
    plen, olen = _cycle(mix, n, block, fixed)
    turn = int(rng.integers(n))
    for i in range(int(mix["max_requests"])):
        j = (turn + i) % n
        out.append(Request(i, tokens(plen[j]), int(olen[j])))
    return out


def classify(exc: BaseException) -> str:
    from paddle_tpu.serving.batcher import (Draining, Overloaded,
                                            RequestTooLong)
    if isinstance(exc, (Overloaded, Draining)):
        return "shed"
    if isinstance(exc, RequestTooLong):
        return "too_long"
    if isinstance(exc, TimeoutError):
        return "timeout"
    return "error"


SEND_SPAN, RECV_SPAN = "bench.serve.send", "bench.serve.recv"


def stream_one(client, model: str, req: Request) -> None:
    """Send one request and read its stream to the final frame.  Every token
    is stamped at its arrival; whatever goes wrong is recorded under one of
    the named classes and never raised."""
    req.t_send = time.perf_counter()
    try:
        gen = client.generate_stream(
            model, req.prompt, max_new_tokens=req.max_new,
            temperature=0.0, seed=req.idx)
        while True:
            try:
                tok = next(gen)
            except StopIteration as stop:
                final = stop.value
                break
            req.t_tokens.append(time.perf_counter())
            req.tokens.append(tok)
        if not final:
            req.failure, req.detail = "error", "stream ended with no final frame"
        else:
            req.finish = final.get("finish")
            if len(req.tokens) < req.max_new and req.finish != "length":
                req.failure = "short"
                req.detail = (f"{len(req.tokens)} of {req.max_new} tokens, "
                              f"finish {req.finish!r}")
    except Exception as e:  # classified; the run goes on
        req.failure = classify(e)
        req.detail = f"request {req.idx}: {e!r}"
    req.done = True


class LoadResult:
    def __init__(self, w0: float, w1: float, sent: List[Request],
                 lag_ms: List[float], pulse: tuple = (0.0, 0.0)):
        self.w0, self.w1 = w0, w1
        self.sent = sent            # every request sent, lead-in included
        self.lag_ms = lag_ms        # open loop: how late each send was
        self.pulse = pulse          # (longest oversleep in ms, seconds into
        #                             the window) of a thread that only sleeps

    def in_window(self, req: Request) -> bool:
        """Sent within the window: an open loop's request by its schedule (a
        request due in the window is one of its operations however late the
        generator sent it), a closed loop's by its send."""
        if req.t_send is None:
            return False
        if req.due_s is not None:
            return req.due_s >= 0.0
        return self.w0 <= req.t_send < self.w1


def run_load(client, model: str, mix: dict, requests: List[Request],
             seconds: float, on_window: Callable = None,
             send: Callable = stream_one) -> LoadResult:
    """Drive the mix's loop.  The window opens ``lead_s`` after the first
    send and lasts ``seconds``; after it closes nothing more is sent and every
    request in flight is drained — waited for outside the window, under the
    mix's fixed ``drain_timeout_s``.  Nothing is cancelled.  ``on_window`` is
    called at the opening (``"open"``) and the closing (``"close"``)."""
    lead = float(mix.get("lead_s", 0.0))
    sent: List[Request] = []
    lag_ms: List[float] = []
    threads: List[threading.Thread] = []
    lock = threading.Lock()
    t_start = time.perf_counter()
    w0, w1 = t_start + lead, t_start + lead + seconds

    def clock_events():
        time.sleep(max(0.0, w0 - time.perf_counter()))
        if on_window:
            on_window("open")
        time.sleep(max(0.0, w1 - time.perf_counter()))
        if on_window:
            on_window("close")

    pulse = [0.0, 0.0]

    def beat(period=0.01):
        """A thread that only sleeps: when it oversleeps, the whole process
        (or its machine) stalled, whatever the server was doing."""
        while time.perf_counter() < w1:
            t = time.perf_counter()
            time.sleep(period)
            over = (time.perf_counter() - t - period) * 1e3
            if t >= w0 and over > pulse[0]:
                pulse[:] = [over, t - w0]

    ticker = threading.Thread(target=clock_events, daemon=True,
                              name="bench-window")
    ticker.start()
    threading.Thread(target=beat, daemon=True, name="bench-pulse").start()

    if mix["loop"] == "closed":
        cursor = [0]

        def caller():
            while True:
                with lock:
                    if time.perf_counter() >= w1 or cursor[0] >= len(requests):
                        return
                    req = requests[cursor[0]]
                    cursor[0] += 1
                    sent.append(req)
                send(client, model, req)

        threads = [threading.Thread(target=caller, daemon=True,
                                    name=f"bench-caller-{i}")
                   for i in range(int(mix["callers"]))]
        for t in threads:
            t.start()
    else:
        for req in requests:
            due = w0 + req.due_s
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            now = time.perf_counter()
            lag_ms.append((now - due) * 1e3)
            sent.append(req)
            t = threading.Thread(target=send, args=(client, model, req),
                                 daemon=True, name=f"bench-req-{req.idx}")
            t.start()
            threads.append(t)

    ticker.join()
    deadline = time.perf_counter() + float(mix["drain_timeout_s"])
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.perf_counter()))
    for req in list(sent):
        if not req.done and req.failure is None:
            req.failure = "timeout"
            req.detail = (f"request {req.idx}: no final frame "
                          f"{mix['drain_timeout_s']} s after the window closed")
    return LoadResult(w0, w1, list(sent), lag_ms, tuple(pulse))


def account(result: LoadResult, acct: Accounting) -> None:
    for req in result.sent:
        acct.record(result.in_window(req), req.failure, req.detail)


def due_time(result: LoadResult, req: Request) -> float:
    """When the request was due: its schedule in an open loop (so the wait a
    stall imposes on later requests counts), its send otherwise."""
    return result.w0 + req.due_s if req.due_s is not None else req.t_send


def latency_samples(result: LoadResult):
    """(ttft_ms, tbt_ms) over the window's requests: time from when a request
    was due to its first token, and every gap between consecutive tokens of
    one stream at the client, wherever they arrive (the drain included)."""
    ttft, tbt = [], []
    for req in result.sent:
        if not result.in_window(req) or not req.t_tokens:
            continue
        ttft.append((req.t_tokens[0] - due_time(result, req)) * 1e3)
        t = req.t_tokens
        tbt.extend((t[i] - t[i - 1]) * 1e3 for i in range(1, len(t)))
    return ttft, tbt


def window_gap_p50_ms(result: LoadResult) -> Optional[float]:
    """The median gap between consecutive tokens of one stream at the client,
    over EVERY gap that ends inside the window — whichever request the stream
    belongs to, the lead-in's too, as :func:`served_tokens` counts.  What a
    reader of a long stream feels per token while the server is full.  One
    gap a stream is all that a pause of the whole machine lengthens, so the
    median stands where the window's rate loses the pause; None where no gap
    ended in the window."""
    gaps = [(req.t_tokens[i] - req.t_tokens[i - 1]) * 1e3
            for req in result.sent for i in range(1, len(req.t_tokens))
            if result.w0 <= req.t_tokens[i] < result.w1]
    return percentile(gaps, 0.5) if gaps else None


def served_tokens(result: LoadResult) -> int:
    """Prompt tokens, credited when the first token arrives, plus output
    tokens at their arrival, counted by arrival time inside the window —
    whichever request they belong to."""
    n = 0
    for req in result.sent:
        t = req.t_tokens
        if t and result.w0 <= t[0] < result.w1:
            n += int(req.prompt.size)
        n += sum(1 for x in t if result.w0 <= x < result.w1)
    return n


def host_spans(result: LoadResult):
    """The benchmark's own host spans of every request: ``bench.serve.send``
    from the send to the first token (connect, queue, prefill, first frame),
    ``bench.serve.recv`` from the first token to the last."""
    out = []
    for req in result.sent:
        if req.t_send is None:
            continue
        first = req.t_tokens[0] if req.t_tokens else time.perf_counter()
        out.append((SEND_SPAN, req.t_send, first))
        if len(req.t_tokens) > 1:
            out.append((RECV_SPAN, first, req.t_tokens[-1]))
    return out


def longest_silence(result: LoadResult) -> tuple:
    """(ms, seconds into the window) of the longest stretch of the window in
    which no token of any stream arrived: a stall of the whole server."""
    ts = sorted(t for req in result.sent for t in req.t_tokens
                if result.w0 <= t < result.w1)
    if len(ts) < 2:
        return (0.0, 0.0)
    k = max(range(1, len(ts)), key=lambda i: ts[i] - ts[i - 1])
    return ((ts[k] - ts[k - 1]) * 1e3, ts[k - 1] - result.w0)
