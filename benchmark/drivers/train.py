"""Driver of the ``train`` kind: ``models/transformer.build`` at the
configuration's sizes, trained through ``fluid.Executor(TPUPlace())`` and
``run_steps`` on one chip, or through ``ParallelExecutor`` on the mix's mesh.
An operation is one optimizer step."""
from __future__ import annotations

import collections
import os
import re
import threading
import time

import numpy as np

from benchmark import harness, trace_reduce
from benchmark.reference import transformer_base as reference

FEED_SPAN, FETCH_SPAN = "bench.train.feed", "bench.train.fetch"
# The loss of a barely trained model sits within 0.05 of ln(V) whatever the
# network does, so the comparison has to be much finer than that to mean
# anything: 1e-4 of the loss is 0.001 nat.  The system's bf16 step and the
# float32 "highest" reference were 2e-7 to 4e-6 apart on the v5e (a mean over
# 4,096 positions averages the rounding out); a wrong mask, a dropped term or
# a block computed in a lower precision moves the part of the loss the network
# controls (0.01-0.05 nat) by a large share of itself
LOSS_RTOL = 1e-4


def validate(cell, seconds: float) -> None:
    mix, cfg = cell.mix, cell.config
    if mix.get("loop") != "steps" or mix.get("call") not in ("run_steps", "run"):
        raise harness.ConfigurationError(
            "a train mix has loop 'steps' and call 'run_steps' or 'run'")
    for k in ("batch_per_chip", "src_len", "tgt_len", "steps_per_fetch"):
        if int(mix.get(k, 0)) < 1:
            raise harness.ConfigurationError(f"train mix: {k} must be >= 1")
    if not 1 <= int(mix.get("calls_in_flight", 1)) <= 4:
        raise harness.ConfigurationError(
            "train mix: calls_in_flight must be 1 to 4")
    if int(mix["src_len"]) != int(mix["tgt_len"]):
        raise harness.ConfigurationError(
            "models/transformer.build has one max_len for source and target")
    mesh = mix.get("mesh")
    n = int(np.prod(list(mesh.values()))) if mesh else 1
    if n != cell.chips:
        raise harness.ConfigurationError(
            f"mesh {mesh} spans {n} chip(s), the cell asks for {cell.chips}")
    if (mix["call"] == "run") != bool(mesh):
        raise harness.ConfigurationError(
            "call 'run' goes with a mesh (ParallelExecutor), 'run_steps' "
            "with none (Executor)")
    if int(cfg["d_model"]) % int(cfg["n_head"]):
        raise harness.ConfigurationError("d_model must divide into heads")


def fresh_program(build_fn, seed: int):
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.program import Program, program_guard
    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = seed
    with program_guard(prog, startup), unique_name.guard():
        out = build_fn()
    return prog, startup, out


def build_programs(cfg: dict, max_len: int):
    """The training program, and the same network with dropout off and no
    optimizer over the same parameter names, for the reference comparison."""
    from paddle_tpu.models import transformer
    sizes = dict(src_vocab=int(cfg["src_vocab"]), tgt_vocab=int(cfg["tgt_vocab"]),
                 max_len=max_len, d_model=int(cfg["d_model"]),
                 n_head=int(cfg["n_head"]), d_ffn=int(cfg["d_ffn"]),
                 n_layer=int(cfg["n_layer"]), dtype=str(cfg["dtype"]),
                 attention_impl=str(cfg["attention_impl"]))
    seed = int(cfg["program_seed"])
    train = fresh_program(lambda: transformer.build(
        dropout=float(cfg["dropout"]), warmup_steps=int(cfg["warmup_steps"]),
        **sizes), seed)
    evalp = fresh_program(lambda: transformer.build(
        dropout=0.0, with_optimizer=False, **sizes), seed)
    return train, evalp


def make_feed(rng, steps: int, batch: int, length: int, vocab: int) -> dict:
    def ids():
        return rng.integers(0, vocab, size=(steps, batch, length),
                            dtype=np.int64)

    mask = np.ones((steps, batch, length), np.float32)
    return {"src_ids": ids(), "tgt_ids": ids(), "lbl_ids": ids(),
            "src_mask": mask, "tgt_mask": mask}


def reference_params(scope, program, cfg: dict) -> dict:
    """The program's parameters under the reference's names.  The feed-forward
    biases carry generated names (``fc_<n>.b_0``); in creation order they are
    fc1 then fc2 of each encoder layer, then of each decoder layer."""
    names = [v.name for v in program.global_block.vars.values()
             if v.persistable and scope.find_var(v.name) is not None]
    out = {n: scope.find_var(n) for n in names
           if not re.fullmatch(r"fc_\d+\.b_\d+", n)}
    biases = sorted((n for n in names if re.fullmatch(r"fc_\d+\.b_\d+", n)),
                    key=lambda n: int(n.split(".")[0][3:]))
    L = int(cfg["n_layer"])
    if len(biases) != 4 * L:
        raise RuntimeError(f"expected {4 * L} feed-forward biases, found "
                           f"{len(biases)}: {biases}")
    prefixes = [f"enc.{i}" for i in range(L)] + [f"dec.{i}" for i in range(L)]
    for k, prefix in enumerate(prefixes):
        out[f"{prefix}.ffn.fc1.b"] = scope.find_var(biases[2 * k])
        out[f"{prefix}.ffn.fc2.b"] = scope.find_var(biases[2 * k + 1])
    return out


def check_reference(checks, exe, scope, evalp, cfg: dict, feed: dict,
                    n_seq: int) -> None:
    """The system's forward pass (dropout off) against the plain reference's
    loss, on the same sequences and the weights the window left behind."""
    import jax
    import jax.numpy as jnp
    prog, _, (_, loss, _) = evalp
    one = {k: np.asarray(v[0][:n_seq]) for k, v in feed.items()}
    (got,) = exe.run(prog, feed=one, fetch_list=[loss], scope=scope)
    params = {k: jnp.asarray(np.asarray(v), jnp.float32)
              for k, v in reference_params(scope, prog, cfg).items()}
    want = jax.jit(lambda p, s, t, l: reference.loss(p, cfg, s, t, l))(
        params, jnp.asarray(one["src_ids"], jnp.int32),
        jnp.asarray(one["tgt_ids"], jnp.int32),
        jnp.asarray(one["lbl_ids"], jnp.int32))
    got, want = float(got), float(want)
    rel = abs(got - want) / abs(want)
    checks.add(f"forward loss within {LOSS_RTOL:g} of the plain reference's",
               np.isfinite(got) and rel <= LOSS_RTOL,
               f"system {got:.5f} reference {want:.5f} rel {rel:.2e} on "
               f"{n_seq} sequences, trained weights, dropout off")


def run(cell, args, log, t_process_start: float, devices) -> dict:
    import paddle_tpu as fluid
    from paddle_tpu.core.executor import Scope

    cfg, mix = cell.config, cell.mix
    seconds = float(args.seconds)
    K = int(mix["steps_per_fetch"])
    chips = cell.chips
    batch = int(mix["batch_per_chip"]) * chips
    T = int(mix["tgt_len"])
    tokens_per_step = batch * T
    place = fluid.TPUPlace() if devices[0].platform == "tpu" \
        else fluid.CPUPlace()
    (prog, startup, (_, loss, _)), evalp = build_programs(cfg, T)
    feed = make_feed(np.random.default_rng(int(args.seed)), K, batch, T,
                     int(cfg["tgt_vocab"]))
    scope = Scope()
    exe = fluid.Executor(place)
    exe.run(startup, scope=scope)
    if mix["call"] == "run":
        from paddle_tpu.parallel import BuildStrategy, ParallelExecutor
        pe = ParallelExecutor(
            loss_name=loss.name, main_program=prog, scope=scope,
            places=list(devices)[:chips],
            build_strategy=BuildStrategy(mesh_shape=dict(mix["mesh"])))

        def dispatch():
            return [pe.run(feed={n: v[k] for n, v in feed.items()},
                           fetch_list=[loss.name], return_numpy=False)[0]
                    for k in range(K)]
    else:
        pe = None

        def dispatch():
            return exe.run_steps(prog, feed=feed, fetch_list=[loss],
                                 scope=scope, return_numpy=False)

    tracer = trace_reduce.Tracer(os.path.join(
        cell.root, ".bench_trace", cell.name)) if args.trace else None
    spans = []
    # calls_in_flight 1: dispatch a call, wait for its losses, dispatch the
    # next, so the device waits while the host feeds.  2 or more: the next
    # call is dispatched before the losses of the one before are waited for
    # (JAX dispatches asynchronously and the weights stay on the device), so
    # the device has work queued while the host feeds, or stalls
    depth = int(mix.get("calls_in_flight", 1))
    pending = collections.deque()

    def send():
        t0 = time.perf_counter()
        out = dispatch()
        pending.append((t0, time.perf_counter(), out))

    def settle():
        t0, t1, out = pending.popleft()
        got = np.concatenate([np.asarray(o, np.float32).reshape(-1)
                              for o in out])
        spans.extend(((FEED_SPAN, t0, t1), (FETCH_SPAN, t1, time.perf_counter())))
        return got

    acct, checks = harness.Accounting(), harness.Checks()
    phases = harness.Phases(t_process_start)
    for _ in range(2):                      # compiles or loads; set-up
        send()
        settle()
    warm_mark = log.mark()
    tracing = None
    if tracer:
        tracer.start()
        tracing = threading.Thread(target=tracer.window, daemon=True, args=(
            min(seconds, float(mix.get("trace_seconds", 5.0))),))
        tracing.start()
    losses, sent, failure = [], 0, None
    n_warm_spans = len(spans)
    w0 = time.perf_counter()
    phases.mark("setup", at=w0)
    mark0 = log.mark()
    try:
        while True:
            sent += 1
            send()
            while len(pending) >= depth:
                losses.append(settle())
            if time.perf_counter() - w0 >= seconds:
                break
        while pending:                      # nothing is sent any more
            losses.append(settle())
    except Exception as e:  # every step of a call that raised failed
        failure = repr(e)
        while pending:
            try:
                losses.append(settle())
            except Exception:
                pass
    elapsed = time.perf_counter() - w0
    for _ in range(K * len(losses)):
        acct.record(True, None)
    for _ in range(K * (sent - len(losses))):
        acct.record(True, "error", failure)
    mark1 = log.mark()
    phases.mark("window")
    if tracing:
        tracing.join(timeout=300.0)
        phases.within("stop_trace", tracer.stop_s)
    peak = harness.device_facts(devices, chips)
    steps = sum(len(l) for l in losses)
    values = {"setup_s": w0 - t_process_start,
              "train_tokens_per_s": steps * tokens_per_step / elapsed}
    flat = np.concatenate(losses) if losses else np.zeros((0,))
    print(f"bench train: {steps} steps of {tokens_per_step} target positions "
          f"in {elapsed:.3f} s over {len(losses)} calls; loss first call mean "
          f"{losses[0].mean():.5f} last call mean {losses[-1].mean():.5f}; "
          f"every call's mean {[round(float(l.mean()), 5) for l in losses]}"
          if losses else "bench train: no call finished", flush=True)
    feeds = [t1 - t0 for n, t0, t1 in spans[n_warm_spans:] if n == FEED_SPAN]
    print(f"bench train host: {depth} call(s) in flight; the host spent "
          f"{sum(feeds):.3f} s of the window feeding and dispatching, "
          f"{1e3 * max(feeds, default=0.0):.1f} ms in the longest call",
          flush=True)
    checks.add("loss finite at every step",
               flat.size > 0 and bool(np.isfinite(flat).all()))
    checks.add("loss lower at the end of the window than at its start",
               len(losses) > 1 and losses[-1].mean() < losses[0].mean(),
               f"same {K} batches: first call mean "
               f"{losses[0].mean() if losses else float('nan'):.5f}, last "
               f"{losses[-1].mean() if losses else float('nan'):.5f}")
    window_compiles = harness.check_program_state(checks, mark0, mark1)
    phases.mark("report")
    check_reference(checks, exe, scope, evalp, cfg, feed,
                    int(mix["sample_sequences"]))
    phases.mark("reference_check")
    if pe is not None:
        pe.close()
    exe.close()
    summary = None
    if tracer:
        tracer.read()       # after the window: nothing is trained any more
        phases.mark("extract")
        if tracer.raw:
            tracer.add_host_spans(spans)
            summary = trace_reduce.reduce(tracer.raw, (FEED_SPAN, FETCH_SPAN))
            phases.mark("reduce")
    ctx = {"trace": summary, "memory": peak, "end_to_end": values,
           "compile": {"in_window": window_compiles,
                       "cache_hits_in_setup": warm_mark[1]},
           "config": cfg, "mix": mix, "chips": chips, "seconds": seconds}
    return {"acct": acct, "checks": checks, "values": values, "ctx": ctx,
            "device": peak, "summary": summary, "phases": phases}
