"""Driver of the ``mla_serve`` kind: a ``decode.mla.MLATransformerLM`` (latent
attention over a paged latent pool, routed and shared experts, YaRN rotary
positions) at the configuration's published widths behind
``DecodeServer``/``DecodeClient`` on the native transport, all in this one
process, under the cell's traffic mix.

The serve loop is ``drivers/serve.py``'s — same load generator, accounting,
window, drain, program-state checks and ``bench time:`` line — for another
model and another reference check; what could be imported is, the rest is a
copy.  The engine keeps the model name ``lm``, so its programs are
``jit_fn_decode_lm_step`` and ``jit_fn_decode_lm_prefill_<rung>`` and the
readers of the serve metrics find them.

``correct`` is decided after the window on what the timed path produced:
:func:`replay` sends a sample of the window's requests, teacher-forced with
the tokens the window produced, through the engine's own compiled programs
(its executable cache is hit by key, nothing compiles) and reads back every
judged position's logits, every position's expert choices and, at the judged
positions, the first expert layer's routed experts' input and output;
:func:`judge` holds them against the plain reference
(``benchmark/reference/deepseek_v2.py``) GIVEN those choices (the logits by
its whole forward, the experts alone by its ``moe`` on the program's own
input rows), and the choices against the reference's own (:data:`LIMITS`).
``benchmark/mla_controls.py`` puts two lower-precision controls through the
same functions; both must come out not correct.
"""
from __future__ import annotations

import json
import os
import threading
from typing import Callable, List, NamedTuple, Optional

import numpy as np

from benchmark import harness, loadgen, trace_reduce
from benchmark.reference import deepseek_v2 as reference

MODEL = "lm"
WEIGHT_SEED = 30            # fixed: traffic, not weights, comes from --seed
SAMPLE = 8                  # requests compared with the reference
EXPERT_ROWS = 128           # the experts' rows are padded to a multiple
# What the reference comparison allows, each a statistic that does not grow
# with the sample.  Each was set ONCE (PR 30), between the sound program's
# largest reading over its runs and the smallest reading of a control that
# must fail it (benchmark/mla_controls.py; PERF.md section 6 has every
# reading, those of the controls that do NOT move a statistic among them).
LIMITS = {
    # share of (expert layer, position) pairs where the program's six
    # experts are not the reference's own six: near-ties of a random router
    # under bf16 activations.  Sound 0.0381-0.0411; no precision below moves
    # it (fp8 pool 0.0385-0.0403, int8 experts 0.0396-0.0419); one of six
    # choices replaced at 5% of the pairs (a planted fault) 0.085-0.087
    "route_differs_share": 0.06,
    # ||program - reference|| / ||reference|| of one row's routed-experts
    # output at the first expert layer, read from the replayed prefill and
    # step programs at the judged positions; the reference's experts alone on
    # the program's own input rows and choices.  The larger of the medians
    # over the prefills' rows and over the steps' rows.
    # Sound 0.00165-0.00169; int8 expert weights, a scale a channel,
    # 0.01462-0.01479 (0.0145 on the prefills' rows alone)
    "expert_err_p50": 0.005,
    # ||program - reference|| / ||reference|| of one position's logits, the
    # reference given the program's expert choices: median, 90th percentile.
    # Sound 0.00942-0.00968 / 0.00987-0.01024; an fp8 (e4m3) latent pool
    # 0.01262-0.01663 / 0.01505-0.01924
    "logit_err_p50": 0.011,
    "logit_err_p90": 0.0125,
    # a token's gap to the reference's argmax, of the reference's logit
    # scale: the 99th percentile of the tokens.  Sound 0-0.0035; no precision
    # below moves it (its own greedy tokens: fp8 pool 0.0029-0.0046, int8
    # experts 0.0010-0.0026); 2% of the tokens replaced by random ones (a
    # planted fault) 0.64-0.91
    "token_gap_p99": 0.02,
}
# kernels whose XLA fallback must never have been taken
FALLBACK_COUNTERS = ("moe.grouped_swiglu_fallbacks",
                     "mla.decode_attn_fallbacks",
                     "mla.prefill_attn_fallbacks")
# counters of decode.<model>.* whose window deltas the per-layer readers use
WINDOW_COUNTERS = (
    "steps", "prefills", "prefill_routed_assignments",
    "step_routed_assignments", "step_moe_dispatches", "step_experts_touched",
    "step_expert_load_max_sum", "prefill_real_tokens", "prefill_pad_tokens",
    "prefill_tokens_sq", "step_context_tokens")
PUBLISHED = (
    "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
    "intermediate_size", "moe_intermediate_size", "n_routed_experts",
    "num_experts_per_tok", "n_shared_experts", "first_k_dense_replace",
    "norm_topk_prob", "routed_scaling_factor", "rms_norm_eps", "rope_theta",
    "rope_scaling")


def validate(cell, seconds: float) -> None:
    loadgen.validate_serve_mix(cell.mix, cell.config, seconds)
    try:
        from paddle_tpu.decode import mla  # noqa: F401
    except ImportError as e:
        # a checkout from before the latent-attention LM: refuse before a
        # device is touched, so that the run ends at once
        raise harness.ConfigurationError(
            f"the program in this checkout cannot run a configuration of "
            f"kind {cell.kind!r}: {e}") from None


def model_config(cfg: dict):
    from paddle_tpu.decode.mla import MLAConfig
    return MLAConfig(**{k: cfg[k] for k in PUBLISHED},
                     max_seq_len=int(cfg["max_seq_len"]),
                     dtype=str(cfg["dtype"]))


def reference_config(cfg: dict) -> dict:
    return {k: cfg[k] for k in PUBLISHED}


def make_params(cfg: dict, seed: int = WEIGHT_SEED) -> dict:
    """Every weight on the device in the configuration's dtype, one jitted
    normal a tensor: matrices fan-in scaled, norm weights scattered about 1
    (a norm left out must show)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.decode.mla import param_shapes
    shapes = param_shapes(model_config(cfg))
    dtype = jnp.dtype(str(cfg["dtype"]))

    def draw(key, shape, std):
        w = jax.random.normal(key, shape, jnp.float32)
        return (1.0 + 0.1 * w if std is None else w * std).astype(dtype)

    make = jax.jit(draw, static_argnums=(1, 2))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return {name: make(key, tuple(shape), std)
            for key, (name, (shape, std)) in zip(keys, shapes.items())}


def build_server(cfg: dict, mix: dict, params):
    from paddle_tpu.data import native
    from paddle_tpu.decode import DecodeClient, DecodeEngine, DecodeServer
    from paddle_tpu.decode.mla import MLATransformerLM
    native.load()       # the native transport, built from source or an error
    eng = mix["engine"]
    engine = DecodeEngine(
        MLATransformerLM(model_config(cfg)), params, name=MODEL,
        max_slots=int(eng["max_slots"]),
        block_tokens=int(eng["block_tokens"]), num_blocks=int(eng["num_blocks"]),
        prefill_buckets=[int(b) for b in eng["prefill_buckets"]],
        max_queue=int(eng["max_queue"]), attn_impl=str(cfg["attn_impl"]),
        cache_dtype=str(cfg["kv_dtype"]), prefix_cache=False, overcommit=False)
    server = DecodeServer("127.0.0.1:0", engines={MODEL: engine})
    server.start()
    return engine, server, DecodeClient(endpoints=[server.endpoint])


def warm_up(client, cfg: dict, mix: dict) -> None:
    """One request per rung of the ladder: its prefill program and the decode
    step compile (or load) here, and nothing else does."""
    rng = np.random.default_rng(0)
    most = int(cfg["max_seq_len"]) - 2      # a prompt and its two tokens
    for b in sorted(int(x) for x in mix["engine"]["prefill_buckets"]):
        req = loadgen.Request(-1, rng.integers(
            0, int(cfg["vocab_size"]), size=min(b, most)).astype(np.int32), 2)
        loadgen.stream_one(client, MODEL, req)
        if req.failure or len(req.tokens) != 2:
            raise RuntimeError(f"warm-up of prefill bucket {b} failed: "
                               f"{req.failure} {req.detail}")


class Sample(NamedTuple):
    """What the engine's programs made of one request, teacher-forced with
    ``produced`` (the tokens a timed path produced for ``prompt``, or the
    programs' own greedy tokens): ``logits`` [n, V] of the positions that
    produced them; the experts chosen at every position fed, ``ids``
    [n_moe, prompt + n - 1, K]; and at those n positions the first expert
    layer's routed experts' input ``expert_x`` [n, D] and output
    ``expert_y`` [n, D] — row 0 from the prefill program, the rest from the
    step program."""

    prompt: np.ndarray
    produced: np.ndarray
    logits: np.ndarray
    ids: np.ndarray
    expert_x: np.ndarray
    expert_y: np.ndarray


def replay(engine, asks, const=None,
           after_prefill: Optional[Callable] = None) -> List[Sample]:
    """``asks``: (prompt, tokens the timed path produced — or an int n: let
    the programs produce n greedy tokens) a request, at most ``max_slots``.
    Every dispatch goes through the idle engine's executor under the engine's
    own keys and shapes, so it runs the very executables the window ran (a
    miss raises: nothing may compile here).  ``const`` replaces the weights
    and ``after_prefill(state) -> state`` rewrites the pool between the
    prefills and the steps (the controls)."""
    from paddle_tpu.decode.cache import blocks_for
    exe, cache = engine._exe, engine.cache
    const = engine._plist if const is None else const
    S, MB, bs = engine.max_slots, engine.max_blocks_per_seq, cache.block_tokens

    def missed():
        raise RuntimeError("replay missed the engine's executable cache")

    def dispatch(key, feed):
        outs, new_state = exe.run_callable(key, missed, feed,
                                           state=cache.state(), const=const)
        cache.update(new_state)
        return outs

    tables = np.zeros((S, MB), np.int32)
    held, toks, want = [], [], []
    logits, ids, xs, ys = [], [], [], []
    for i, (prompt, produced) in enumerate(asks):
        n = produced if isinstance(produced, int) else len(produced)
        P = int(prompt.size)
        blocks = cache.allocator.alloc(blocks_for(P + n, bs))
        if blocks is None:
            raise RuntimeError("replay: the idle engine's pool is short")
        held.append(blocks)
        tables[i, :len(blocks)] = blocks
        bucket = engine.prefill_ladder.snap(P)
        feed_tokens = np.zeros((1, bucket), np.int32)
        feed_tokens[0, :P] = prompt
        tok, lg, _, chosen, x, y = dispatch(
            f"decode/{engine.name}/prefill/{bucket}",
            [feed_tokens, np.int32(P), tables[i].copy(), np.uint32(0),
             np.float32(0.0), np.int32(0)])
        want.append(n)
        toks.append([int(np.asarray(tok))] if isinstance(produced, int)
                    else [int(t) for t in produced])
        logits.append([np.asarray(lg)])
        ids.append([np.asarray(chosen)[:, :P]])
        xs.append([np.asarray(x, np.float32)[0]])
        ys.append([np.asarray(y)[0]])
    if after_prefill is not None:
        cache.update(after_prefill(cache.state()))
    zeros_u, zeros_i = np.zeros((S,), np.uint32), np.zeros((S,), np.int32)
    zeros_f = np.zeros((S,), np.float32)
    k = len(asks)
    for j in range(1, max(want)):
        tokens, positions = zeros_i.copy(), zeros_i.copy()
        table = np.zeros_like(tables)
        for i, (prompt, _) in enumerate(asks):
            if want[i] > j:
                tokens[i], positions[i] = toks[i][j - 1], prompt.size + j - 1
                table[i] = tables[i]
        nxt, lg, _, chosen, x, y = dispatch(
            f"decode/{engine.name}/step",
            [tokens, positions, table, zeros_u, zeros_i, zeros_f, zeros_i])
        nxt, lg = np.asarray(nxt[:k]), np.asarray(lg[:k])
        chosen = np.asarray(chosen[:, :k])
        x, y = np.asarray(x[:k], np.float32), np.asarray(y[:k])
        for i in range(k):
            if want[i] > j:
                logits[i].append(lg[i])
                ids[i].append(chosen[:, i:i + 1])
                xs[i].append(x[i])
                ys[i].append(y[i])
                if len(toks[i]) <= j:
                    toks[i].append(int(nxt[i]))
    for blocks in held:
        cache.allocator.release(blocks)
    return [Sample(np.asarray(prompt, np.int32), np.asarray(t, np.int32),
                   np.stack(lg).astype(np.float32),
                   np.concatenate(c, axis=1).astype(np.int32),
                   np.stack(x), np.stack(y).astype(np.float32))
            for (prompt, _), t, lg, c, x, y in zip(asks, toks, logits, ids,
                                                   xs, ys)]


def run_reference(params, cfg: dict, samples: List[Sample]) -> list:
    """The plain reference's logits at every judged position of every sample,
    GIVEN the sample's expert choices, and the reference's own choices:
    [(logits [n, V], own ids [n_moe, L, K])].  One shape for all (the
    context limit), so one compile."""
    import jax
    import jax.numpy as jnp
    ref_cfg = reference_config(cfg)
    T = int(cfg["max_seq_len"])
    n_max = max(len(s.produced) for s in samples)
    fwd = jax.jit(lambda p, t, ln, out, forced: reference.forward(
        p, ref_cfg, t, ln, out, forced))
    out = []
    for s in samples:
        P, n = int(s.prompt.size), len(s.produced)
        L = P + n - 1
        seq = np.zeros((T,), np.int32)
        seq[:L] = np.concatenate([s.prompt, s.produced[:-1]])
        at = np.zeros((n_max,), np.int32)
        at[:n] = P - 1 + np.arange(n)
        forced = np.zeros((s.ids.shape[0], T, s.ids.shape[2]), np.int32)
        forced[:, :L] = s.ids
        lg, own = fwd(params, jnp.asarray(seq), jnp.int32(L), jnp.asarray(at),
                      jnp.asarray(forced))
        out.append((np.asarray(lg)[:n], np.asarray(own)[:, :L]))
    return out


def reference_experts(params, cfg: dict, samples: List[Sample]) -> list:
    """The plain reference's routed part — its ``moe`` with the shared
    experts zeroed — of the first expert layer, on every sample's
    ``expert_x`` rows, GIVEN the program's choices there, over the weights
    the SEED gives: [want [n, D]] a sample.  The rows of all samples go
    through in one call, padded to a multiple of :data:`EXPERT_ROWS`."""
    import jax
    import jax.numpy as jnp
    L = f"l{int(cfg['first_k_dense_replace'])}."
    D = int(cfg["hidden_size"])
    p = {"l." + k: params[L + k]
         for k in ("router", "e_gate", "e_up", "e_down")}
    p.update({"l.s_gate": jnp.zeros((D, 1)), "l.s_up": jnp.zeros((D, 1)),
              "l.s_down": jnp.zeros((1, D))})      # no shared expert here
    x = np.concatenate([s.expert_x for s in samples])
    ids = np.concatenate([
        s.ids[0, s.prompt.size - 1:s.prompt.size - 1 + len(s.produced)]
        for s in samples])
    rows = x.shape[0]
    pad = -rows % EXPERT_ROWS
    x = np.concatenate([x, np.zeros((pad, D), x.dtype)])
    ids = np.concatenate([ids, np.zeros((pad, ids.shape[1]), ids.dtype)])
    with jax.default_matmul_precision("highest"):
        y, _ = jax.jit(lambda p, x, ids: reference.moe(
            p, "l.", reference_config(cfg), x, ids))(
            p, jnp.asarray(x), jnp.asarray(ids))
    cuts = np.cumsum([len(s.produced) for s in samples])[:-1]
    return np.split(np.asarray(y)[:rows], cuts)


def readings(samples: List[Sample], refs: list, experts: list) -> dict:
    """The statistics :data:`LIMITS` bounds, and what they were taken over.
    ``refs``: :func:`run_reference`'s, ``experts``:
    :func:`reference_experts`'s.  The experts' error is read apart on the
    rows the prefill program computed (128-row tiles there) and on the step
    program's (16-row tiles): ``expert_err_p50`` is the larger median."""
    errs, gaps, scales, differs, e_prefill, e_step = [], [], [], [], [], []
    for s, (ref_logits, own), want in zip(samples, refs, experts):
        d = s.logits - ref_logits
        errs.append(np.sqrt((d * d).sum(-1) / (ref_logits ** 2).sum(-1)))
        chosen = np.take_along_axis(ref_logits, s.produced[:, None], 1)[:, 0]
        gaps.append(ref_logits.max(-1) - chosen)
        scales.append(np.abs(ref_logits).max())
        differs.append((np.sort(own, -1) != np.sort(s.ids, -1)).any(-1).ravel())
        d = s.expert_y - want
        e = np.sqrt((d * d).sum(-1) / (want * want).sum(-1))
        e_prefill.append(e[:1])
        e_step.append(e[1:])
    errs, gaps = np.concatenate(errs), np.concatenate(gaps)
    differs, scale = np.concatenate(differs), float(max(scales))
    e_prefill, e_step = np.concatenate(e_prefill), np.concatenate(e_step)
    medians = [harness.percentile(e, 0.5) for e in (e_prefill, e_step)
               if e.size]
    return {"route_differs_share": float(differs.mean()),
            "expert_err_p50": max(medians),
            "logit_err_p50": harness.percentile(errs, 0.5),
            "logit_err_p90": harness.percentile(errs, 0.9),
            "token_gap_p99": harness.percentile(gaps, 0.99) / scale,
            "positions": int(errs.size), "routings": int(differs.size),
            "exact_tokens": int((gaps == 0).sum()), "logit_scale": scale,
            "logit_err_max": float(errs.max()),
            "token_gap_max": float(gaps.max()) / scale,
            "expert_err_p50_prefill_rows": medians[0],
            "expert_err_p50_step_rows": medians[-1],
            "expert_rows": [int(e_prefill.size), int(e_step.size)],
            "expert_err_max": float(max(e_prefill.max(initial=0.0),
                                        e_step.max(initial=0.0))),
            "finite": bool(np.isfinite(errs).all()
                           and np.isfinite(e_prefill).all()
                           and np.isfinite(e_step).all())}


def judge(checks, got: dict) -> None:
    """One check a limit; a reading that is not a number fails its check."""
    for name, limit in LIMITS.items():
        v = got[name]
        checks.add(f"reference comparison: {name} within {limit:g}",
                   got["finite"] and bool(v <= limit),
                   f"read {v:.6g} over {got['positions']} positions / "
                   f"{got['routings']} routings")
    print("bench reference readings:", json.dumps(got), flush=True)


def check_sample(checks, cfg: dict, params, engine, result, seed: int) -> None:
    done = [r for r in result.sent if result.in_window(r) and r.tokens
            and r.failure is None]
    if not done:
        checks.add("reference comparison", False, "no finished request")
        return
    pick = np.random.default_rng(int(seed)).permutation(len(done))[:SAMPLE]
    samples = replay(engine, [(done[j].prompt, list(done[j].tokens))
                              for j in pick])
    judge(checks, readings(samples, run_reference(params, cfg, samples),
                           reference_experts(params, cfg, samples)))


def window_counters(name: str) -> dict:
    c = harness.program_counters()
    return {k: float(c.get(f"decode.{name}.{k}", 0)) for k in WINDOW_COUNTERS}


def run(cell, args, log, t_process_start: float, devices) -> dict:
    cfg, mix = cell.config, cell.mix
    seconds = float(args.seconds)
    requests = loadgen.build_requests(mix, int(cfg["vocab_size"]), args.seed,
                                      seconds)
    params = make_params(cfg)
    engine, server, client = build_server(cfg, mix, params)
    acct, checks = harness.Accounting(), harness.Checks()
    phases = harness.Phases(t_process_start)
    state = {}
    tracer = trace_reduce.Tracer(os.path.join(
        cell.root, ".bench_trace", cell.name)) if args.trace else None
    tracing = None
    try:
        warm_up(client, cfg, mix)
        warm_mark = log.mark()

        def on_window(event):
            nonlocal tracing
            st = engine.stats
            state[event] = {"mark": log.mark(), "z": engine.decodez(),
                            "counters": window_counters(MODEL),
                            "walls": (st.step_ms.sum, st.step_ms.count,
                                      st.prefill_ms.sum, st.prefill_ms.count)}
            if event == "open" and tracer:
                tracer.start()
                tracing = threading.Thread(
                    target=tracer.window, daemon=True, args=(
                        min(seconds, float(mix.get("trace_seconds", 5.0))),))
                tracing.start()

        phases.mark("setup")
        result = loadgen.run_load(client, MODEL, mix, requests, seconds,
                                  on_window=on_window)
        phases.mark("lead_in_and_window", at=result.w1)
        if tracing:
            tracing.join(timeout=300.0)
            phases.within("stop_trace", tracer.stop_s)
        peak = harness.device_facts(devices, cell.chips)
        z_end = engine.decodez()
        loadgen.account(result, acct)
        phases.mark("drain")
        check_sample(checks, cfg, params, engine, result, args.seed)
        phases.mark("reference_check")
    finally:
        server.stop()

    setup_s = result.w0 - t_process_start
    ttft, tbt = loadgen.latency_samples(result)
    values = {"setup_s": setup_s,
              "served_tokens_per_s": loadgen.served_tokens(result) / seconds,
              "tbt_p50_ms": loadgen.window_gap_p50_ms(result)}
    print(f"bench latency: ttft_ms p50 {harness.percentile(ttft, 0.5):.2f} "
          f"p90 {harness.percentile(ttft, 0.9):.2f} over {len(ttft)} requests; "
          f"tbt_ms p50 {harness.percentile(tbt, 0.5):.2f} "
          f"p95 {harness.percentile(tbt, 0.95):.2f} over {len(tbt)} gaps"
          if ttft and tbt else "bench latency: no sample", flush=True)
    z0, z1 = state["open"]["z"], state["close"]["z"]
    dz = {k: z1[k] - z0[k] for k in ("tokens", "steps", "prefills")}
    dc = {k: state["close"]["counters"][k] - state["open"]["counters"][k]
          for k in WINDOW_COUNTERS}
    dc.update(steps=float(dz["steps"]), prefills=float(dz["prefills"]))
    a, b = state["open"]["walls"], state["close"]["walls"]
    step_s, prefill_s = (b[0] - a[0]) / 1e3, (b[2] - a[2]) / 1e3
    silence, pulse = loadgen.longest_silence(result), result.pulse
    print(f"bench engine: in the window {b[1] - a[1]} steps took {step_s:.3f} s "
          f"and {b[3] - a[3]} prefills {prefill_s:.3f} s by the engine's own "
          f"clock; {seconds - step_s - prefill_s:.3f} s of the window were "
          f"neither", flush=True)
    print(f"bench stall: longest silence between token arrivals "
          f"{silence[0]:.1f} ms at +{silence[1]:.2f} s; a thread that only "
          f"sleeps overslept by at most {pulse[0]:.1f} ms at +{pulse[1]:.2f} s",
          flush=True)
    print("bench decodez: window deltas", json.dumps(dz), "end",
          json.dumps({k: z_end[k] for k in ("joins", "leaves", "shed")}),
          flush=True)
    print("bench counters: window deltas", json.dumps(dc), flush=True)
    window_compiles = harness.check_program_state(
        checks, state["open"]["mark"], state["close"]["mark"])
    c = harness.program_counters()
    bad = {n: int(c.get(n, 0)) for n in FALLBACK_COUNTERS if c.get(n, 0)}
    checks.add("no new kernel fell back to XLA", not bad, json.dumps(bad))
    checks.add("joins == leaves after the drain",
               z_end["joins"] == z_end["leaves"],
               f"{z_end['joins']} joins, {z_end['leaves']} leaves")
    checks.add("the server shed nothing", z_end["shed"] == 0,
               f"engine counter shed = {z_end['shed']}")
    checks.add("no failure outside the window", acct.failed_outside == 0,
               json.dumps(acct.outside_by_class))
    phases.mark("report")
    summary = None
    if tracer:
        tracer.read()       # after the drain: nothing is served any more
        phases.mark("extract")
        if tracer.raw:
            tracer.add_host_spans(loadgen.host_spans(result))
            summary = trace_reduce.reduce(
                tracer.raw, (loadgen.SEND_SPAN, loadgen.RECV_SPAN))
            phases.mark("reduce")
    ctx = {"trace": summary, "decodez": dz, "memory": peak,
           "lag_ms": result.lag_ms, "ttft_ms": ttft, "tbt_ms": tbt,
           "end_to_end": values,
           "compile": {"in_window": window_compiles,
                       "cache_hits_in_setup": warm_mark[1]},
           "config": cfg, "mix": mix, "chips": cell.chips, "seconds": seconds,
           "window_counters": dc, "trace_raw": tracer.raw if tracer else None,
           "xplane": tracer.xplane if tracer else None,
           "device_kind": str(devices[0].device_kind)}
    return {"acct": acct, "checks": checks, "values": values, "ctx": ctx,
            "device": peak, "summary": summary, "phases": phases}
