"""Driver of the ``serve`` kind: a ``decode.TransformerLM`` at the
configuration's sizes behind ``DecodeServer``/``DecodeClient`` on the native
transport, all in this one process, under the cell's traffic mix."""
from __future__ import annotations

import json
import os
import threading

import numpy as np

from benchmark import harness, loadgen, trace_reduce
from benchmark.reference import tlm as reference

MODEL = "lm"
WEIGHT_SEED = 24            # fixed: traffic, not weights, comes from --seed
SAMPLE = 8                  # requests compared with the reference
LOGIT_TOL = 0.015           # of the reference's logit scale (see check_sample)


def validate(cell, seconds: float) -> None:
    loadgen.validate_serve_mix(cell.mix, cell.config, seconds)


def make_params(cfg: dict):
    """Every weight on the device, float32, in one jitted call."""
    import jax
    import jax.numpy as jnp
    V, D, F = int(cfg["vocab"]), int(cfg["d_model"]), int(cfg["d_ffn"])
    shapes = {"emb": ((V, D), D ** -0.5), "out_proj": ((D, V), D ** -0.5)}
    for i in range(int(cfg["n_layer"])):
        for w in ("wq", "wk", "wv", "wo"):
            shapes[f"l{i}.{w}"] = ((D, D), D ** -0.5)
        shapes[f"l{i}.fc1"] = ((D, F), D ** -0.5)
        shapes[f"l{i}.fc2"] = ((F, D), F ** -0.5)

    def make(key):
        keys = jax.random.split(key, len(shapes))
        p = {n: jax.random.normal(k, shape, jnp.float32) * scale
             for k, (n, (shape, scale)) in zip(keys, sorted(shapes.items()))}
        for i in range(int(cfg["n_layer"])):
            for ln in ("ln1", "ln2"):
                p[f"l{i}.{ln}.g"] = jnp.ones((D,), jnp.float32)
                p[f"l{i}.{ln}.b"] = jnp.zeros((D,), jnp.float32)
        return p

    return jax.jit(make)(jax.random.PRNGKey(WEIGHT_SEED))


def build_server(cfg: dict, mix: dict, params):
    from paddle_tpu.data import native
    from paddle_tpu.decode import (DecodeClient, DecodeEngine, DecodeServer,
                                   LMConfig, TransformerLM)
    native.load()       # the native transport, built from source or an error
    eng = mix["engine"]
    model = TransformerLM(LMConfig(
        vocab=int(cfg["vocab"]), d_model=int(cfg["d_model"]),
        n_head=int(cfg["n_head"]), d_ffn=int(cfg["d_ffn"]),
        n_layer=int(cfg["n_layer"]), max_seq_len=int(cfg["max_seq_len"]),
        dtype=str(cfg["dtype"])))
    engine = DecodeEngine(
        model, params, name=MODEL, max_slots=int(eng["max_slots"]),
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
    for b in sorted(int(x) for x in mix["engine"]["prefill_buckets"]):
        req = loadgen.Request(-1, rng.integers(
            0, int(cfg["vocab"]), size=b).astype(np.int32), 2)
        loadgen.stream_one(client, MODEL, req)
        if req.failure or len(req.tokens) != 2:
            raise RuntimeError(f"warm-up of prefill bucket {b} failed: "
                               f"{req.failure} {req.detail}")


def check_sample(checks, cfg: dict, params, result, seed: int) -> None:
    """Prefill and decoding through the cache against the plain reference's
    full forward, on a seeded sample of the window's own requests — logits,
    not tokens.  The engine and the reference run the same float32 model
    through differently shaped matmuls, and at the TPU's default precision
    the engine's make one bf16 pass (8 bits of mantissa) through 12 layers,
    so two nearly equal logits can swap: every token the engine produced must
    be the reference's argmax to within 1.5% of the reference's logit scale.
    ``chip_smoke.py`` holds its 6-layer, 512-wide LM to 0.5% and saw 0.02%;
    this 12-layer, 768-wide one read up to 0.35% in one run's 600 sampled
    tokens (my chip run, PR 24), so 0.5% would be crossed by chance within a
    few dozen runs.  A wrong mask, position or cache block moves logits by
    tens of percent and fails this; it cannot tell bf16 weights from float32
    ones — tier-1 compares logits tightly where arithmetic is exact."""
    import jax
    import jax.numpy as jnp
    done = [r for r in result.sent if result.in_window(r) and r.tokens
            and r.failure is None]
    if not done:
        checks.add("reference comparison", False, "no finished request")
        return
    pick = np.random.default_rng(int(seed)).permutation(len(done))[:SAMPLE]
    T = int(cfg["max_seq_len"])
    toks = np.zeros((len(pick), T), np.int32)
    nxt = np.zeros((len(pick), T), np.int32)
    lens = np.zeros((len(pick),), np.int32)
    mask = np.zeros((len(pick), T), bool)
    for row, j in enumerate(pick):
        r = done[j]
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        P, n = r.prompt.size, len(r.tokens)
        toks[row, :seq.size] = seq
        lens[row] = seq.size
        nxt[row, P - 1:P - 1 + n] = r.tokens
        mask[row, P - 1:P - 1 + n] = True
    ref_cfg = {k: cfg[k] for k in ("d_model", "n_head", "n_layer")}
    gaps, scales = jax.jit(
        lambda p, t, l, n: reference.token_gaps(p, ref_cfg, t, l, n))(
        params, jnp.asarray(toks), jnp.asarray(lens), jnp.asarray(nxt))
    gaps, scales = np.asarray(gaps)[mask], np.asarray(scales)[mask]
    scale = float(scales.max())
    worst = float(gaps.max())
    exact = int((gaps == 0).sum())
    checks.add(f"engine tokens within {LOGIT_TOL:.1%} of the reference's logit "
               "scale",
               np.isfinite(gaps).all() and worst <= LOGIT_TOL * scale,
               f"{len(pick)} requests, {gaps.size} tokens, {exact} exactly the "
               f"reference argmax, worst gap {worst:.5f} of scale {scale:.3f}")


def run(cell, args, log, t_process_start: float, devices) -> dict:
    cfg, mix = cell.config, cell.mix
    seconds = float(args.seconds)
    requests = loadgen.build_requests(mix, int(cfg["vocab"]), args.seed, seconds)
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
        check_sample(checks, cfg, params, result, args.seed)
        phases.mark("reference_check")
    finally:
        server.stop()

    setup_s = result.w0 - t_process_start
    ttft, tbt = loadgen.latency_samples(result)
    values = {"setup_s": setup_s,
              "served_tokens_per_s": loadgen.served_tokens(result) / seconds,
              "tbt_p50_ms": loadgen.window_gap_p50_ms(result)}
    if ttft:
        values["ttft_p50_ms"] = harness.percentile(ttft, 0.50)
    if tbt:
        values["tbt_p95_ms"] = harness.percentile(tbt, 0.95)
    print(f"bench latency: ttft_ms p50 {harness.percentile(ttft, 0.5):.2f} "
          f"p90 {harness.percentile(ttft, 0.9):.2f} over {len(ttft)} requests; "
          f"tbt_ms p50 {harness.percentile(tbt, 0.5):.2f} "
          f"p95 {harness.percentile(tbt, 0.95):.2f} over {len(tbt)} gaps"
          if ttft and tbt else "bench latency: no sample", flush=True)
    if result.lag_ms:
        print(f"bench generator: lag_ms p50 "
              f"{harness.percentile(result.lag_ms, 0.5):.3f} p99 "
              f"{harness.percentile(result.lag_ms, 0.99):.3f} over "
              f"{len(result.lag_ms)} sends", flush=True)
    z0, z1 = state["open"]["z"], state["close"]["z"]
    dz = {k: z1[k] - z0[k] for k in ("tokens", "steps", "prefills")}
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
    window_compiles = harness.check_program_state(
        checks, state["open"]["mark"], state["close"]["mark"])
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
           "config": cfg, "mix": mix, "chips": cell.chips, "seconds": seconds}
    return {"acct": acct, "checks": checks, "values": values, "ctx": ctx,
            "device": peak, "summary": summary, "phases": phases}
