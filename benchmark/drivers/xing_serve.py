"""Driver of the ``xing_serve`` kind: a ``decode.mla.HyperMLATransformerLM``
(``model_type`` xing4_0: four residual streams a token mixed round every
sub-layer by manifold-constrained hyper-connections, a low-rank query into
latent attention over a paged latent pool, 64 sigmoid-routed experts at top-4
chosen under a selection bias beside one shared, YaRN rotary positions) at the
configuration's published widths behind ``DecodeServer``/``DecodeClient`` on
the native transport, all in this one process, under the cell's traffic mix.

The serve loop is ``drivers/serve.py``'s — same load generator, accounting,
window, drain, program-state checks and ``bench time:`` line — for another
model and another reference check.  What the accepted drivers expose is
imported (``mla_serve``'s ``warm_up`` and its ``readings`` of logits, routing
and experts, ``smallthinker_serve``'s ``draw``, ``lfm2_serve``'s
``draw_norm``); ``replay``, ``judge`` and ``run`` read their module's own
constants, model and outputs, so they are a copy of ``mla_serve``'s.  The
engine keeps the model name ``lm``, so its programs are
``jit_fn_decode_lm_step`` and ``jit_fn_decode_lm_prefill_<rung>`` and the
readers of the serve metrics find them.

``correct`` is decided after the window on what the timed path produced:
:func:`replay` sends a sample of the window's requests, teacher-forced with
the tokens the window produced, through the engine's own compiled programs
(its executable cache is hit by key, nothing compiles) and reads back every
judged position's logits, every position's expert choices and, at the judged
positions, the first expert layer's routed experts' input and output and its
feed-forward sub-layer's streams and three maps; :func:`judge` holds them
against the plain reference (``benchmark/reference/xing4.py``) GIVEN those
choices (the logits by its whole forward, the experts and the maps alone on
the program's own input rows), the choices against the reference's own
(:data:`LIMITS`), and the reference's own layers to :data:`REFERENCE_RANGES`.
``benchmark/xing_controls.py`` puts three lower-precision controls and the
planted faults through the same functions; each must come out not correct.
"""
from __future__ import annotations

import json
import os
import threading
from typing import Callable, List, NamedTuple, Optional

import numpy as np

from benchmark import harness, loadgen, trace_reduce
from benchmark.drivers import mla_serve
from benchmark.drivers.lfm2_serve import draw_norm
from benchmark.drivers.mla_serve import warm_up  # noqa: F401  the same ladder
from benchmark.drivers.smallthinker_serve import draw
from benchmark.reference import xing4 as reference

MODEL = "lm"
WEIGHT_SEED = 54            # fixed: traffic, not weights, comes from --seed
SAMPLE = 8                  # requests compared with the reference
# What the reference comparison allows, each a statistic that does not grow
# with the sample.  Each was set ONCE (PR 54), between the sound program's
# largest reading over its runs and the smallest reading of a control that
# must fail it (benchmark/xing_controls.py; PERF.md section 6 has every
# reading).
LIMITS = {
    # share of (expert layer, position) pairs where the program's four
    # experts are not the reference's own four: near-ties of sixty-four
    # sigmoid scores under bf16 activations.  Sound 0.0546-0.0611; no
    # precision below moves it (fp8 pool 0.0570-0.0633, int8 experts
    # 0.0601-0.0620); one of four choices replaced at 5% of the pairs (a
    # planted fault) 0.1024-0.1045
    "route_differs_share": 0.08,
    # ||program - reference|| / ||reference|| of one row's routed-experts
    # output at the first expert layer, the reference's experts alone on the
    # program's own input rows and choices: the larger of the medians over
    # the prefills' rows and over the steps' rows.  Sound 0.00165-0.00172;
    # int8 expert weights, a scale a channel, 0.0151-0.0156
    "expert_err_p50": 0.005,
    # ||program - reference|| / ||reference|| of one position's logits, the
    # reference given the program's expert choices: median, 90th percentile.
    # Sound 0.0171-0.0202 / 0.0200-0.0229 (seven layers of bf16 streams
    # mixed fourteen times: twice DeepSeek-V2-Lite's 0.0095); an fp8 (e4m3)
    # latent pool 0.0521-0.0561 / 0.0611-0.0674
    "logit_err_p50": 0.03,
    "logit_err_p90": 0.035,
    # a token's gap to the reference's argmax, of the reference's logit
    # scale: the 99th percentile of the tokens.  Sound 0.0006-0.0068 (an fp8
    # pool's own greedy tokens 0.013-0.026); 2% of the tokens replaced by
    # random ones (a planted fault) 0.68-0.97
    "token_gap_p99": 0.03,
    # ||program - reference|| / ||reference|| of one row's three maps (H_pre,
    # H_post, H_res: 24 numbers) at the first expert layer's feed-forward
    # sub-layer, the reference's maps alone on the program's own streams:
    # the median over the judged rows.  Sound 1.2e-7-1.3e-7 (float32 sums in
    # another order); maps from bf16 products (Phi through bfloat16)
    # 3.9e-4-4.2e-4
    "hc_map_err_p50": 1e-5,
}
# What the plain reference's own layers must read for the numbers above to
# guard anything (the configuration's ``assumed``), whatever the program
# does: [low, high] of the smallest and the largest reading over (sample,
# sub-layer).
REFERENCE_RANGES = {
    # the mean diagonal of H_res: 1/4 is a flat mixing, 1 no mixing at all
    "ref_hres_diag": (0.4, 0.9),
    # the standard deviation over a prompt's tokens of an entry of H_res,
    # meaned over the entries: the dynamic part (alpha x Phi u) shows
    "ref_hres_token_std": (0.02, 0.5),
    # a branch's output over the streams it is added to, root mean square
    # over the real positions: each mechanism is visible in the logits
    "ref_attn_rms": (0.1, 1.2),
    "ref_ffn_rms": (0.1, 1.2),
    # the streams' root mean square after the last layer over the
    # embedding's: the mixing neither blows the streams up nor lets them die
    "ref_stream_growth": (0.5, 2.0),
}
# kernels whose XLA fallback must never have been taken
FALLBACK_COUNTERS = ("moe.grouped_swiglu_fallbacks",
                     "mla.decode_attn_fallbacks",
                     "mla.prefill_attn_fallbacks",
                     "mhc.pre_fallbacks", "mhc.post_fallbacks")
# counters of decode.<model>.* whose window deltas the per-layer readers use
WINDOW_COUNTERS = mla_serve.WINDOW_COUNTERS + ("prefill_mhc_rows",
                                               "step_mhc_rows")
MODEL_KEYS = mla_serve.PUBLISHED + (
    "q_lora_rank", "scoring_func", "topk_method", "hc_mult",
    "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
    "mhc_h_res_clamp_max")
NORMS = ("attn_norm", "ffn_norm", "final_norm", "kv_norm", "q_norm")
# what a matrix is drawn at, over its fan-in^-0.5: the low-rank query's
# second matrix and the attention's last at 1.5 (visible scores then spread —
# a softmax over thousands of keys that is not flat — and the branch's share
# of its streams hardly falls with the prompt's length: 0.80 at 512 positions,
# 0.64 at 4,096), the feed-forwards' last matrices at 0.7 (fourteen branches
# are added to every stream; at 1 the streams grow 2.5 times over seven
# layers); chosen with the hyper-connections' draw below, once, on the
# reference alone
GAINS = {"wq_b": 1.5, "wo": 1.5, "w_down": 0.7, "e_down": 0.7, "s_down": 0.7}
# the selection bias: N(0, BIAS_STD^2) (PR 44's finding for sixty-four
# sigmoid scores at top-4: 0.1 turns the choice of nearly every token)
BIAS_STD = 0.008
# the hyper-connections' draw, chosen ONCE on the reference alone at a
# reduced hidden size on the CPU so that the maps are neither trivial nor
# degenerate (REFERENCE_RANGES holds them there in every run): Phi N(0, 1 /
# nD) so that m is N(0, 1) a number; alpha (pre, post, res) about ALPHA; b
# N(0, B_STD^2) about 0 for H_pre, about B_POST for H_post (2 sigmoid(-1.1) =
# 0.5 of a branch into each stream: the streams then grow 1.6-1.7 times over
# the seven layers) and about B_DIAG on H_res's diagonal (exp(2) against 1: a
# mean diagonal of 0.6-0.7 after the Sinkhorn rounds, and with ALPHA's 0.6 an
# entry moves by 0.06 from token to token).  At hidden 256, 8 heads, 7 layers
# and 512 / 1,024 / 4,096 positions the reference read: diagonal 0.60-0.71,
# token std 0.048-0.088, attention 0.44-0.80, feed-forward 0.35-0.48, growth
# 1.56-1.70 (CPU, PR 54)
ALPHA = (0.5, 0.5, 0.6)
B_STD = 0.3
B_DIAG = 2.0
B_POST = -1.1


def validate(cell, seconds: float) -> None:
    loadgen.validate_serve_mix(cell.mix, cell.config, seconds)
    try:
        from paddle_tpu.decode.mla import HyperMLATransformerLM  # noqa: F401
    except ImportError as e:
        # a checkout from before the hyper-connection LM: refuse before a
        # device is touched, so that the run ends at once
        raise harness.ConfigurationError(
            f"the program in this checkout cannot run a configuration of "
            f"kind {cell.kind!r}: {e}") from None


def model_config(cfg: dict):
    from paddle_tpu.decode.mla import HyperMLAConfig
    return HyperMLAConfig(**{k: cfg[k] for k in MODEL_KEYS},
                          max_seq_len=int(cfg["max_seq_len"]),
                          dtype=str(cfg["dtype"]))


def reference_config(cfg: dict) -> dict:
    return {k: cfg[k] for k in MODEL_KEYS}


def draw_hc(key, leaf: str, shape: tuple):
    """One of a sub-layer's three float32 tensors (see :data:`ALPHA`)."""
    import jax
    import jax.numpy as jnp
    w = jax.random.normal(key, shape, jnp.float32)
    if leaf == "hc_phi":
        return w * shape[1] ** -0.5
    if leaf == "hc_alpha":
        return jnp.asarray(ALPHA, jnp.float32) * (1.0 + 0.1 * w)
    n = int(round((shape[0] + 1) ** 0.5)) - 1
    mean = jnp.concatenate([jnp.zeros((n,), jnp.float32),
                            jnp.full((n,), B_POST, jnp.float32),
                            B_DIAG * jnp.eye(n, dtype=jnp.float32).ravel()])
    return mean + B_STD * w


def draw_rule(leaf: str, shape: tuple):
    """How :func:`make_params` makes the tensor named ``leaf``: ``norm``, or
    the standard deviations of a normal as ((columns, std), ...) over the last
    axis.  Every matrix [.., in, out] is at in^-0.5 times its gain; the
    embedding at 1."""
    if leaf in NORMS:
        return "norm"
    if leaf == "emb":
        return ((shape[-1], 1.0),)
    if leaf == "router_bias":
        return ((shape[-1], BIAS_STD),)
    return ((shape[-1], shape[-2] ** -0.5 * GAINS.get(leaf, 1.0)),)


def make_params(cfg: dict, seed: int = WEIGHT_SEED) -> dict:
    """Every weight on the device, one jitted draw a tensor; the program
    gives the names, the shapes and the dtypes."""
    import jax
    from paddle_tpu.decode.mla import param_dtype, param_shapes
    mc = model_config(cfg)
    shapes = param_shapes(mc)
    make = jax.jit(draw, static_argnums=(1, 2, 3))
    norm = jax.jit(draw_norm, static_argnums=(1, 2, 3))
    hc = jax.jit(draw_hc, static_argnums=(1, 2))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    out = {}
    for key, (name, (shape, _)) in zip(keys, shapes.items()):
        leaf = name.rpartition(".")[2]
        dtype = param_dtype(mc, name)
        if "_hc_" in leaf:
            out[name] = hc(key, leaf.partition("_")[2], tuple(shape))
            continue
        rule = draw_rule(leaf, tuple(shape))
        out[name] = norm(key, 1.0, tuple(shape), dtype) if rule == "norm" \
            else make(key, rule, tuple(shape), dtype)
    return out


def build_server(cfg: dict, mix: dict, params):
    from paddle_tpu.data import native
    from paddle_tpu.decode import DecodeClient, DecodeEngine, DecodeServer
    from paddle_tpu.decode.mla import HyperMLATransformerLM
    native.load()       # the native transport, built from source or an error
    eng = mix["engine"]
    engine = DecodeEngine(
        HyperMLATransformerLM(model_config(cfg)), params, name=MODEL,
        max_slots=int(eng["max_slots"]),
        block_tokens=int(eng["block_tokens"]), num_blocks=int(eng["num_blocks"]),
        prefill_buckets=[int(b) for b in eng["prefill_buckets"]],
        max_queue=int(eng["max_queue"]), attn_impl=str(cfg["attn_impl"]),
        cache_dtype=str(cfg["kv_dtype"]), prefix_cache=False, overcommit=False)
    server = DecodeServer("127.0.0.1:0", engines={MODEL: engine})
    server.start()
    return engine, server, DecodeClient(endpoints=[server.endpoint])


class Sample(NamedTuple):
    """``mla_serve.Sample``'s fields (what its ``readings`` reads) and, at
    the n judged positions, the first expert layer's feed-forward sub-layer's
    streams ``hc_x`` [n, 4 D] and maps ``hc_maps`` [n, 24] (H_pre, H_post,
    H_res row-major) — row 0 from the prefill program, the rest from the step
    program."""

    prompt: np.ndarray
    produced: np.ndarray
    logits: np.ndarray
    ids: np.ndarray
    expert_x: np.ndarray
    expert_y: np.ndarray
    hc_x: np.ndarray
    hc_maps: np.ndarray


def _maps_row(pre, post, res, i) -> np.ndarray:
    return np.concatenate([np.asarray(pre[i], np.float32),
                           np.asarray(post[i], np.float32),
                           np.asarray(res[i], np.float32).ravel()])


def replay(engine, asks, const=None,
           after_prefill: Optional[Callable] = None) -> List[Sample]:
    """``mla_serve.replay`` for a program that returns the mixing's probe
    too: ``asks`` (prompt, tokens the timed path produced — or an int n: let
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
    logits, ids, xs, ys, hx, hm = [], [], [], [], [], []
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
        tok, lg, _, chosen, x, y, sx, pre, post, res = dispatch(
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
        hx.append([np.asarray(sx, np.float32)[0]])
        hm.append([_maps_row(pre, post, res, 0)])
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
        nxt, lg, _, chosen, x, y, sx, pre, post, res = dispatch(
            f"decode/{engine.name}/step",
            [tokens, positions, table, zeros_u, zeros_i, zeros_f, zeros_i])
        nxt, lg = np.asarray(nxt[:k]), np.asarray(lg[:k])
        chosen = np.asarray(chosen[:, :k])
        x, y = np.asarray(x[:k], np.float32), np.asarray(y[:k])
        sx = np.asarray(sx[:k], np.float32)
        pre, post, res = (np.asarray(a[:k]) for a in (pre, post, res))
        for i in range(k):
            if want[i] > j:
                logits[i].append(lg[i])
                ids[i].append(chosen[:, i:i + 1])
                xs[i].append(x[i])
                ys[i].append(y[i])
                hx[i].append(sx[i])
                hm[i].append(_maps_row(pre, post, res, i))
                if len(toks[i]) <= j:
                    toks[i].append(int(nxt[i]))
    for blocks in held:
        cache.allocator.release(blocks)
    return [Sample(np.asarray(prompt, np.int32), np.asarray(t, np.int32),
                   np.stack(lg).astype(np.float32),
                   np.concatenate(c, axis=1).astype(np.int32),
                   np.stack(x), np.stack(y).astype(np.float32),
                   np.stack(sx), np.stack(m))
            for (prompt, _), t, lg, c, x, y, sx, m in zip(
                asks, toks, logits, ids, xs, ys, hx, hm)]


def run_reference(params, cfg: dict, samples: List[Sample]) -> list:
    """The plain reference's logits at every judged position of every sample,
    GIVEN the sample's expert choices, the reference's own choices and its own
    readings: [(logits [n, V], own ids [n_moe, L, K], {name: numbers})].  One
    shape for all (the context limit), so one compile a kind of sub-layer."""
    ref_cfg = reference_config(cfg)
    T = int(cfg["max_seq_len"])
    n_max = max(len(s.produced) for s in samples)
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
        lg, own, stats = reference.forward(params, ref_cfg, seq, L, at, forced)
        out.append((np.asarray(lg)[:n], np.asarray(own)[:, :L],
                    {k: np.asarray(v) for k, v in stats.items()}))
    return out


def reference_experts(params, cfg: dict, samples: List[Sample]) -> list:
    """The plain reference's routed experts alone, of the first expert layer,
    on every sample's ``expert_x`` rows GIVEN the program's choices there:
    [want [n, D]] a sample; all samples' rows in one call, padded to a
    multiple of ``mla_serve.EXPERT_ROWS``."""
    x = np.concatenate([s.expert_x for s in samples])
    ids = np.concatenate([
        s.ids[0, s.prompt.size - 1:s.prompt.size - 1 + len(s.produced)]
        for s in samples])
    rows = x.shape[0]
    pad = -rows % mla_serve.EXPERT_ROWS
    x = np.concatenate([x, np.zeros((pad, x.shape[1]), x.dtype)])
    ids = np.concatenate([ids, np.zeros((pad, ids.shape[1]), ids.dtype)])
    y = reference.experts_alone(params, reference_config(cfg),
                                int(cfg["first_k_dense_replace"]), x, ids)
    cuts = np.cumsum([len(s.produced) for s in samples])[:-1]
    return np.split(np.asarray(y)[:rows], cuts)


def map_errors(params, cfg: dict, samples: List[Sample]) -> np.ndarray:
    """||program - reference|| / ||reference|| of every judged row's three
    maps, the reference's alone on the program's own streams."""
    x = np.concatenate([s.hc_x for s in samples])
    got = np.concatenate([s.hc_maps for s in samples])
    rows = x.shape[0]
    pad = -rows % mla_serve.EXPERT_ROWS
    x = np.concatenate([x, np.ones((pad, x.shape[1]), x.dtype)])
    pre, post, res = reference.sublayer_maps(
        params, reference_config(cfg), int(cfg["first_k_dense_replace"]),
        "ffn", x)
    want = np.concatenate([np.asarray(pre), np.asarray(post),
                           np.asarray(res).reshape(len(x), -1)], 1)[:rows]
    d = got - want
    return np.sqrt((d * d).sum(-1) / (want * want).sum(-1))


def readings(samples: List[Sample], refs: list, experts: list,
             map_err: np.ndarray) -> dict:
    """The statistics :data:`LIMITS` and :data:`REFERENCE_RANGES` bound, and
    what they were taken over: ``mla_serve.readings``' of the logits, the
    routing and the experts, the maps' error and the reference's own."""
    got = mla_serve.readings(samples, [r[:2] for r in refs], experts)
    got["hc_map_err_p50"] = harness.percentile(map_err, 0.5)
    got["hc_map_err_max"] = float(map_err.max())
    got["finite"] = bool(got["finite"] and np.isfinite(map_err).all())
    stats = [r[2] for r in refs]
    for name in ("hres_diag", "hres_token_std", "attn_rms", "ffn_rms"):
        v = np.concatenate([st[name].ravel() for st in stats])
        got["ref_" + name] = [float(v.min()), float(v.max())]
        got["finite"] = bool(got["finite"] and np.isfinite(v).all())
    growth = [float(st["stream_rms"][1] / st["stream_rms"][0])
              for st in stats]
    got["ref_stream_growth"] = [min(growth), max(growth)]
    return got


def judge(checks, got: dict) -> None:
    """One check a limit and one a range of the reference's own; a reading
    that is not a number fails its check."""
    for name, limit in LIMITS.items():
        v = got[name]
        checks.add(f"reference comparison: {name} within {limit:g}",
                   got["finite"] and bool(v <= limit),
                   f"read {v:.6g} over {got['positions']} positions / "
                   f"{got['routings']} routings")
    for name, (low, high) in REFERENCE_RANGES.items():
        least, most = got[name]
        checks.add(f"the reference's own: {name} within [{low:g}, {high:g}]",
                   got["finite"] and bool(low <= least and most <= high),
                   f"read {least:.6g} to {most:.6g} over samples and "
                   f"sub-layers")
    print("bench reference readings:", json.dumps(got), flush=True)


def read(params, cfg: dict, samples: List[Sample], refs=None) -> dict:
    refs = refs or run_reference(params, cfg, samples)
    return readings(samples, refs, reference_experts(params, cfg, samples),
                    map_errors(params, cfg, samples))


def check_sample(checks, cfg: dict, params, engine, result, seed: int) -> None:
    done = [r for r in result.sent if result.in_window(r) and r.tokens
            and r.failure is None]
    if not done:
        checks.add("reference comparison", False, "no finished request")
        return
    pick = np.random.default_rng(int(seed)).permutation(len(done))[:SAMPLE]
    samples = replay(engine, [(done[j].prompt, list(done[j].tokens))
                              for j in pick])
    judge(checks, read(params, cfg, samples))


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
