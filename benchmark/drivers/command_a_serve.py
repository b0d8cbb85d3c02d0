"""Driver of the ``command_a_serve`` kind: a ``decode.command_a.CommandALM``
(Command A+: ONE LayerNorm a layer and three branches summed — 128 query heads
on 8 K/V heads over a 4,096-row ring a slot in three layers of four and a
position-free paged pool in the fourth, a SHARE of 128 sigmoid-routed experts
at top-8, four shared experts averaged; a slice of the tied vocabulary) at the
configuration's published widths behind ``DecodeServer``/``DecodeClient`` on
the native transport, all in this one process, under the cell's traffic mix.

The serve loop is ``drivers/serve.py``'s — same load generator, accounting,
window, drain, program-state checks and ``bench time:`` line — for another
model and another reference check.  What the accepted drivers expose is
imported (``mla_serve.warm_up``, ``sambay_serve.trace_later``,
``smallthinker_serve``'s ``draw`` and ``_err``, ``lfm2_serve``'s
``draw_norm``); ``replay``, ``judge``, ``pick`` and ``run`` read their
module's own constants and model, so they are a copy (as
``drivers/kimi_linear_serve.py``'s are).  The engine keeps the model name
``lm``, so its programs are ``jit_fn_decode_lm_step`` and
``jit_fn_decode_lm_prefill_<rung>`` and the readers of the serve metrics find
them.

``correct`` is decided after the window on what the timed path produced:
:func:`replay` sends :data:`SAMPLE` of the window's requests — a quarter of
them past the window where the window saw as many — teacher-forced with the
tokens the window produced, through the engine's own compiled programs (its
executable cache is hit by key, nothing compiles) — the prefill at the timed
rung and :data:`REPLAY_TOKENS` - 1 decode steps of the 32-slot program
through pool and rings — and reads back the judged positions' LOGITS over
the held vocabulary rows, the experts chosen at every position fed, at the
judged rows every layer's routing weights, router input and router logits,
and after the last step the slot's rings; :func:`judge` holds them against
the plain reference's full forward (``benchmark/reference/command_a.py``,
given the program's expert choices so that a near tie turned by bf16
activations is not an error of everything downstream; its OWN choices judge
the routing) under :data:`LIMITS`.  ``route_differs_share`` and
``ring_err_max`` share nothing with the program; ``router_score_err_max``,
``route_weight_err_max`` and ``norm_unit_err_max`` take the program's own
rows and hold ONE product's, the weights' and the norm's precision and
equations, which the logits cannot see.  ``benchmark/command_a_controls.py``
puts lower-precision controls and planted mechanisms through the same
functions; every one must come out not correct.

The weights are drawn HERE (:func:`make_params`), by the rules the
configuration file's ``assumed`` states; the program gives names and shapes
only, so a fault in the program's own initialiser cannot reach both sides of
the comparison.  :data:`REFERENCE_RANGES` holds the plain reference's own
readings — each branch's share of the residual stream, the attention scores'
spread, the router's sharpness, the share of choices that are held — to what
those rules are meant to give, whatever the program does.
"""
from __future__ import annotations

import json
import os
import threading
from typing import Callable, List, NamedTuple, Optional

import numpy as np

from benchmark import harness, loadgen, trace_reduce
from benchmark.drivers.lfm2_serve import draw_norm
from benchmark.drivers.mla_serve import warm_up  # noqa: F401  the same ladder
from benchmark.drivers.sambay_serve import trace_later
from benchmark.drivers.smallthinker_serve import _err, draw
from benchmark.reference import command_a as reference

MODEL = "lm"
WEIGHT_SEED = 59            # fixed: traffic, not weights, comes from --seed
SAMPLE = 16                 # requests compared with the reference
PAST_WINDOW = 4             # of them, prompts longer than the window
REPLAY_TOKENS = 65          # the prefill's token and 64 decode steps
JUDGE_FROM, JUDGE_EVERY = 2, 2      # decode steps judged: 2, 4, ..., 64
# What the reference comparison allows, each a statistic that does not grow
# with the sample (the largest-of grow slowly: their room is wider).  Each
# stands between the sound program's largest reading over the builder's runs
# (eight of the cell, four seeds of the controls: sixteen requests each) and
# the smallest reading of a control that must fail it
# (benchmark/command_a_controls.py, four seeds).  PERF.md section 6 has every
# reading.
LIMITS = {
    # ||program - reference|| / ||reference|| of one position's logits over
    # the 32,768 held rows (bf16 through 4 layers against float32, the
    # reference given the program's expert choices): the median and the 90th
    # percentile over the judged decode positions.  Sound 0.00620-0.00642 /
    # 0.00672-0.00720 (my chip runs, PR 59); pool and rings rounded to e4m3
    # 0.0224-0.0232 / 0.0254-0.0282: the limits at the geometric middle, 1.9
    # times above the sound readings and 1.8-1.9 below the control's
    "logit_err_decode_p50": 0.012,
    "logit_err_decode_p90": 0.014,
    # the same at the prefills' last positions, the largest: what another
    # model moves whatever the state's precision.  Sound 0.0065-0.0071;
    # rotate-half on the window layers 0.437-0.482, the shared experts
    # summed 0.95-0.98, no renormalisation 1.02-1.14: 7 times above the
    # sound maximum, 8.7 below the smallest control's.  (One precision below
    # float32 in the norms' statistics reads 0.0085-0.0090 and in the
    # softmax 0.0072-0.0077 — inside what bf16 activations leave: the two
    # checks of the program's own rows below hold the first, nothing the
    # second)
    "logit_err_prefill_max": 0.05,
    # ||a slot's ring - the reference's rows [k rotated | v] of the positions
    # it must hold, row r the last position that is r mod 4,096|| / ||the
    # latter||, a window layer a stream after the last replayed step, the
    # largest: bf16 rows of bf16 activations against float32.  Sound
    # 0.0057-0.0060; rings rounded to e4m3 0.0276-0.0277; a ring that files
    # position p at row (p - 1) mod 4,096 1.404 (every row another
    # position's): the geometric middle of the first two, 2.2 times above
    # and 2.1 below
    "ring_err_max": 0.013,
    # share of (layer, real position) pairs where the program's eight experts
    # are not the reference's own eight: near ties of a random router under
    # bf16 activations, over 253-268 thousand pairs a run.  The one check of
    # the routing that shares nothing with the program.  Sound 0.0343-0.0355;
    # a planted model 0.67-0.75 (none is guarded by it)
    "route_differs_share": 0.15,
    # ||program's router logits - (the program's own u) W_r at the highest
    # precision|| / ||the latter||, the largest over the judged rows and the
    # layers: float32 accumulation of bf16 products reads 0; logits kept in
    # bf16 read 0.00206-0.00213
    "router_score_err_max": 1e-4,
    # the largest |program's routing weight - the equations' weight from the
    # program's OWN router logits and choices| over the judged rows, layers
    # and the eight: float32 both sides reads 0; the renormalisation left
    # out reads 0.84-0.85
    "route_weight_err_max": 1e-4,
    # the largest |var(u / g) - 1| over the judged rows and the layers, u the
    # program's own normed rows and g the layer's norm weight: float32
    # statistics leave the bf16 output's rounding, 2.9e-4-3.7e-4 over 2,112
    # rows; a mean and a scale rounded to bfloat16 read 5.5e-3-6.2e-3: the
    # geometric middle, 3.8 times above and 3.9 below
    "norm_unit_err_max": 1.4e-3,
    # a token's gap to the reference's argmax, of the reference's logit
    # scale: the largest over the judged tokens (528 a run: one token of
    # another stream's lies under any percentile of so many).  Sound 0 (every
    # one of 528 the reference's own argmax, six runs); ONE judged token of
    # 528 another stream's 0.92-0.95
    "token_gap_max": 0.03,
}
# What the plain reference's own layers must read for the numbers above to
# guard anything (the configuration's ``assumed``), whatever the program
# does: [low, high] of the smallest and the largest reading over (sample,
# layer).
REFERENCE_RANGES = {
    # a branch's output over the residual stream it is added to, root mean
    # square over the real positions: each mechanism is visible in the logits
    # (the routed part is one held choice of eight in the mean, and none at a
    # third of the positions)
    "ref_attn_rms": (0.1, 1.2),
    "ref_routed_rms": (0.05, 1.2),
    "ref_shared_rms": (0.1, 1.2),
    # the visible attention scores' standard deviation
    "ref_attn_logit_std": (0.5, 4.0),
    # the mean largest routing weight of eight: 0.125 is a flat router (a
    # sigmoid's largest eight of 128 lie close: renormalised, little above)
    "ref_top1_weight": (0.125, 0.3),
    # share of the router's choices that fall on the held 16 of 128: an
    # eighth under uniform ids over all layers; one layer's random router
    # favours or slights the held eighth
    "ref_held_choice_share": (0.05, 0.25),
}
# kernels whose XLA fallback must never have been taken
FALLBACK_COUNTERS = ("moe.grouped_swiglu_fallbacks",
                     "attn.gqa_window_prefill_fallbacks",
                     "attn.gqa_ring_decode_fallbacks",
                     "attn.gqa_decode_fallbacks")
# counters of decode.<model>.* whose window deltas the per-layer readers use
WINDOW_COUNTERS = (
    "steps", "prefills", "prefill_real_tokens", "prefill_pad_tokens",
    "prefill_routed_assignments", "prefill_choices", "prefill_plan_rows",
    "prefill_window_pairs", "prefill_tokens_sq", "step_routed_assignments",
    "step_choices", "step_moe_dispatches", "step_experts_touched",
    "step_expert_load_max_sum", "step_context_tokens", "step_ring_rows_live",
    "step_ring_rows_held", "step_streams_past_window", "step_streams")
MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
    "num_attention_heads", "num_key_value_heads", "head_dim", "num_experts",
    "num_experts_per_tok", "num_shared_experts", "norm_topk_prob",
    "expert_selection_fn", "shared_expert_combination_strategy",
    "layer_norm_eps", "rope_theta", "position_embedding_type", "rotary_pct",
    "layer_types", "sliding_window", "logit_scale", "tie_word_embeddings",
    "use_parallel_block", "use_qk_norm", "use_gated_activation",
    "attention_bias", "hidden_act", "first_k_dense_replace",
    "router_experts", "first_expert")


def validate(cell, seconds: float) -> None:
    loadgen.validate_serve_mix(cell.mix, cell.config, seconds)
    ids = cell.mix.get("prompt_ids", {"dist": "uniform"})
    if ids.get("dist") != "uniform":
        raise harness.ConfigurationError(
            "this driver draws prompt ids uniformly over the held vocabulary")
    if int(cell.mix["engine"]["max_slots"]) < SAMPLE:
        raise harness.ConfigurationError(
            f"the reference check replays {SAMPLE} requests at once")
    try:
        from paddle_tpu.decode import command_a  # noqa: F401
    except ImportError as e:
        # a checkout from before this model: refuse before a device is
        # touched, so that the run ends at once
        raise harness.ConfigurationError(
            f"the program in this checkout cannot run a configuration of "
            f"kind {cell.kind!r}: {e}") from None


def model_config(cfg: dict):
    from paddle_tpu.decode.command_a import CommandAConfig
    return CommandAConfig.from_dict(
        {**{k: cfg[k] for k in MODEL_KEYS},
         "max_seq_len": int(cfg["max_seq_len"]), "dtype": str(cfg["dtype"])})


def reference_config(cfg: dict) -> dict:
    return {k: cfg[k] for k in MODEL_KEYS}


NORMS = ("ln", "final_norm")                            # 1 + 0.1 N(0, 1)
# what a matrix is drawn at, over its fan-in^-0.5: queries at 2 (visible
# scores with a standard deviation near 2: a softmax over thousands of keys
# that is not flat), the attention's last matrix at 2 (it averages values),
# the held experts' last matrix at 4 (silu(g) * u has a root mean square near
# 0.6, and of eight choices near 0.13 each ONE is held in the mean), the
# shared experts' last matrix at 1.5 (four are averaged)
GAINS = {"q": 2.0, "wo": 2.0, "e_down": 4.0, "s_down": 1.5}


def draw_rule(cfg: dict, leaf: str, shape: tuple):
    """How :func:`make_params` makes the tensor named ``leaf``: ``norm`` or
    the standard deviations of a normal as ((columns, std), ...) over the last
    axis.  Every matrix [.., in, out] is at in^-0.5 times its gain — the
    shared experts' last matrix at ONE expert's fan-in, ``intermediate_size``;
    the tied embedding at 1."""
    if leaf in NORMS:
        return "norm"
    if leaf == "emb":
        return ((shape[-1], 1.0),)
    fan = shape[-2] ** -0.5
    if leaf == "s_down":
        fan = int(cfg["intermediate_size"]) ** -0.5
    if leaf == "wqkv":
        dh = int(cfg["head_dim"])
        q, kv = (int(cfg["num_attention_heads"]) * dh,
                 int(cfg["num_key_value_heads"]) * dh)
        return ((q, fan * GAINS["q"]), (2 * kv, fan))
    return ((shape[-1], fan * GAINS.get(leaf, 1.0)),)


def make_params(cfg: dict, seed: int = WEIGHT_SEED) -> dict:
    """Every weight on the device in the configuration's dtype, one jitted
    draw a tensor; the program gives the names and the shapes."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.decode.command_a import param_shapes
    shapes = param_shapes(model_config(cfg))
    make = jax.jit(draw, static_argnums=(1, 2, 3))
    norm = jax.jit(draw_norm, static_argnums=(1, 2, 3))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    dtype = jnp.dtype(str(cfg["dtype"]))
    out = {}
    for key, (name, (shape, _)) in zip(keys, shapes.items()):
        rule = draw_rule(cfg, name.rsplit(".", 1)[-1], tuple(shape))
        out[name] = norm(key, 1.0, tuple(shape), dtype) if rule == "norm" \
            else make(key, rule, tuple(shape), dtype)
    return out


def build_server(cfg: dict, mix: dict, params):
    from paddle_tpu.data import native
    from paddle_tpu.decode import DecodeClient, DecodeEngine, DecodeServer
    from paddle_tpu.decode.command_a import CommandALM
    native.load()       # the native transport, built from source or an error
    eng = mix["engine"]
    engine = DecodeEngine(
        CommandALM(model_config(cfg)), params, name=MODEL,
        max_slots=int(eng["max_slots"]),
        block_tokens=int(eng["block_tokens"]),
        num_blocks=int(eng["num_blocks"]),
        prefill_buckets=[int(b) for b in eng["prefill_buckets"]],
        max_queue=int(eng["max_queue"]), cache_dtype=str(cfg["kv_dtype"]),
        prefix_cache=False, overcommit=False)
    server = DecodeServer("127.0.0.1:0", engines={MODEL: engine})
    server.start()
    return engine, server, DecodeClient(endpoints=[server.endpoint])


class Sample(NamedTuple):
    """What the engine's programs made of one request, teacher-forced with
    ``produced``: ``logits`` [len(at), V] of the judged tokens ``at`` (0 is
    the prefill's, j the j-th decode step's); the experts chosen at every
    position fed, ``ids`` [L, prompt + n - 1, K]; at the judged rows every
    layer's routing weights ``weights`` [len(at), L, K], router input
    ``router_u`` [len(at), L, D] and router logits ``router_r`` [len(at), L,
    Er]; and the slot's rings after the last step, ``rings`` [window layers,
    window, 2 kw] in the cache's dtype."""

    prompt: np.ndarray
    produced: np.ndarray
    at: np.ndarray
    logits: np.ndarray
    ids: np.ndarray
    weights: np.ndarray
    router_u: np.ndarray
    router_r: np.ndarray
    rings: np.ndarray


def judged_steps(n: int) -> List[int]:
    """Of ``n`` teacher-forced tokens: the prefill's (0) and the decode steps
    from :data:`JUDGE_FROM` on, every :data:`JUDGE_EVERY`-th — or, of a
    shorter replay, its last steps at that spacing."""
    first = JUDGE_FROM if n > JUDGE_FROM else max(1, (n - 1) % JUDGE_EVERY)
    return [0] + list(range(first, n, JUDGE_EVERY))


def replay(engine, asks, after_dispatch: Optional[Callable] = None,
           const=None) -> List[Sample]:
    """``asks``: (prompt, tokens the timed path produced) a request, at most
    ``max_slots``; every request is replayed for as many tokens as the
    shortest has (a slot whose stream has ended would go on scribbling on
    its rings).  Every dispatch goes through the idle engine's executor under
    the engine's own keys and shapes, so it runs the very executables the
    window ran (a miss raises: nothing may compile here).
    ``after_dispatch(state) -> state`` rewrites the state after every
    dispatch and ``const`` replaces the weights (the controls)."""
    from paddle_tpu.decode.cache import blocks_for
    exe, cache = engine._exe, engine.cache
    const = engine._plist if const is None else const
    S, MB, bs = engine.max_slots, engine.max_blocks_per_seq, cache.block_tokens
    n = min(len(produced) for _, produced in asks)
    at = judged_steps(n)
    k = len(asks)

    def missed():
        raise RuntimeError("replay missed the engine's executable cache")

    def dispatch(key, feed):
        outs, new_state = exe.run_callable(key, missed, feed,
                                           state=cache.state(), const=const)
        if after_dispatch is not None:
            new_state = after_dispatch(new_state)
        cache.update(new_state)
        return outs

    tables = np.zeros((S, MB), np.int32)
    held = []
    logits, ids, ws, us, rs = ([[] for _ in asks] for _ in range(5))
    for i, (prompt, _) in enumerate(asks):
        P = int(prompt.size)
        blocks = cache.allocator.alloc(blocks_for(P + n, bs))
        if blocks is None:
            raise RuntimeError("replay: the idle engine's pool is short")
        held.append(blocks)
        tables[i, :len(blocks)] = blocks
        bucket = engine.prefill_ladder.snap(P)
        feed_tokens = np.zeros((1, bucket), np.int32)
        feed_tokens[0, :P] = prompt
        _, lg, _, chosen, w, u, r = dispatch(
            f"decode/{engine.name}/prefill/{bucket}",
            [feed_tokens, np.int32(P), np.int32(i), tables[i].copy(),
             np.uint32(0), np.float32(0.0), np.int32(0)])
        logits[i].append(np.asarray(lg))
        ids[i].append(np.asarray(chosen)[:, :P])
        ws[i].append(np.asarray(w)[:, 0])
        us[i].append(np.asarray(u)[:, 0])
        rs[i].append(np.asarray(r)[:, 0])
    zeros_u, zeros_i = np.zeros((S,), np.uint32), np.zeros((S,), np.int32)
    zeros_f = np.zeros((S,), np.float32)
    tokens, positions = zeros_i.copy(), zeros_i.copy()
    for j in range(1, n):
        for i, (prompt, produced) in enumerate(asks):
            tokens[i], positions[i] = produced[j - 1], prompt.size + j - 1
        _, lg, _, chosen, w, u, r = dispatch(
            f"decode/{engine.name}/step",
            [tokens.copy(), positions.copy(), tables.copy(), zeros_u, zeros_i,
             zeros_f, zeros_i])
        chosen = np.asarray(chosen[:, :k])
        for i in range(k):
            ids[i].append(chosen[:, i:i + 1])
        if j in at:
            lg, w, u, r = (np.asarray(a) for a in
                           (lg[:k], w[:, :k], u[:, :k], r[:, :k]))
            for i in range(k):
                logits[i].append(lg[i])
                ws[i].append(w[:, i])
                us[i].append(u[:, i])
                rs[i].append(r[:, i])
    # a slot's ring blocks are its own, one after another: a slot at a time
    rings = cache.state()[1]
    nrb = cache.ring_blocks
    held_rings = [np.asarray(rings[:, i * nrb:(i + 1) * nrb]).reshape(
        rings.shape[0], cache.window, rings.shape[-1]) for i in range(k)]
    for blocks in held:
        cache.allocator.release(blocks)
    return [Sample(np.asarray(prompt, np.int32),
                   np.asarray(produced[:n], np.int32), np.asarray(at),
                   np.stack(logits[i]).astype(np.float32),
                   np.concatenate(ids[i], axis=1).astype(np.int32),
                   np.stack(ws[i]).astype(np.float32), np.stack(us[i]),
                   np.stack(rs[i]).astype(np.float32), held_rings[i])
            for i, (prompt, produced) in enumerate(asks)]


def reference_lengths(mix: dict, cfg: dict) -> List[int]:
    """The padded lengths of a cell's reference runs, shortest first: a
    window and the replayed tokens (half of the prompts), and the mix's
    longest prompt and the replayed tokens; a sample takes the first that
    holds it, so a reference compiles twice."""
    most = int(mix["prompt_tokens"]["max"]) + REPLAY_TOKENS - 1
    short = int(cfg["sliding_window"]) + REPLAY_TOKENS - 1
    return sorted({min(short, most), most})


def run_reference(params, cfg: dict, samples: List[Sample],
                  lengths: Optional[List[int]] = None, faults=()) -> list:
    """The plain reference's logits at every judged position of every sample,
    its own chosen experts at every position fed and its own readings: [(logits
    [len(at), V], own ids [L, prompt + n - 1, K], {``stats`` [L, len(STATS)],
    ``u`` [len(at), L, D] its own normed rows at the judged positions,
    ``ring_err`` [window layers]: ||the sample's ring - the rows the
    reference says it must hold|| / ||the latter||})].  The reference is
    given the program's choices.  ``faults`` make it another model: the
    controls."""
    import jax.numpy as jnp
    ref_cfg = reference_config(cfg)
    out = []
    for s in samples:
        P, n = int(s.prompt.size), len(s.produced)
        L = P + n - 1
        T = next((t for t in sorted(lengths or [L]) if t >= L), L)
        seq = np.zeros((T,), np.int32)
        seq[:L] = np.concatenate([s.prompt, s.produced[:-1]])
        forced = np.zeros(s.ids.shape[:1] + (T,) + s.ids.shape[2:], np.int32)
        forced[:, :L] = s.ids
        lg, own, got = reference.forward(
            params, ref_cfg, seq, L, P - 1 + s.at, forced=forced,
            faults=faults, rings=True)
        held = got["ring_rows"][None, :, None]
        want = jnp.where(held, got["rings"], 0.0)
        d = jnp.where(held, jnp.asarray(s.rings).astype(jnp.float32), 0.0) \
            - want
        ring_err = jnp.sqrt(jnp.sum(d * d, (1, 2)) / jnp.sum(want * want,
                                                              (1, 2)))
        out.append((np.asarray(lg), np.asarray(own)[:, :L],
                    {"stats": np.asarray(got["stats"]),
                     "u": np.asarray(got["u"]),
                     "ring_err": np.asarray(ring_err)}))
    return out


def own_row_errors(params, cfg: dict, samples: List[Sample], faults=()
                   ) -> tuple:
    """What the program's OWN rows at the judged positions say of three
    precisions: (||program's router logits - u W_r|| / ||u W_r|| a (layer,
    sample x judged row), the product of the program's own ``u`` at the
    highest precision; |program's routing weights - the equations' from the
    program's own router logits and choices| the same rows x K; |var(u / g) -
    1| the same rows, g the layer's norm weight)."""
    ref_cfg = reference_config(cfg)
    sz = reference.sizes(ref_cfg)
    errs, werrs, nerrs = [], [], []
    for l in range(sz["L"]):
        w, _, _ = reference.layer_weights(params, sz, l)
        u = np.concatenate([s.router_u[:, l] for s in samples])
        got = np.concatenate([s.router_r[:, l] for s in samples])
        errs.append(_err(got, np.asarray(reference.router_scores(
            w["router"], u))))
        used = np.concatenate([
            s.ids[l][s.prompt.size - 1 + s.at] for s in samples])
        weights = np.concatenate([s.weights[:, l] for s in samples])
        werrs.append(np.abs(weights - np.asarray(reference.route_weights(
            ref_cfg, got, used, faults))))
        nerrs.append(np.asarray(reference.norm_unit_error(u, w["ln"])))
    return np.stack(errs), np.stack(werrs), np.stack(nerrs)


def readings(samples: List[Sample], refs: list, row_err) -> dict:
    """The statistics :data:`LIMITS` and :data:`REFERENCE_RANGES` bound, and
    what they were taken over."""
    prefill, decode, gaps, scales, differs, rings = ([] for _ in range(6))
    for s, (ref_logits, own, got) in zip(samples, refs):
        err = _err(s.logits, ref_logits)
        prefill.append(err[:1])
        decode.append(err[1:])
        chosen = np.take_along_axis(ref_logits, s.produced[s.at][:, None],
                                    1)[:, 0]
        gaps.append(ref_logits.max(-1) - chosen)
        scales.append(np.abs(ref_logits).max())
        differs.append((np.sort(own, -1) != np.sort(s.ids, -1)
                        ).any(-1).ravel())
        rings.append(got["ring_err"])
    prefill, decode = np.concatenate(prefill), np.concatenate(decode)
    gaps, differs = np.concatenate(gaps), np.concatenate(differs)
    rings = np.concatenate(rings).astype(np.float64)
    scale = float(max(scales))
    stats = np.stack([r[2]["stats"] for r in refs]).astype(np.float64)
    if not decode.size:         # a replay of one token: nothing was decoded
        decode = prefill
    own = {"ref_" + name: [float(stats[..., i].min()),
                           float(stats[..., i].max())]
           for i, name in enumerate(reference.STATS)}
    score_err, weight_err, norm_err = (np.asarray(a, np.float64)
                                       for a in row_err)
    every = [prefill, decode, rings, score_err, weight_err, norm_err, stats]
    return {"logit_err_prefill_max": float(prefill.max()),
            "logit_err_decode_p50": harness.percentile(decode, 0.5),
            "logit_err_decode_p90": harness.percentile(decode, 0.9),
            "ring_err_max": float(rings.max()),
            "route_differs_share": float(differs.mean()),
            "router_score_err_max": float(score_err.max()),
            "route_weight_err_max": float(weight_err.max()),
            "norm_unit_err_max": float(norm_err.max()),
            "token_gap_max": float(gaps.max()) / scale,
            **own,
            "positions": int(prefill.size + decode.size),
            "routed_pairs": int(differs.size),
            "prompts": [int(s.prompt.size) for s in samples],
            "steps_replayed": int(len(samples[0].produced) - 1),
            "judged_steps": [int(a) for a in samples[0].at],
            "exact_tokens": int((gaps == 0).sum()), "logit_scale": scale,
            "logit_err_decode_max": float(decode.max()),
            "logit_err_prefill_p50": harness.percentile(prefill, 0.5),
            "ring_err_p50": harness.percentile(rings, 0.5),
            "router_score_err_p50": harness.percentile(score_err.ravel(),
                                                       0.5),
            "norm_unit_err_p50": harness.percentile(norm_err.ravel(), 0.5),
            "token_gap_p99": harness.percentile(gaps, 0.99) / scale,
            "finite": all(bool(np.isfinite(a).all()) for a in every)}


def judge(checks, got: dict) -> None:
    """One check a limit and one a range of the reference's own; a reading
    that is not a number fails its check."""
    for name, limit in LIMITS.items():
        v = got[name]
        checks.add(f"reference comparison: {name} within {limit:g}",
                   got["finite"] and bool(v <= limit),
                   f"read {v:.6g} over {got['positions']} positions and "
                   f"{got['routed_pairs']} routed (layer, position) pairs, "
                   f"{got['steps_replayed']} steps replayed, prompts "
                   f"{got['prompts']}")
    for name, (low, high) in REFERENCE_RANGES.items():
        least, most = got[name]
        checks.add(f"the reference's own: {name} within [{low:g}, {high:g}]",
                   got["finite"] and bool(low <= least and most <= high),
                   f"read {least:.6g} to {most:.6g} over samples and layers")
    print("bench reference readings:", json.dumps(got), flush=True)


def pick(done: list, seed: int, window: int) -> list:
    """A seeded sample of :data:`SAMPLE` finished requests that produced at
    least :data:`REPLAY_TOKENS` tokens, :data:`PAST_WINDOW` of them with a
    prompt longer than the window (their rings have wrapped, and go on
    wrapping through the replay) where the window finished as many; where
    fewer produced that many tokens, the longest outputs."""
    order = np.random.default_rng(int(seed)).permutation(len(done))
    long = [done[j] for j in order if len(done[j].tokens) >= REPLAY_TOKENS]
    if len(long) < SAMPLE:
        long = sorted((done[j] for j in order),
                      key=lambda r: -len(r.tokens))[:max(SAMPLE, len(long))]
    # a run sends its cycle of requests more than once: a prompt once
    seen, once, again = set(), [], []
    for r in long:
        key = r.prompt.tobytes()
        (again if key in seen else once).append(r)
        seen.add(key)
    past = [r for r in once if r.prompt.size > window][:PAST_WINDOW]
    rest = [r for r in once if not any(r is o for o in past)]
    return (past + rest + again)[:SAMPLE]


def check_sample(checks, cfg: dict, params, engine, result, seed: int,
                 mix: dict) -> None:
    done = [r for r in result.sent if result.in_window(r) and r.tokens
            and r.failure is None]
    if not done:
        checks.add("reference comparison", False, "no finished request")
        return
    asks = [(r.prompt, list(r.tokens)[:REPLAY_TOKENS])
            for r in pick(done, seed, int(cfg["sliding_window"]))]
    samples = replay(engine, asks)
    judge(checks, readings(
        samples, run_reference(params, cfg, samples,
                               reference_lengths(mix, cfg)),
        own_row_errors(params, cfg, samples)))


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
                tracing = threading.Thread(
                    target=trace_later, daemon=True, args=(tracer, mix,
                                                           seconds))
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
        check_sample(checks, cfg, params, engine, result, args.seed, mix)
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
          "cache", json.dumps(z_end["cache"]), flush=True)
    print("bench counters: window deltas", json.dumps(dc), flush=True)
    window_compiles = harness.check_program_state(
        checks, state["open"]["mark"], state["close"]["mark"])
    c = harness.program_counters()
    bad = {n: int(c.get(n, 0)) for n in FALLBACK_COUNTERS if c.get(n, 0)}
    checks.add("no new kernel fell back to XLA", not bad, json.dumps(bad))
    k, layers = int(cfg["num_experts_per_tok"]), int(cfg["num_hidden_layers"])
    checks.add("every token routed over all the router's experts: choices == "
               "tokens x top-k x layers, and the held experts' rows are "
               "their assignments padded to whole tiles",
               dc["prefill_choices"] == dc["prefill_real_tokens"] * k * layers
               and dc["step_choices"] == dc["step_streams"] * k * layers
               and 0 < dc["prefill_routed_assignments"]
               <= dc["prefill_plan_rows"]
               and 0 < dc["step_routed_assignments"] <= dc["step_choices"],
               json.dumps(dc))
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
