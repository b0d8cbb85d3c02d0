"""Driver of the ``kimi_linear_serve`` kind: a ``decode.kimi_linear.
KimiLinearLM`` (three KDA layers — a gated delta rule with a decay a channel,
a float32 recurrent row and a convolution tail a slot — to one position-free
latent-attention layer with a latent row a token in a paged pool, all under
one cache; a dense first layer and then a SHARE of 256 sigmoid-routed experts
at top-8 beside a shared one; a slice of the vocabulary) at the
configuration's published widths behind ``DecodeServer``/``DecodeClient`` on
the native transport, all in this one process, under the cell's traffic mix.

The serve loop is ``drivers/serve.py``'s — same load generator, accounting,
window, drain, program-state checks and ``bench time:`` line — for another
model and another reference check.  What the accepted drivers expose is
imported (``mla_serve.warm_up``, ``sambay_serve.trace_later``,
``smallthinker_serve``'s ``draw`` and ``_err``, ``lfm2_serve``'s
``draw_norm``); ``replay``, ``judge``, ``pick`` and ``run`` read their
module's own constants and model, so they are a copy (as
``drivers/lfm2_serve.py``'s are).  The engine keeps the model name ``lm``, so
its programs are ``jit_fn_decode_lm_step`` and
``jit_fn_decode_lm_prefill_<rung>`` and the readers of the serve metrics find
them.

``correct`` is decided after the window on what the timed path produced:
:func:`replay` sends a sample of the window's requests teacher-forced with the
tokens the window produced, through the engine's own compiled programs (its
executable cache is hit by key, nothing compiles) — the prefill at the timed
rung and :data:`REPLAY_TOKENS` - 1 decode steps through pool, recurrent rows
and tails — and reads back the judged positions' logits, the experts chosen
at every position fed, at the judged rows every expert layer's routing
weights, router input and router logits, and after the last step the slot's
recurrent rows; :func:`judge` holds them against the plain reference's full
forward (``benchmark/reference/kimi_linear.py``: the recurrence one position
at a time; given the program's expert choices so that a near tie turned by
bf16 activations is not an error of everything downstream) under
:data:`LIMITS`.  A verdict covers :data:`SAMPLE` requests of the 56-69 a
window finishes: their whole prompts (routing at every position, the state
they leave), 1,040 served tokens teacher-forced through 64 steps of the
64-slot program, and 560 of those tokens' logits.  What holds the routing
INDEPENDENTLY of the program is ``route_differs_share``: the reference's own
choices from its own float32 activations against the program's, at every
position fed.  ``router_score_err_max`` and ``route_weight_err_max`` are not
independent and are not meant to be: they take the program's own router
input and logits and hold ONE product's and the weights' precision and
equations, which the logits cannot see.
``benchmark/kimi_linear_controls.py`` puts lower-precision controls and
planted mechanisms through the same functions; every one must come out not
correct.

The weights are drawn HERE (:func:`make_params`), by the rules the
configuration file's ``assumed`` states; the program gives names and shapes
only, so a fault in the program's own initialiser cannot reach both sides of
the comparison.  :data:`REFERENCE_RANGES` holds the plain reference's own
readings — each branch's share of the residual stream, the attention scores'
spread, the router's sharpness, the share of choices that are held, the
decays' range and what the delta correction takes off — to what those rules
are meant to give, whatever the program does.
"""
from __future__ import annotations

import json
import math
import os
import threading
from typing import Callable, List, NamedTuple, Optional

import numpy as np

from benchmark import harness, loadgen, trace_reduce
from benchmark.drivers.lfm2_serve import draw_norm
from benchmark.drivers.mla_serve import warm_up  # noqa: F401  the same ladder
from benchmark.drivers.sambay_serve import trace_later
from benchmark.drivers.smallthinker_serve import _err, draw
from benchmark.reference import kimi_linear as reference

MODEL = "lm"
WEIGHT_SEED = 50            # fixed: traffic, not weights, comes from --seed
SAMPLE = 16                 # requests compared with the reference
REPLAY_TOKENS = 65          # the prefill's token and 64 decode steps
JOIN_STEPS = (1, 2, 3)      # the decode steps that read a prefill's tail
JUDGE_FROM, JUDGE_EVERY = 4, 2      # then judged: 4, 6, ..., 64
# What the reference comparison allows, each a statistic that does not grow
# with the sample (the three largest-of grow slowly: their room is wider).  Each
# stands between the sound program's largest reading over the builder's runs
# (fourteen of 4 requests, thirteen of 16) and the smallest reading of a
# control that must fail it (benchmark/kimi_linear_controls.py, six seeds of
# 16).
# PERF.md section 6 has every reading.
LIMITS = {
    # ||program - reference|| / ||reference|| of one position's logits (bf16
    # through 9 layers against float32, the reference given the program's
    # expert choices): the median and the 90th percentile over the judged
    # decode positions from step 4 on.  Sound 0.0132-0.0142 / 0.0141-0.0153;
    # the latent pool rounded to e4m3 (two layers of nine read it: the
    # nearest control) 0.0711-0.137 / 0.325-0.359, the tails to e4m3
    # 0.337-0.385 / 0.620-0.653, recurrent rows to bf16 0.202-0.233 /
    # 0.433-0.522: the limits 1.8 and 2 times above the sound readings, 2.8
    # and 10 below the nearest control's
    "logit_err_decode_p50": 0.025,
    "logit_err_decode_p90": 0.03,
    # the same at the prefills' last positions, the largest: what another
    # model moves whatever the state's precision.  Sound 0.0137-0.0163; keys
    # rotated in the latent layers 0.305-0.436, the delta correction dropped
    # 0.542-0.620, a scalar decay a head 0.674-0.758, q and k left
    # unnormalised not a number (the delta rule diverges): 2.1 times above
    # the sound maximum (of sixteen prompts a run; fresh seeds read higher),
    # 8.7 below the smallest control's
    "logit_err_prefill_max": 0.035,
    # the same at the first three decode steps, the largest: what a wrong
    # row or tail at the prefill -> decode join moves first.  Sound
    # 0.0142-0.0163; a tail of zeros 1.10-1.14, a tail from the padded
    # rung's end 1.07-1.10 (the delta rule keeps what a wrong key wrote), the
    # pool at e4m3 0.504-0.571
    "logit_err_join_max": 0.035,
    # ||program - reference|| / ||reference|| of one KDA layer's recurrent
    # rows of one stream after the last replayed step: the median over
    # (stream, layer).  Sound 0.0122-0.0129 (the scan's INPUTS come through
    # bf16 activations; the largest 0.0192); rows rounded to bf16 after
    # every dispatch 0.102-0.168 (ten seeds), the pool at e4m3 0.040-0.059:
    # 2.3 times above the sound readings, 3.4 below the control's
    "state_err_p50": 0.03,
    # share of (expert layer, real position) pairs where the program's eight
    # experts are not the reference's own eight: near ties of a random
    # router under bf16 activations, over 0.13-1.0 million pairs a run.  The
    # one check of the routing that shares nothing with the program.  Sound
    # 0.0909-0.0977; a planted model 0.71-1.0 (none is guarded by it)
    "route_differs_share": 0.3,
    # ||program's router logits - (the program's own u) W_r at the highest
    # precision|| / ||the latter||, the largest over the judged rows and the
    # layers: float32 accumulation of bf16 products reads 0; logits kept in
    # bf16 read 0.00192-0.00203
    "router_score_err_max": 1e-4,
    # the largest |program's routing weight - the equations' weight from the
    # program's OWN router logits and choices| over the judged rows, layers
    # and the eight: float32 both sides reads 0; a renormalisation over the
    # held choices only 2.40-2.52 (bf16 scores 0.00030-0.00036)
    "route_weight_err_max": 1e-4,
    # a token's gap to the reference's argmax, of the reference's logit
    # scale: the largest over the judged tokens (560 a run: one token of
    # another stream's lies under any percentile of so many).  Sound
    # 0.0039-0.0093 (thirteen runs of 16); ONE judged token of 560 another
    # stream's 0.318-0.733
    "token_gap_max": 0.03,
}
# What the plain reference's own layers must read for the numbers above to
# guard anything (the configuration's ``assumed``), whatever the program
# does: [low, high] of the smallest and the largest reading over (sample,
# layer).
REFERENCE_RANGES = {
    # a branch's output over the residual stream it is added to, root mean
    # square over the real positions: each mechanism is visible in the logits
    "ref_kda_rms": (0.1, 1.2),
    "ref_mla_rms": (0.1, 1.2),
    "ref_ffn_rms": (0.1, 1.2),
    # the visible attention scores' standard deviation
    "ref_attn_logit_std": (0.5, 4.0),
    # the mean largest routing weight of eight: 2.446 / 8 is a flat router
    "ref_top1_weight": (0.3, 0.8),
    # share of real positions whose chosen eight the selection bias turns
    "ref_bias_turns_share": (0.05, 0.6),
    # share of the router's choices that fall on the held 64 of 256: a
    # quarter under uniform ids over all layers; one layer's random router
    # favours or slights the held quarter (0.24-0.32 a layer, first run)
    "ref_held_choice_share": (0.15, 0.4),
    # the strongest and the weakest log-decay a position a channel (nats):
    # the draw reaches past what one chunk of a naive form survives
    # (exp(64 x 1.4) overflows float32) and down to a memory of thousands of
    # positions
    "ref_decay_strongest": (-200.0, -1.5),
    "ref_decay_weakest": (-1e-3, -1e-7),
    # ||S'^T k|| / ||v||, the mean over positions: what the delta correction
    # takes off a value — neither nothing nor everything
    "ref_delta_share": (0.02, 1.5),
}
# kernels whose XLA fallback must never have been taken
FALLBACK_COUNTERS = ("moe.grouped_swiglu_fallbacks", "kda.chunk_fallbacks",
                     "kda.step_fallbacks", "mla.decode_attn_fallbacks",
                     "mla.prefill_attn_fallbacks")
# counters of decode.<model>.* whose window deltas the per-layer readers use
WINDOW_COUNTERS = (
    "steps", "prefills", "prefill_real_tokens", "prefill_pad_tokens",
    "prefill_routed_assignments", "prefill_choices", "prefill_moe_dispatches",
    "prefill_experts_touched", "prefill_expert_load_max_sum",
    "prefill_plan_rows", "prefill_plan_pad_rows", "prefill_tokens_sq",
    "step_routed_assignments", "step_choices", "step_moe_dispatches",
    "step_experts_touched", "step_expert_load_max_sum", "step_context_tokens",
    "step_streams", "step_state_bytes")
MODEL_KEYS = (
    "vocab_size", "hidden_size", "intermediate_size", "moe_intermediate_size",
    "num_hidden_layers", "num_attention_heads", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "kv_lora_rank", "q_lora_rank",
    "mla_use_nope", "first_k_dense_replace", "num_experts",
    "num_experts_per_token", "num_shared_experts", "moe_renormalize",
    "moe_router_activation_func", "routed_scaling_factor", "num_expert_group",
    "topk_group", "rms_norm_eps", "tie_word_embeddings", "linear_attn_config",
    "router_experts", "first_expert")


def validate(cell, seconds: float) -> None:
    loadgen.validate_serve_mix(cell.mix, cell.config, seconds)
    ids = cell.mix.get("prompt_ids", {"dist": "uniform"})
    if ids.get("dist") != "uniform":
        raise harness.ConfigurationError(
            "this driver draws prompt ids uniformly over the held vocabulary")
    try:
        from paddle_tpu.decode import kimi_linear  # noqa: F401
    except ImportError as e:
        # a checkout from before this model: refuse before a device is
        # touched, so that the run ends at once
        raise harness.ConfigurationError(
            f"the program in this checkout cannot run a configuration of "
            f"kind {cell.kind!r}: {e}") from None


def model_config(cfg: dict):
    from paddle_tpu.decode.kimi_linear import KimiLinearConfig
    return KimiLinearConfig.from_dict(
        {**{k: cfg[k] for k in MODEL_KEYS},
         "max_seq_len": int(cfg["max_seq_len"]), "dtype": str(cfg["dtype"])})


def reference_config(cfg: dict) -> dict:
    return {k: cfg[k] for k in MODEL_KEYS + ("rope_theta",) if k in cfg}


# norm weights: 1 + 0.1 N(0, 1)
NORMS = ("ln1", "ln2", "final_norm", "kv_norm", "o_norm")
# what a matrix is drawn at, over its fan-in^-0.5, by the mixer it belongs to:
# the latent queries at 2.5 (visible scores then have a standard deviation
# near 2.5: a softmax over thousands of keys that is not flat) and the latent
# layers' last matrix at 2 (it averages values); the experts' last matrix at
# 2.5 (silu(g) * u has a root mean square near 0.6, and of eight choices at
# 0.3 each two are held)
GAINS = {"mla": {"wq": 2.5, "wo": 2.0}, "ffn": {"e_down": 2.5}}
# the selection bias: N(0, BIAS_STD^2).  256 sigmoid scores lie about 0.007
# apart near the eighth, so 0.004 turns a fifth to a half of the tokens'
# chosen sets (REFERENCE_RANGES holds it)
BIAS_STD = 0.004
DECAYS = ("a_log", "dt_bias")
_DT_MIN, _DT_MAX = 1e-3, 1e-1


def draw_rule(kind: str, leaf: str, shape: tuple):
    """How :func:`make_params` makes the tensor named ``leaf`` of a layer
    whose mixer is ``kind`` (``kda`` / ``mla``; None: the model's own):
    ``norm``, ``a_log`` / ``dt_bias`` (the family's own initialisation), or
    the standard deviations of a normal as ((columns, std), ...) over the last
    axis.  Every matrix [.., in, out] is at in^-0.5 times its gain; the taps
    [4, 3W] at 4^-0.5; the embedding at 1."""
    if leaf in NORMS:
        return "norm"
    if leaf in DECAYS:
        return leaf
    if leaf == "emb":
        return ((shape[-1], 1.0),)
    if leaf == "router_bias":
        return ((shape[-1], BIAS_STD),)
    gain = {**GAINS["ffn"], **GAINS.get(kind, {})}.get(leaf, 1.0)
    return ((shape[-1], shape[-2] ** -0.5 * gain),)


def draw_decay(key, rule: str, shape: tuple, dtype):
    """``a_log``: the log of a decay uniform in [1, 16] a head; ``dt_bias``:
    the inverse softplus of a step log-uniform in [1e-3, 1e-1] a channel."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    if rule == "a_log":
        w = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    else:
        u = jax.random.uniform(key, shape, f32)
        dt = jnp.exp(u * (math.log(_DT_MAX) - math.log(_DT_MIN))
                     + math.log(_DT_MIN))
        w = dt + jnp.log(-jnp.expm1(-dt))
    return w.astype(dtype)


def make_params(cfg: dict, seed: int = WEIGHT_SEED) -> dict:
    """Every weight on the device in the configuration's dtype, one jitted
    draw a tensor; the program gives the names and the shapes."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.decode.kimi_linear import param_shapes
    mc = model_config(cfg)
    shapes = param_shapes(mc)
    kinds = {"d": "kda", **{f"p{j}": k for j, k in enumerate(mc.pattern)}}
    make = jax.jit(draw, static_argnums=(1, 2, 3))
    norm = jax.jit(draw_norm, static_argnums=(1, 2, 3))
    decay = jax.jit(draw_decay, static_argnums=(1, 2, 3))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    dtype = jnp.dtype(str(cfg["dtype"]))
    out = {}
    for key, (name, (shape, _)) in zip(keys, shapes.items()):
        stack, _, leaf = name.rpartition(".")
        rule = draw_rule(kinds.get(stack), leaf, tuple(shape))
        if rule == "norm":
            out[name] = norm(key, 1.0, tuple(shape), dtype)
        elif rule in DECAYS:
            out[name] = decay(key, rule, tuple(shape), dtype)
        else:
            out[name] = make(key, rule, tuple(shape), dtype)
    return out


def build_server(cfg: dict, mix: dict, params):
    from paddle_tpu.data import native
    from paddle_tpu.decode import DecodeClient, DecodeEngine, DecodeServer
    from paddle_tpu.decode.kimi_linear import KimiLinearLM
    native.load()       # the native transport, built from source or an error
    eng = mix["engine"]
    engine = DecodeEngine(
        KimiLinearLM(model_config(cfg)), params, name=MODEL,
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
    position fed, ``ids`` [Le, prompt + n - 1, K]; at the judged rows every
    expert layer's routing weights ``weights`` [len(at), Le, K], router input
    ``router_u`` [len(at), Le, D] and router logits ``router_r`` [len(at),
    Le, Er]; and the slot's recurrent rows after the last step, ``state``
    [KDA layers, H, K (value), K (key)]."""

    prompt: np.ndarray
    produced: np.ndarray
    at: np.ndarray
    logits: np.ndarray
    ids: np.ndarray
    weights: np.ndarray
    router_u: np.ndarray
    router_r: np.ndarray
    state: np.ndarray


def judged_steps(n: int) -> List[int]:
    """Of ``n`` teacher-forced tokens: the prefill's (0), the steps that read
    its tail (:data:`JOIN_STEPS`) and the decode steps from
    :data:`JUDGE_FROM` on, every :data:`JUDGE_EVERY`-th — or, of a shorter
    replay, its last steps at that spacing."""
    first = JUDGE_FROM if n > JUDGE_FROM else max(1, (n - 1) % JUDGE_EVERY)
    return sorted({0, *(j for j in JOIN_STEPS if j < n),
                   *range(first, n, JUDGE_EVERY)})


def replay(engine, asks, after_dispatch: Optional[Callable] = None,
           const=None, after_prefill: Optional[Callable] = None,
           tail_from_rung_end: bool = False) -> List[Sample]:
    """``asks``: (prompt, tokens the timed path produced) a request, at most
    ``max_slots``; every request is replayed for as many tokens as the
    shortest has.  Every dispatch goes through the idle engine's executor
    under the engine's own keys and shapes, so it runs the very executables
    the window ran (a miss raises: nothing may compile here).
    ``after_dispatch(state) -> state`` rewrites the state after every
    dispatch, ``after_prefill`` after a prefill alone, ``const`` replaces the
    weights, and ``tail_from_rung_end`` leaves in a slot's tails what a
    prefill told that its prompt fills the rung leaves there — the padded
    rung's last positions' (the controls)."""
    from paddle_tpu.decode.cache import blocks_for
    exe, cache = engine._exe, engine.cache
    const = engine._plist if const is None else const
    S, MB, bs = engine.max_slots, engine.max_blocks_per_seq, cache.block_tokens
    n = min(len(produced) for _, produced in asks)
    at = judged_steps(n)
    k = len(asks)

    def missed():
        raise RuntimeError("replay missed the engine's executable cache")

    def dispatch(key, feed, hook=None):
        outs, new_state = exe.run_callable(key, missed, feed,
                                           state=cache.state(), const=const)
        for h in (hook, after_dispatch):
            if h is not None:
                new_state = h(new_state)
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
        key = f"decode/{engine.name}/prefill/{bucket}"

        def feed(length):
            return [feed_tokens, np.int32(length), np.int32(i),
                    tables[i].copy(), np.uint32(0), np.float32(0.0),
                    np.int32(0)]

        hook = after_prefill
        if tail_from_rung_end:
            dispatch(key, feed(bucket))
            wrong = cache.state()[2][:, i]

            def hook(state, wrong=wrong, i=i):
                return [state[0], state[1], state[2].at[:, i].set(wrong)]
        _, lg, _, chosen, w, u, r = dispatch(key, feed(P), hook)
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
    # a slot at a time: sixteen slots' rows at once are 235 MB beside a pool
    rec = cache.state()[1]
    rows = [np.asarray(rec[:, i], np.float32) for i in range(k)]
    for blocks in held:
        cache.allocator.release(blocks)
    return [Sample(np.asarray(prompt, np.int32),
                   np.asarray(produced[:n], np.int32), np.asarray(at),
                   np.stack(logits[i]).astype(np.float32),
                   np.concatenate(ids[i], axis=1).astype(np.int32),
                   np.stack(ws[i]).astype(np.float32), np.stack(us[i]),
                   np.stack(rs[i]).astype(np.float32), rows[i])
            for i, (prompt, produced) in enumerate(asks)]


def reference_lengths(mix: dict, cfg: dict) -> List[int]:
    """The padded lengths of a cell's reference runs, shortest first: a third
    of the longest prompt (most prompts) and the longest, each with the
    replayed tokens; a sample takes the first that holds it, so a reference
    compiles twice."""
    most = int(mix["prompt_tokens"]["max"]) + REPLAY_TOKENS - 1
    return sorted({int(mix["prompt_tokens"]["max"]) // 3 + REPLAY_TOKENS - 1,
                   most})


def run_reference(params, cfg: dict, samples: List[Sample],
                  lengths: Optional[List[int]] = None, faults=()) -> list:
    """The plain reference's logits at every judged position of every sample,
    its own chosen experts at every position fed and its own readings (the
    KDA layers' states after the last position fed among them): [(logits
    [len(at), V], own ids [Le, prompt + n - 1, K], {name: a number a
    layer})].  The reference is given the program's choices.  ``faults`` make
    it another model: the controls."""
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
        lg, own, stats = reference.forward(
            params, ref_cfg, seq, L, P - 1 + s.at, forced=forced,
            faults=faults)
        out.append((np.asarray(lg), np.asarray(own)[:, :L],
                    {k: np.asarray(v) for k, v in stats.items()}))
    return out


def router_errors(params, cfg: dict, samples: List[Sample], faults=()
                  ) -> tuple:
    """(||program's router logits - u W_r|| / ||u W_r|| a (layer, sample x
    judged row), the product of the program's own ``u`` at the highest
    precision; |program's routing weights - the equations' from the
    program's own router logits and choices| the same rows x K)."""
    ref_cfg = reference_config(cfg)
    sz = reference.sizes(ref_cfg)
    errs, werrs = [], []
    for e in range(sz["L"] - sz["nd"]):
        w, _, _ = reference.layer_weights(params, sz, sz["nd"] + e)
        u = np.concatenate([s.router_u[:, e] for s in samples])
        got = np.concatenate([s.router_r[:, e] for s in samples])
        errs.append(_err(got, np.asarray(reference.router_scores(
            w["router"], u))))
        used = np.concatenate([
            s.ids[e][s.prompt.size - 1 + s.at] for s in samples])
        weights = np.concatenate([s.weights[:, e] for s in samples])
        werrs.append(np.abs(weights - np.asarray(reference.route_weights(
            ref_cfg, got, w["router_bias"], used, faults))))
    return np.stack(errs), np.stack(werrs)


def readings(samples: List[Sample], refs: list, router_err) -> dict:
    """The statistics :data:`LIMITS` and :data:`REFERENCE_RANGES` bound, and
    what they were taken over."""
    prefill, join, decode, gaps, scales, differs, states = ([] for _ in
                                                            range(7))
    for s, (ref_logits, own, stats) in zip(samples, refs):
        err = _err(s.logits, ref_logits)
        early = np.isin(s.at, JOIN_STEPS)
        prefill.append(err[s.at == 0])
        join.append(err[early])
        decode.append(err[(s.at > 0) & ~early])
        chosen = np.take_along_axis(ref_logits, s.produced[s.at][:, None],
                                    1)[:, 0]
        gaps.append(ref_logits.max(-1) - chosen)
        scales.append(np.abs(ref_logits).max())
        differs.append((np.sort(own, -1) != np.sort(s.ids, -1)
                        ).any(-1).ravel())
        # the program keeps a row value-major, the reference key-major
        want = np.swapaxes(stats["states"], -1, -2)
        d = (s.state - want).reshape(want.shape[0], -1)
        states.append(np.sqrt((d * d).sum(-1) / (want * want).reshape(
            want.shape[0], -1).sum(-1)))
    prefill, join, decode = (np.concatenate(a) for a in
                             (prefill, join, decode))
    gaps, differs = np.concatenate(gaps), np.concatenate(differs)
    states = np.concatenate(states)
    scale = float(max(scales))
    if not join.size:           # a replay of one token: nothing was decoded
        join = prefill
    if not decode.size:
        decode = join
    own = {}
    for name in reference.STATS:
        v = np.concatenate([np.ravel(r[2][name]) for r in refs]
                           ).astype(np.float64)
        own["ref_" + name] = [float(v.min()), float(v.max())]
    score_err, weight_err = (np.asarray(a, np.float64) for a in router_err)
    every = [prefill, join, decode, states, score_err, weight_err] + \
        [np.asarray(v) for v in own.values()]
    return {"logit_err_prefill_max": float(prefill.max()),
            "logit_err_join_max": float(join.max()),
            "logit_err_decode_p50": harness.percentile(decode, 0.5),
            "logit_err_decode_p90": harness.percentile(decode, 0.9),
            "state_err_p50": harness.percentile(states, 0.5),
            "route_differs_share": float(differs.mean()),
            "router_score_err_max": float(score_err.max()),
            "route_weight_err_max": float(weight_err.max()),
            "token_gap_max": float(gaps.max()) / scale,
            **own,
            "positions": int(prefill.size + join.size + decode.size),
            "routed_pairs": int(differs.size),
            "prompts": [int(s.prompt.size) for s in samples],
            "steps_replayed": int(len(samples[0].produced) - 1),
            "judged_steps": [int(a) for a in samples[0].at],
            "exact_tokens": int((gaps == 0).sum()), "logit_scale": scale,
            "logit_err_decode_max": float(decode.max()),
            "logit_err_prefill_p50": harness.percentile(prefill, 0.5),
            "state_err_max": float(states.max()),
            "router_score_err_p50": harness.percentile(score_err.ravel(),
                                                       0.5),
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


def pick(done: list, seed: int) -> list:
    """A seeded sample of :data:`SAMPLE` finished requests that produced at
    least :data:`REPLAY_TOKENS` tokens; where fewer produced that many, the
    longest outputs."""
    order = np.random.default_rng(int(seed)).permutation(len(done))
    long = [done[j] for j in order if len(done[j].tokens) >= REPLAY_TOKENS]
    if len(long) < SAMPLE:
        long = sorted((done[j] for j in order),
                      key=lambda r: -len(r.tokens))
    return long[:SAMPLE]


def check_sample(checks, cfg: dict, params, engine, result, seed: int,
                 mix: dict) -> None:
    done = [r for r in result.sent if result.in_window(r) and r.tokens
            and r.failure is None]
    if not done:
        checks.add("reference comparison", False, "no finished request")
        return
    asks = [(r.prompt, list(r.tokens)[:REPLAY_TOKENS])
            for r in pick(done, seed)]
    samples = replay(engine, asks)
    judge(checks, readings(
        samples, run_reference(params, cfg, samples,
                               reference_lengths(mix, cfg)),
        router_errors(params, cfg, samples)))


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
    k = int(cfg["num_experts_per_token"])
    layers = int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])
    checks.add("every token routed over all the router's experts: choices == "
               "tokens x top-k x expert layers, and every assignment to a "
               "held expert has its row",
               dc["prefill_choices"] == dc["prefill_real_tokens"] * k * layers
               and dc["step_choices"] == dc["step_streams"] * k * layers
               and dc["prefill_plan_rows"] - dc["prefill_plan_pad_rows"]
               == dc["prefill_routed_assignments"]
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
