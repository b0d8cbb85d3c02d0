"""Driver of the ``nemotron_h_serve`` kind: a ``decode.nemotron_h.
NemotronHLM`` (ONE mixer a layer by a pattern string: Mamba-2 of 64-wide heads
with a float32 recurrent row and a convolution tail a slot, ungated relu2
experts held by SHARE beside a shared one behind a sigmoid router with a
selection bias, position-free grouped-query attention with a row a token in a
paged pool; a slice of the vocabulary) at the configuration's published widths
behind ``DecodeServer``/``DecodeClient`` on the native transport, all in this
one process, under the cell's traffic mix.

The serve loop is ``drivers/serve.py``'s — same load generator, accounting,
window, drain, program-state checks and ``bench time:`` line — for another
model and another reference check.  What the accepted drivers expose is
imported (``mla_serve.warm_up``, ``sambay_serve.trace_later``,
``smallthinker_serve``'s ``draw`` and ``_err``, ``lfm2_serve``'s
``draw_norm``); ``replay``, ``judge``, ``pick`` and ``run`` read their
module's own constants and model, so they are a copy (as
``drivers/kimi_linear_serve.py``'s are).  The engine
keeps the model name ``lm``, so its programs are ``jit_fn_decode_lm_step`` and
``jit_fn_decode_lm_prefill_<rung>`` and the readers of the serve metrics find
them.

``correct`` is decided after the window on what the timed path produced:
:func:`replay` sends a sample of the window's requests teacher-forced with the
tokens the window produced, through the engine's own compiled programs (its
executable cache is hit by key, nothing compiles) — the prefill at the timed
rung and :data:`REPLAY_TOKENS` - 1 decode steps of the 128-slot program
through pool, recurrent rows and tails — and reads back the judged positions'
logits, the experts chosen at every position fed, at the judged rows every
expert layer's routing weights, router input, router logits and output, and
after the last step the slot's recurrent rows and tails themselves and the
pool's rows of its last positions;
:func:`judge` holds them against the plain reference's full forward
(``benchmark/reference/nemotron_h.py``: the recurrence one position at a
time; given the program's expert choices so that a near tie turned by bf16
activations is not an error of everything downstream) under :data:`LIMITS`.
What holds the routing INDEPENDENTLY of the program is
``route_differs_share``: the reference's own choices from its own float32
activations against the program's, at every position fed.
``router_score_err_max`` and ``route_weight_err_max`` are not independent and
are not meant to be: they take the program's own router input and logits and
hold ONE product's and the weights' precision and equations, which the logits
cannot see.  ``benchmark/nemotron_h_controls.py`` puts lower-precision
controls and planted mechanisms through the same functions; every one must
come out not correct.

The weights are drawn HERE (:func:`make_params`), by the rules the
configuration file's ``assumed`` states; the program gives names and shapes
only, so a fault in the program's own initialiser cannot reach both sides of
the comparison.  :data:`REFERENCE_RANGES` holds the plain reference's own
readings — each mixer's share of the residual stream, the attention scores'
spread, the router's sharpness, how often the selection bias turns a choice,
the share of choices that are held, the step sizes' range and the weakest
decay — to what those rules are meant to give, whatever the program does.
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
from benchmark.reference import nemotron_h as reference

MODEL = "lm"
WEIGHT_SEED = 61            # fixed: traffic, not weights, comes from --seed
SAMPLE = 16                 # requests compared with the reference
REPLAY_TOKENS = 65          # the prefill's token and 64 decode steps
JOIN_STEPS = (1, 2, 3)      # the decode steps that read a prefill's tail
JUDGE_FROM, JUDGE_EVERY = 4, 2      # then judged: 4, 6, ..., 64
# What the reference comparison allows, each a statistic that does not grow
# with the sample (the largest-of grow slowly: their room is wider).  Each
# stands between the sound program's largest reading over the builder's runs
# (eleven of 16 requests, my chip runs, PR 61) and the smallest reading of a
# control that must fail it (benchmark/nemotron_h_controls.py, two seeds of
# 16).  PERF.md section 6 has every reading.
LIMITS = {
    # ||program - reference|| / ||reference|| of one position's logits (bf16
    # through 13 layers against float32, the reference given the program's
    # expert choices): the median and the 90th percentile over the judged
    # decode positions from step 4 on.  Sound 0.01034-0.01044 / 0.01086-0.01102
    # (eleven runs of 16); the step size and the decay in bf16 (the nearest
    # control) 0.0187-0.0196 / 0.0226-0.0242, the tails at e4m3 0.0344 /
    # 0.0369-0.0373: 1.45 times above the sound readings, 1.25-1.4 below
    "logit_err_decode_p50": 0.015,
    "logit_err_decode_p90": 0.016,
    # the same at the prefills' last positions, the largest: what another
    # model moves whatever the state's precision.  Sound 0.01062-0.01133;
    # rotation on the attention layers 0.221-0.231, one norm group for eight
    # 0.449-0.532, the norm before the gate 0.508-0.546, D left out
    # 0.772-0.867 (and the step size in bf16 0.0208-0.0230): 1.5 times above
    # the sound maximum (of sixteen prompts a run; fresh seeds read higher)
    "logit_err_prefill_max": 0.017,
    # the same at the first three decode steps, the largest: what a wrong row
    # or tail at the prefill -> decode join moves first.  Sound
    # 0.01112-0.01164; tails at e4m3 0.0356-0.0367
    "logit_err_join_max": 0.018,
    # ||program - reference|| / ||reference|| of one Mamba layer's recurrent
    # rows of one stream after the last replayed step: the median over
    # (stream, layer).  Sound 0.01083-0.01175 (the scan's INPUTS come through
    # bf16 activations; the largest 0.0158-0.0225); the step size and the
    # decay in bf16 0.0233-0.0275 (a decay of 0.9997 rounds to 1): near the
    # geometric middle.  Rows rounded to bf16 after every dispatch read only
    # 0.0136-0.0137 here - 64 roundings of 2^-9 beside 0.011 of inputs - so
    # that control has a statistic of its own, below
    "state_err_p50": 0.016,
    # share of a stream's nonzero recurrent numbers that bfloat16 holds
    # exactly (their low sixteen bits zero): float32 rows read about 2^-16,
    # rows rounded to bf16 after every dispatch read 1
    "state_bf16_share": 0.01,
    # the same of a layer's convolution tail (the last three inputs, bf16 as
    # the configuration states): the largest over (stream, layer).  Sound
    # 0.01016-0.01080; at e4m3 0.0457-0.0460
    "tail_err_max": 0.02,
    # the same of an attention layer's cache rows [k | v] of a stream's last
    # 64 positions as the POOL holds them (the replayed steps wrote them;
    # bf16 as the configuration states): the largest over (stream, layer).
    # Two layers of thirteen read the pool, so the logits see little of it
    # (decode p90 0.0136-0.0142 at e4m3).  Sound 0.01051-0.01063; at e4m3
    # 0.0298-0.0301: the geometric middle
    "pool_err_max": 0.018,
    # ||program - reference|| / ||reference|| of the FIRST expert layer's
    # output (held experts' weighted relu2 units + the shared one) at a judged
    # row, the reference given the program's choices: the 90th percentile.
    # One Mamba layer lies before it, so this holds the unit itself.  Sound
    # 0.00507-0.00512; a SiLU-gated unit in relu2's place 0.233-0.234 (the
    # step size in bf16, one layer up, 0.0144-0.0153)
    "expert_out_err_p90": 0.009,
    # share of (expert layer, real position) pairs where the program's six
    # experts are not the reference's own six: near ties of a random router
    # under bf16 activations.  The one check of the routing that shares
    # nothing with the program.  Sound 0.0478-0.0497; a planted model
    # 0.42-1.0 (none is guarded by it)
    "route_differs_share": 0.15,
    # ||program's router logits - (the program's own u) W_r at the highest
    # precision|| / ||the latter||, the largest over the judged rows and the
    # layers: float32 accumulation of bf16 products reads 0; logits kept in
    # bf16 read 0.00218-0.00220
    "router_score_err_max": 1e-4,
    # the largest |program's routing weight - the equations' weight from the
    # program's OWN router logits and choices| over the judged rows, layers
    # and the six, of the scaling factor: float32 both sides reads 0 (bf16
    # scores 0.00018-0.00019)
    "route_weight_err_max": 1e-4,
    # a token's gap to the reference's argmax, of the reference's logit
    # scale: the largest over the judged tokens (560 a run).  Sound
    # 0.0032-0.0067; ONE judged token of 560 another stream's 0.37-0.77
    "token_gap_max": 0.03,
}
# What the plain reference's own layers must read for the numbers above to
# guard anything (the configuration's ``assumed``), whatever the program
# does: [low, high] of the smallest and the largest reading over (sample,
# layer).
REFERENCE_RANGES = {
    # a mixer's output over the residual stream it is added to, root mean
    # square over the real positions: each mechanism is visible in the logits
    "ref_mamba_rms": (0.05, 1.5),
    "ref_experts_rms": (0.05, 1.5),
    "ref_attn_rms": (0.05, 1.5),
    # the visible attention scores' standard deviation
    "ref_attn_logit_std": (0.5, 4.0),
    # the mean largest routing weight of six over the scaling factor: 1 / 6 is
    # a flat router
    "ref_top1_weight": (0.168, 0.4),
    # share of real positions whose chosen six the selection bias turns
    "ref_bias_turns_share": (0.05, 0.7),
    # share of the router's choices that fall on the held 64 of 128
    "ref_held_choice_share": (0.35, 0.65),
    # the real positions' smallest and largest step size: the family's range
    # (softplus of the drawn bias plus the projection's part)
    "ref_step_size_min": (1e-7, 1e-2),
    "ref_step_size_max": (5e-2, 5.0),
    # the weakest decay exp(dt A) a real position a head: a memory of
    # thousands of positions
    "ref_decay_weakest": (0.99, 1.0),
}
# kernels whose XLA fallback must never have been taken
FALLBACK_COUNTERS = ("moe.grouped_relu2_fallbacks", "ssm.ssd_fallbacks",
                     "attn.gqa_decode_fallbacks",
                     "attn.gqa_window_prefill_fallbacks")
# counters of decode.<model>.* whose window deltas the per-layer readers use
WINDOW_COUNTERS = (
    "steps", "prefills", "prefill_real_tokens", "prefill_pad_tokens",
    "prefill_routed_assignments", "prefill_choices", "prefill_plan_rows",
    "prefill_tokens_sq", "prefill_scan_chunks", "step_routed_assignments",
    "step_choices", "step_moe_dispatches", "step_experts_touched",
    "step_expert_load_max_sum", "step_context_tokens", "step_streams",
    "step_state_bytes")
MODEL_KEYS = (
    "vocab_size", "hidden_size", "num_hidden_layers",
    "hybrid_override_pattern", "num_attention_heads", "num_key_value_heads",
    "head_dim", "mamba_num_heads", "mamba_head_dim", "n_groups",
    "ssm_state_size", "conv_kernel", "chunk_size", "use_conv_bias",
    "mamba_hidden_act", "mamba_proj_bias", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "n_routed_experts",
    "n_shared_experts", "num_experts_per_tok", "norm_topk_prob",
    "routed_scaling_factor", "n_group", "topk_group", "mlp_hidden_act",
    "mlp_bias", "attention_bias", "use_bias", "layer_norm_epsilon",
    "tie_word_embeddings", "time_step_min", "time_step_max",
    "time_step_floor", "router_experts", "first_expert")


def validate(cell, seconds: float) -> None:
    loadgen.validate_serve_mix(cell.mix, cell.config, seconds)
    ids = cell.mix.get("prompt_ids", {"dist": "uniform"})
    if ids.get("dist") != "uniform":
        raise harness.ConfigurationError(
            "this driver draws prompt ids uniformly over the held vocabulary")
    try:
        from paddle_tpu.decode import nemotron_h  # noqa: F401
    except ImportError as e:
        # a checkout from before this model: refuse before a device is
        # touched, so that the run ends at once
        raise harness.ConfigurationError(
            f"the program in this checkout cannot run a configuration of "
            f"kind {cell.kind!r}: {e}") from None


def model_config(cfg: dict):
    from paddle_tpu.decode.nemotron_h import NemotronHConfig
    return NemotronHConfig.from_dict(
        {**{k: cfg[k] for k in MODEL_KEYS},
         "max_seq_len": int(cfg["max_seq_len"]), "dtype": str(cfg["dtype"])})


def reference_config(cfg: dict) -> dict:
    return {k: cfg[k] for k in MODEL_KEYS + ("rope_theta",) if k in cfg}


# norm weights: 1 + 0.1 N(0, 1)
NORMS = ("ln", "final_norm", "ssm_norm")
# what a matrix is drawn at, over its fan-in^-0.5: the queries at 2 (visible
# scores then have a standard deviation near 2: a softmax over thousands of
# keys that is not flat) and the attention's last matrix at 2 (it averages
# values); everything else at 1 — relu2 of a unit normal has a root mean
# square of 1.22, so an expert layer shows without a gain
GAINS = {"wo": 2.0}
Q_GAIN = 2.0
# the selection bias: N(0, BIAS_STD^2).  128 sigmoid scores lie about 0.01
# apart near the sixth, so this turns a share of the tokens' chosen sets
# that REFERENCE_RANGES holds
BIAS_STD = 0.008
DECAYS = ("a_log", "dt_bias", "d_skip")


def draw_rule(leaf: str, shape: tuple, q_columns: int = 0):
    """How :func:`make_params` makes the tensor named ``leaf``: ``norm``,
    ``a_log`` / ``dt_bias`` / ``d_skip`` (the family's own initialisation),
    or the standard deviations of a normal as ((columns, std), ...) over the
    last axis.  A matrix [.., in, out] is at in^-0.5 times its gain — an
    expert's ``e_up`` [.., F, D] lies [out, in]: at D^-0.5 —; the taps [4,
    ·] at 4^-0.5; the convolution's bias at 0.02; the embedding at 1."""
    if leaf in NORMS:
        return "norm"
    if leaf in DECAYS:
        return leaf
    if leaf == "emb":
        return ((shape[-1], 1.0),)
    if leaf == "router_bias":
        return ((shape[-1], BIAS_STD),)
    if leaf == "conv_b":
        return ((shape[-1], 0.02),)
    if leaf == "e_up":
        return ((shape[-1], shape[-1] ** -0.5),)
    std = shape[-2] ** -0.5 * GAINS.get(leaf, 1.0)
    if leaf == "wqkv":
        return ((q_columns, std * Q_GAIN), (shape[-1] - q_columns, std))
    return ((shape[-1], std),)


def draw_decay(key, rule: str, shape: tuple, dtype, steps: tuple):
    """``a_log``: the log of a decay uniform in [1, 16] a head; ``dt_bias``:
    the inverse softplus of a step log-uniform in [time_step_min,
    time_step_max] floored at time_step_floor (``steps``); ``d_skip``: 1."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    if rule == "a_log":
        w = jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0))
    elif rule == "d_skip":
        w = jnp.ones(shape, f32)
    else:
        low, high, floor = steps
        u = jax.random.uniform(key, shape, f32)
        dt = jnp.maximum(jnp.exp(u * (np.log(high) - np.log(low))
                                 + np.log(low)), floor)
        w = dt + jnp.log(-jnp.expm1(-dt))
    return w.astype(dtype)


def make_params(cfg: dict, seed: int = WEIGHT_SEED) -> dict:
    """Every weight on the device in the configuration's dtype, one jitted
    draw a tensor; the program gives the names and the shapes."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.decode.nemotron_h import param_shapes
    mc = model_config(cfg)
    shapes = param_shapes(mc)
    make = jax.jit(draw, static_argnums=(1, 2, 3))
    norm = jax.jit(draw_norm, static_argnums=(1, 2, 3))
    decay = jax.jit(draw_decay, static_argnums=(1, 2, 3, 4))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    dtype = jnp.dtype(str(cfg["dtype"]))
    steps = tuple(float(cfg[k]) for k in ("time_step_min", "time_step_max",
                                          "time_step_floor"))
    out = {}
    for key, (name, (shape, _)) in zip(keys, shapes.items()):
        rule = draw_rule(name.rpartition(".")[2], tuple(shape), mc.q_width)
        if rule == "norm":
            out[name] = norm(key, 1.0, tuple(shape), dtype)
        elif rule in DECAYS:
            out[name] = decay(key, rule, tuple(shape), dtype, steps)
        else:
            out[name] = make(key, rule, tuple(shape), dtype)
    return out


def build_server(cfg: dict, mix: dict, params):
    from paddle_tpu.data import native
    from paddle_tpu.decode import DecodeClient, DecodeEngine, DecodeServer
    from paddle_tpu.decode.nemotron_h import NemotronHLM
    native.load()       # the native transport, built from source or an error
    eng = mix["engine"]
    engine = DecodeEngine(
        NemotronHLM(model_config(cfg)), params, name=MODEL,
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
    ``router_u`` [len(at), Le, D], router logits ``router_r`` [len(at), Le,
    Er] and output ``expert_out`` [len(at), Le, D]; and after the last step
    the slot's recurrent rows ``state`` [Mamba layers, H, N, P] (unpacked
    from the kept layout) and convolution tails ``tails`` [Mamba layers, K -
    1, conv width]; and the pool's rows of the stream's last
    ``reference.POOL_ROWS`` positions, ``pool`` [attention layers, ·, 2·kw]."""

    prompt: np.ndarray
    produced: np.ndarray
    at: np.ndarray
    logits: np.ndarray
    ids: np.ndarray
    weights: np.ndarray
    router_u: np.ndarray
    router_r: np.ndarray
    expert_out: np.ndarray
    state: np.ndarray
    tails: np.ndarray
    pool: np.ndarray


def judged_steps(n: int) -> List[int]:
    """Of ``n`` teacher-forced tokens: the prefill's (0), the steps that read
    its tail (:data:`JOIN_STEPS`) and the decode steps from
    :data:`JUDGE_FROM` on, every :data:`JUDGE_EVERY`-th — or, of a shorter
    replay, its last steps at that spacing."""
    first = JUDGE_FROM if n > JUDGE_FROM else max(1, (n - 1) % JUDGE_EVERY)
    return sorted({0, *(j for j in JOIN_STEPS if j < n),
                   *range(first, n, JUDGE_EVERY)})


def unpack_rows(kept: np.ndarray, P: int) -> np.ndarray:
    """A slot's rows as the program keeps them [layers, ·, N, ·] → [layers,
    H, N, P]: 64-wide heads lie two to a lane tile, head 2j on lanes 0-63 of
    pair j and head 2j + 1 on lanes 64-127 (the program's layout, undone
    here in numpy: the reference keeps a head a row)."""
    if kept.shape[-1] == P:
        return kept
    L, Hp, N, _ = kept.shape
    return kept.reshape(L, Hp, N, 2, P).transpose(0, 1, 3, 2, 4).reshape(
        L, 2 * Hp, N, P)


def replay(engine, asks, after_dispatch: Optional[Callable] = None,
           const=None, after_prefill: Optional[Callable] = None
           ) -> List[Sample]:
    """``asks``: (prompt, tokens the timed path produced) a request, at most
    ``max_slots``; every request is replayed for as many tokens as the
    shortest has.  Every dispatch goes through the idle engine's executor
    under the engine's own keys and shapes, so it runs the very executables
    the window ran (a miss raises: nothing may compile here).
    ``after_dispatch(state) -> state`` rewrites the state after every
    dispatch, ``after_prefill`` after a prefill alone and ``const`` replaces
    the weights (the controls)."""
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
    logits, ids, ws, us, rs, eo = ([[] for _ in asks] for _ in range(6))
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
        _, lg, _, chosen, w, u, r, out = dispatch(
            f"decode/{engine.name}/prefill/{bucket}",
            [feed_tokens, np.int32(P), np.int32(i), tables[i].copy(),
             np.uint32(0), np.float32(0.0), np.int32(0)], after_prefill)
        logits[i].append(np.asarray(lg))
        ids[i].append(np.asarray(chosen)[:, :P])
        for rows, a in ((ws, w), (us, u), (rs, r), (eo, out)):
            rows[i].append(np.asarray(a)[:, 0])
    zeros_u, zeros_i = np.zeros((S,), np.uint32), np.zeros((S,), np.int32)
    zeros_f = np.zeros((S,), np.float32)
    tokens, positions = zeros_i.copy(), zeros_i.copy()
    for j in range(1, n):
        for i, (prompt, produced) in enumerate(asks):
            tokens[i], positions[i] = produced[j - 1], prompt.size + j - 1
        _, lg, _, chosen, w, u, r, out = dispatch(
            f"decode/{engine.name}/step",
            [tokens.copy(), positions.copy(), tables.copy(), zeros_u, zeros_i,
             zeros_f, zeros_i])
        chosen = np.asarray(chosen[:, :k])
        for i in range(k):
            ids[i].append(chosen[:, i:i + 1])
        if j in at:
            lg = np.asarray(lg[:k])
            w, u, r, out = (np.asarray(a[:, :k]) for a in (w, u, r, out))
            for i in range(k):
                logits[i].append(lg[i])
                for rows, a in ((ws, w), (us, u), (rs, r), (eo, out)):
                    rows[i].append(a[:, i])
    # a slot at a time: sixteen slots' rows at once are 200 MB beside a pool
    kv, rec, conv = cache.state()
    P = engine.model.config.mamba_head_dim
    rows = [unpack_rows(np.asarray(rec[:, i], np.float32), P)
            for i in range(k)]
    tails = [np.asarray(conv[:, i], np.float32) for i in range(k)]
    pool = []
    for i, (prompt, _) in enumerate(asks):
        # the stream's last positions' rows, where its table says they lie
        at_pos = np.maximum(int(prompt.size) + n - 1 - reference.POOL_ROWS
                            + np.arange(reference.POOL_ROWS), 0)
        pool.append(np.asarray(
            kv[:, tables[i, at_pos // bs], at_pos % bs], np.float32))
    for blocks in held:
        cache.allocator.release(blocks)
    return [Sample(np.asarray(prompt, np.int32),
                   np.asarray(produced[:n], np.int32), np.asarray(at),
                   np.stack(logits[i]).astype(np.float32),
                   np.concatenate(ids[i], axis=1).astype(np.int32),
                   np.stack(ws[i]).astype(np.float32), np.stack(us[i]),
                   np.stack(rs[i]).astype(np.float32),
                   np.stack(eo[i]).astype(np.float32), rows[i], tails[i],
                   pool[i])
            for i, (prompt, produced) in enumerate(asks)]


def reference_lengths(mix: dict, cfg: dict) -> List[int]:
    """The padded lengths of a cell's reference runs, shortest first: a
    quarter of the longest prompt (most prompts), a half, and the longest,
    each with the replayed tokens; a sample takes the first that holds it, so
    a reference compiles three times."""
    most = int(mix["prompt_tokens"]["max"])
    return sorted({most // 4 + REPLAY_TOKENS - 1,
                   most // 2 + REPLAY_TOKENS - 1, most + REPLAY_TOKENS - 1})


def run_reference(params, cfg: dict, samples: List[Sample],
                  lengths: Optional[List[int]] = None, faults=()) -> list:
    """The plain reference's logits at every judged position of every sample,
    its own chosen experts at every position fed and its own readings (the
    Mamba layers' states and tails after the last position fed and the
    expert layers' outputs at the judged positions among them): [(logits
    [len(at), V], own ids [Le, prompt + n - 1, K], {name: an array})].  The
    reference is given the program's choices.  ``faults`` make it another
    model: the controls."""
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


def router_errors(params, cfg: dict, samples: List[Sample]) -> tuple:
    """(||program's router logits - u W_r|| / ||u W_r|| a (layer, sample x
    judged row), the product of the program's own ``u`` at the highest
    precision; |program's routing weights - the equations' from the
    program's own router logits and choices| the same rows x K, over the
    scaling factor)."""
    ref_cfg = reference_config(cfg)
    errs, werrs = [], []
    for e in range(samples[0].ids.shape[0]):
        w = reference.layer_weights(params, "E", e, but=("e_up", "e_down"))
        u = np.concatenate([s.router_u[:, e] for s in samples])
        got = np.concatenate([s.router_r[:, e] for s in samples])
        errs.append(_err(got, np.asarray(reference.router_scores(
            w["router"], u))))
        used = np.concatenate([
            s.ids[e][s.prompt.size - 1 + s.at] for s in samples])
        weights = np.concatenate([s.weights[:, e] for s in samples])
        werrs.append(np.abs(weights - np.asarray(reference.route_weights(
            ref_cfg, got, w["router_bias"], used)))
            / float(cfg["routed_scaling_factor"]))
    return np.stack(errs), np.stack(werrs)


def _rows_err(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """||got - want|| / ||want|| a leading row (a layer), over the rest."""
    d = (got - want).reshape(want.shape[0], -1)
    w = want.reshape(want.shape[0], -1)
    return np.sqrt((d * d).sum(-1) / (w * w).sum(-1))


def readings(samples: List[Sample], refs: list, router_err) -> dict:
    """The statistics :data:`LIMITS` and :data:`REFERENCE_RANGES` bound, and
    what they were taken over."""
    (prefill, join, decode, gaps, scales, differs, states, tails, outs,
     pools, held16) = ([] for _ in range(11))
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
        states.append(_rows_err(s.state, stats["states"]))
        bits = np.ascontiguousarray(s.state, np.float32).view(np.uint32)
        held16.append([np.count_nonzero((bits & 0xFFFF) == 0)
                       - np.count_nonzero(bits << 1 == 0),
                       np.count_nonzero(bits << 1)])
        tails.append(_rows_err(s.tails, stats["tails"]))
        pools.append(_rows_err(s.pool, stats["pool_rows"]))
        # the first expert layer's output at the judged rows
        outs.append(_err(s.expert_out[:, 0], stats["expert_out"][0]))
    prefill, join, decode = (np.concatenate(a) for a in
                             (prefill, join, decode))
    gaps, differs = np.concatenate(gaps), np.concatenate(differs)
    states, tails, outs, pools = (np.concatenate(a) for a in
                                  (states, tails, outs, pools))
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
    every = [prefill, join, decode, states, tails, pools, outs, score_err,
             weight_err] + [np.asarray(v) for v in own.values()]
    return {"logit_err_prefill_max": float(prefill.max()),
            "logit_err_join_max": float(join.max()),
            "logit_err_decode_p50": harness.percentile(decode, 0.5),
            "logit_err_decode_p90": harness.percentile(decode, 0.9),
            "state_err_p50": harness.percentile(states, 0.5),
            "state_bf16_share": float(np.sum(held16, 0)[0]
                                      / max(np.sum(held16, 0)[1], 1)),
            "tail_err_max": float(tails.max()),
            "pool_err_max": float(pools.max()),
            "expert_out_err_p90": harness.percentile(outs, 0.9),
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
            "expert_out_err_max": float(outs.max()),
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
    k = int(cfg["num_experts_per_tok"])
    layers = str(cfg["hybrid_override_pattern"]
                 )[:int(cfg["num_hidden_layers"])].count("E")
    checks.add("every token routed over all the router's experts: choices == "
               "tokens x top-k x expert layers, and some of them are held",
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
