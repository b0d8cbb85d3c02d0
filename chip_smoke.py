"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py          # on a machine with a TPU; one process

Drives the main paths once through the entry points a user would call, at
the full width of Transformer-base (random weights from a seed), and
checks what comes out by the repo's own means:

- trainer:       layer DSL + append_backward + optimizer, run by
                 ``fluid.Executor(fluid.TPUPlace())`` — ``run`` steps and
                 one ``run_steps`` call; loss finite and falling, one
                 compile per executable, none after the first step.
- kernels:       the Pallas kernels the program selects by itself on a
                 TPU (fused LSTM / GRU fwd+bwd, flash attention fwd+bwd),
                 each inside a program run by ``Executor.run``, checked
                 against the XLA lowering of the same op, with a Mosaic
                 custom call found in the executable that ran.
- decode_server: a Transformer-base ``TransformerLM`` served by
                 ``DecodeEngine`` behind ``DecodeServer``/``DecodeClient``;
                 requests join and leave mid-batch; tokens checked
                 against a full re-forward.
- expert_walk:   the prefill form of the grouped expert kernel (an expert a
                 grid step) against its XLA fallback at DeepSeek-V2-Lite's
                 expert shapes, on a prompt's plan and on one expert's.
- expert_plan:   the experts' plan (made by counting) against a NumPy stable
                 sort, and ``routed_experts`` against the result under the
                 sorted plan, to the bit, at the four expert cells' shapes.
- group_flash:   the serve cells' prefill attention (a K/V head's group a
                 grid step, pad tiles skipped) against dense attention at
                 SmallThinker's and LFM2's head layouts, 12,288 positions.
- proj_xent:     the trainer's output projection that keeps the loss's
                 log-sum-exp (``kernels/xent.py proj_xent_fwd``) against
                 ``jnp.matmul`` + ``logsumexp`` at the train cells' head,
                 24,576 x 512 x 37,000, and the kernel-alone table: ms a
                 call by tile shape beside XLA's two operations.
- latent_walk:   the latent decode walk (``kernels/mla.py``'s
                 ``mla_paged_decode_attn``) against its XLA lowering at the
                 three latent cells' shapes, and the kernel-alone table: ms
                 a call as it is and with the copies taken out, beside the
                 bytes' time at the HBM peak.
- four_chip:     the trainer program through ``ParallelExecutor`` on a
                 dp=2 x mp=2 mesh, then one ZeRO step on dp=4 — only
                 where JAX sees >= 4 devices.

There is no size switch and no CPU mode: with no TPU the script exits
non-zero at once, naming the platform it found.  The phase functions take
their sizes as arguments so tier-1 can run them tiny on the CPU mesh
(tests/test_chip_smoke.py).  Wall and compile seconds in the output are
set-up facts of this run, not benchmark metrics.  The full report
(environment, phases, fallback counters, cache facts) is printed as one
``chip_smoke report: {...}`` line and written to
``chip_smoke_out/last_run.json``; the LAST line of stdout is the result
object and nothing else, ``{"ok": ..., "device": {"platform", "kind",
"count"}}``.  The exit code is 0 only if every phase passed and every
fallback counter is zero.
"""
from __future__ import annotations

import gc
import json
import os
import re
import sys
import threading
import time
import traceback

import numpy as np

# fallback counters of the main paths: any non-zero value fails the run.
# (decode.attn_fallbacks can no longer be incremented — the latch that
# counted it was removed with the fallback — and is read so that a
# re-introduced one could not pass unnoticed.)
FALLBACK_COUNTERS = (
    "decode.attn_fallbacks",
    "quant.matmul_fallbacks",
    "quant.lower_fallbacks",
    "quant.runtime_disables",
    "compile_cache.faults",
)

# the XLA fallbacks of the latent-attention LM's kernels (kernels/moe.py,
# kernels/mla.py): taken only by name (impl="xla"), counted when taken
LATENT_FALLBACK_COUNTERS = (
    "moe.grouped_swiglu_fallbacks",
    "mla.decode_attn_fallbacks",
    "mla.prefill_attn_fallbacks",
)

# the XLA fallbacks of the hybrid (state-space / window / shared-pool) LM's
# kernels (kernels/ssm.py, kernels/diffattn.py)
HYBRID_FALLBACK_COUNTERS = (
    "ssm.scan_fallbacks",
    "attn.diff_decode_fallbacks",
    "attn.diff_prefill_fallbacks",
)

# the XLA fallbacks of the window-and-full attention expert LM's kernels
# (kernels/gqa.py's window flash forward and ring walk, kernels/moe.py with
# the gate relu)
WINDOW_EXPERT_FALLBACK_COUNTERS = (
    "moe.grouped_reglu_fallbacks",
    "attn.gqa_window_prefill_fallbacks",
    "attn.gqa_ring_decode_fallbacks",
)

# the XLA fallbacks of the short-convolution expert LM's kernels
# (kernels/gqa.py at heads of 64, kernels/moe.py with the gate silu)
CONV_EXPERT_FALLBACK_COUNTERS = (
    "moe.grouped_swiglu_fallbacks",
    "attn.gqa_window_prefill_fallbacks",
    "attn.gqa_decode_fallbacks",
)

# the XLA fallbacks of the parallel-hybrid (Mamba-2 beside grouped-query
# attention) LM's kernels (kernels/ssd.py, kernels/gqa.py)
PARALLEL_HYBRID_FALLBACK_COUNTERS = (
    "ssm.ssd_fallbacks",
    "attn.gqa_decode_fallbacks",
    "attn.gqa_prefill_fallbacks",
)

MOSAIC_CALL = "tpu_custom_call"


class SmokeFailure(AssertionError):
    """A check of the smoke did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# instrumentation: what jax compiled, and what its persistent cache served
# ---------------------------------------------------------------------------

class CompileLog:
    """Counts XLA backend compiles (with their seconds) and persistent
    compilation-cache hits through ``jax.monitoring`` — independent of
    the executor's own counters, so a recompile hidden inside a jit
    cache still shows."""

    def __init__(self):
        import jax
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self._event = BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **_kw):
        if event == self._event:
            self.compiles += 1
            self.compile_s += float(duration)

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return (self.compiles, self.compile_s, self.cache_hits)


def counters() -> dict:
    from paddle_tpu.observability import stats
    return stats.to_dict()


def counter_delta(before: dict, name: str) -> int:
    return int(counters().get(name, 0)) - int(before.get(name, 0))


def rel_max_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want))
                 / (np.max(np.abs(want)) + 1e-12))


def fresh_program(build_fn, seed: int):
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.program import Program, program_guard

    prog, startup = Program(), Program()
    prog.random_seed = startup.random_seed = seed
    with program_guard(prog, startup), unique_name.guard():
        out = build_fn()
    return prog, startup, out


def transformer_program(vocab, max_len, d_model, n_head, d_ffn, n_layer,
                        dtype, dropout, warmup_steps, seed=7):
    from paddle_tpu.models import transformer

    return fresh_program(lambda: transformer.build(
        src_vocab=vocab, tgt_vocab=vocab, max_len=max_len, d_model=d_model,
        n_head=n_head, d_ffn=d_ffn, n_layer=n_layer, dropout=dropout,
        warmup_steps=warmup_steps, dtype=dtype, attention_impl="auto"), seed)


def transformer_feed(batch, max_len, vocab, seed=0):
    rng = np.random.RandomState(seed)
    mask = np.ones((batch, max_len), "float32")
    return {"src_ids": rng.randint(0, vocab, (batch, max_len)).astype("int64"),
            "tgt_ids": rng.randint(0, vocab, (batch, max_len)).astype("int64"),
            "lbl_ids": rng.randint(0, vocab, (batch, max_len)).astype("int64"),
            "src_mask": mask, "tgt_mask": mask}


# ---------------------------------------------------------------------------
# phase 1: trainer
# ---------------------------------------------------------------------------

def phase_trainer(place, batch=32, max_len=256, vocab=32000, d_model=512,
                  n_head=8, d_ffn=2048, n_layer=6, dtype="bfloat16",
                  dropout=0.1, steps=6, scan_steps=4):
    """Transformer-base (the widths and depth of
    benchmark/configs/transformer-base-wmt.json), trained on one fixed
    batch so the loss must fall.  ``warmup_steps`` is cut to 8 (a schedule hyper-parameter, not
    geometry): at the default 4000 the first steps' learning rate is
    1e-7 and nothing could be seen to move."""
    import paddle_tpu as fluid
    from paddle_tpu.core.executor import Scope

    prog, startup, (_, loss, _) = transformer_program(
        vocab, max_len, d_model, n_head, d_ffn, n_layer, dtype, dropout,
        warmup_steps=8)
    feed = transformer_feed(batch, max_len, vocab)
    scope = Scope()
    exe = fluid.Executor(place)
    c0 = counters()
    exe.run(startup, scope=scope)

    losses = []
    (l,) = exe.run(prog, feed=feed, fetch_list=[loss], scope=scope)
    losses.append(float(l))
    after_first = LOG.mark()
    for _ in range(steps - 1):
        (l,) = exe.run(prog, feed=feed, fetch_list=[loss], scope=scope)
        losses.append(float(l))
    check(LOG.mark()[0] == after_first[0],
          f"run() recompiled after its first step: "
          f"{LOG.mark()[0] - after_first[0]} extra XLA compile(s)")

    stacked = {n: np.stack([v] * scan_steps) for n, v in feed.items()}
    (ls,) = exe.run_steps(prog, feed=stacked, fetch_list=[loss], scope=scope)
    after_scan = LOG.mark()
    (ls2,) = exe.run_steps(prog, feed=stacked, fetch_list=[loss],
                           scope=scope)
    check(LOG.mark()[0] == after_scan[0],
          "run_steps() recompiled on its second call")
    losses += [float(x) for x in np.asarray(ls).reshape(-1)]
    losses += [float(x) for x in np.asarray(ls2).reshape(-1)]

    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: first {losses[0]:.4f} last {losses[-1]:.4f}")
    # exactly one executable each for startup, run and run_steps
    misses = counter_delta(c0, "executor.cache_misses")
    check(misses == 3, f"expected 3 executor compiles "
                       f"(startup, run, run_steps), counted {misses}")
    check(counter_delta(c0, "executor.shape_recompiles") == 0,
          "executor counted a shape recompile")
    check(counter_delta(c0, "executor.cache_hits") == steps,
          "executor cache hits do not match the steps taken")
    exe.close()
    # the output projection and its loss (fc_softmax_with_cross_entropy): on
    # the chip both executables took the kernel that keeps the log-sum-exp
    chose = {k: counter_delta(c0, f"loss.proj_xent_{k}")
             for k in ("kernel", "fallbacks")}
    if place is not None:
        check(chose["kernel"] >= 2 and not chose["fallbacks"],
              f"the output projection did not take proj_xent_fwd: {chose}")
    return {"first_loss": losses[0], "last_loss": losses[-1],
            "steps": len(losses), "executor_compiles": misses,
            "tokens_per_step": batch * max_len, "proj_xent": chose}


# ---------------------------------------------------------------------------
# phase 2: the kernels the program selects by itself on a TPU
# ---------------------------------------------------------------------------

def _run_warm(place, prog, startup, fetch, feed, expect_mosaic):
    """Warm-start (AOT compile) then run one step through the public
    API; returns (fetched arrays, whether the executable that ran holds
    a Mosaic custom call)."""
    import paddle_tpu as fluid
    from paddle_tpu.core.executor import Scope

    scope = Scope()
    exe = fluid.Executor(place)
    exe.run(startup, scope=scope)
    warmed = exe.warm_start(prog, feed, fetch, scope=scope)
    check(warmed["compiled"] == 1 and not warmed["skipped"],
          f"warm_start did not compile the program: {warmed}")
    c0 = counters()
    outs = exe.run(prog, feed=feed, fetch_list=fetch, scope=scope)
    check(counter_delta(c0, "executor.cache_misses") == 0,
          "run() did not dispatch the warm-started executable")
    hlo = exe.aot_hlo()
    check(len(hlo) == 1, f"expected one AOT executable, found {len(hlo)}")
    has_call = MOSAIC_CALL in hlo[0]
    outs = [np.asarray(o, np.float32) for o in outs]
    exe.close()
    if expect_mosaic is not None:
        check(has_call == expect_mosaic,
              f"Mosaic custom call present={has_call}, "
              f"expected {expect_mosaic}")
    return outs, has_call


def _kernel_vs_xla(place, on_chip, make, feed, tol):
    """Run one program twice — ``make(True)`` with the kernel,
    ``make(False)`` with the XLA lowering of the same op, each returning
    (program, startup, fetch list) — and compare every fetch.  On the
    chip the executable that ran must (not) hold a Mosaic custom call."""
    got, has_call = _run_warm(place, *make(True), feed,
                              True if on_chip else None)
    want, _ = _run_warm(place, *make(False), feed,
                        False if on_chip else None)
    errs = [rel_max_err(g, w) for g, w in zip(got, want)]
    check(all(np.isfinite(g).all() for g in got), "non-finite kernel output")
    check(max(errs) <= tol,
          f"kernel vs XLA lowering: rel-max errors {errs} exceed {tol}")
    return {"mosaic_custom_call": has_call, "max_rel_err": max(errs),
            "tolerance": tol, "rel_errs": [float(f"{e:.2e}") for e in errs]}


def _rnn_stack_case(place, on_chip, cell, batch, seq, hidden, layers):
    """The recurrent stack of models/stacked_lstm.py (fc -> cell, every
    second layer reversed, so a reversed layer feeds further ops) for
    ``cell`` "lstm" or "gru", with
    the loss on the hidden states themselves.  (Under the model's own
    classifier loss the LSTM weight gradients at initialization are
    ~2e-6, and XLA's default-precision result for them is 9-15% from
    its own highest-precision one — too ill-conditioned to compare two
    implementations on: PERF.md Bring-up.)"""
    import paddle_tpu as fluid

    gates = {"lstm": 4, "gru": 3}[cell] * hidden

    def rnn(x, reverse):
        if cell == "lstm":
            return fluid.layers.dynamic_lstm(x, gates, is_reverse=reverse)[0]
        return fluid.layers.dynamic_gru(x, hidden, is_reverse=reverse)

    def build():
        h = rnn(fluid.layers.data("x", [seq, gates]), False)
        for i in range(2, layers + 1):
            h = rnn(fluid.layers.fc(h, gates, num_flatten_dims=2),
                    (i % 2) == 0)
        loss = fluid.layers.mean(fluid.layers.square(h))
        pairs = fluid.append_backward(loss)
        return [loss] + [g for p, g in pairs if p.name.startswith(cell)]

    def make(kernel):
        """The cell the op selects by itself on the chip (off it nothing
        selects one: forced, in interpret mode), or the XLA scan."""
        prog, startup, fetch = fresh_program(build, 11)
        use_pallas = (None if on_chip else True) if kernel else False
        if use_pallas is not None:
            for op in prog.global_block.ops:
                if op.type in (cell, cell + "_grad"):
                    op.set_attr("use_pallas_kernel", use_pallas)
        return prog, startup, fetch

    rng = np.random.RandomState(0)
    feed = {"x": (rng.randn(batch, seq, gates) * 0.3).astype("float32")}
    # f32 cells: at the TPU's default precision the XLA scan's and
    # Mosaic's f32 matmuls both run one bf16 pass — bit-equal forward,
    # ~2e-4 apart in the gradients (the backward dots round differently)
    return _kernel_vs_xla(place, on_chip, make, feed, tol=5e-3)


def _flash_case(place, on_chip, batch, seq, n_head, head_dim):
    """Causal self-attention block of models/transformer.py with
    attention_impl "auto" (the flash kernel from the op's own
    thresholds on a TPU) against impl "xla": output and the gradients of
    the q/k/v/out projections, bf16."""
    import paddle_tpu as fluid
    from paddle_tpu.models import transformer

    d_model = n_head * head_dim

    def build(impl):
        x = fluid.layers.data("x", [seq, d_model])
        mask = fluid.layers.data("mask", [seq])
        xb = fluid.layers.cast(x, "bfloat16")
        out = transformer.multi_head_attention(
            xb, xb, xb, None, d_model, n_head, 0.0, "att", kv_mask=mask,
            causal=True, impl=impl)
        out = fluid.layers.cast(out, "float32")
        loss = fluid.layers.mean(fluid.layers.square(out))
        pairs = fluid.append_backward(loss)
        return [loss, out] + [g for _, g in pairs]

    def make(kernel):
        impl = ("auto" if on_chip else "pallas") if kernel else "xla"
        return fresh_program(lambda: build(impl), 13)

    rng = np.random.RandomState(2)
    feed = {"x": (rng.randn(batch, seq, d_model) * 0.5).astype("float32"),
            "mask": np.ones((batch, seq), "float32")}
    # bf16 operands, f32 accumulation on both sides; they differ in
    # accumulation order and in where probabilities round to bf16
    return _kernel_vs_xla(place, on_chip, make, feed, tol=5e-2)


def phase_kernels(place, on_chip=True,
                  rnn=dict(batch=128, seq=128, hidden=512, layers=3),
                  flash=(dict(batch=2, seq=2048, n_head=4, head_dim=128),
                         dict(batch=2, seq=4096, n_head=8, head_dim=64))):
    """``on_chip=False`` (tier-1, CPU) forces each kernel in Pallas
    interpret mode, since off a TPU no op selects one, and drops the
    Mosaic assertion; on the chip the ops choose for themselves and the
    executable that ran must hold the custom call.  Every case runs even
    if an earlier one failed, so one report names them all."""
    cases = [(cell, _rnn_stack_case, dict(cell=cell, **rnn))
             for cell in ("lstm", "gru")]
    cases += [(f"flash_d{f['head_dim']}_t{f['seq']}", _flash_case, f)
              for f in flash]
    out, failed = {}, []
    for name, fn, sizes in cases:
        try:
            out[name] = fn(place, on_chip, **sizes)
        except Exception as e:  # collected; the phase fails below
            traceback.print_exc()
            failed.append(f"{name}: {e!r}"[:400])
    check(not failed, "; ".join(failed))
    return out


# ---------------------------------------------------------------------------
# phase 3: decode server
# ---------------------------------------------------------------------------

def phase_decode_server(on_chip=True, vocab=32000, d_model=512, n_head=8,
                        d_ffn=2048, n_layer=6, max_seq_len=512, max_slots=4,
                        prompt_lens=(5, 17, 40, 100, 9, 33),
                        new_tokens=(24, 8, 16, 12, 20, 6)):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core import flags
    from paddle_tpu.data import native
    from paddle_tpu.decode import (DecodeClient, DecodeEngine, DecodeServer,
                                   LMConfig, TransformerLM)

    # the RPC plane takes the native transport by default and falls back
    # to Python sockets without a word when the library cannot be built:
    # build it here, from the tracked sources, or fail
    native.load()
    transport_backend = flags.get_flags("rpc_transport")

    cfg = LMConfig(vocab=vocab, d_model=d_model, n_head=n_head, d_ffn=d_ffn,
                   n_layer=n_layer, max_seq_len=max_seq_len)
    model = TransformerLM(cfg)
    params = model.init_params(seed=3)
    attn_impl = "pallas"   # the engine's default (attn_impl=None) by name
    engine = DecodeEngine(model, params, name="lm", max_slots=max_slots,
                          attn_impl=attn_impl)
    server = DecodeServer("127.0.0.1:0", engines={"lm": engine})
    server.start()
    try:
        client = DecodeClient(endpoints=[server.endpoint])

        def wave(seed):
            """All requests at once: more requests than slots, unequal
            budgets, so streams join and leave a running batch."""
            rng = np.random.RandomState(seed)
            prompts = [rng.randint(0, vocab, (n,)).astype("int32")
                       for n in prompt_lens]
            results = [None] * len(prompts)
            errors = []

            def one(i):
                try:
                    results[i] = client.generate(
                        "lm", prompts[i], max_new_tokens=new_tokens[i],
                        timeout=900.0)
                except Exception as e:  # reported below, per stream
                    errors.append(f"request {i}: {e!r}")

            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(len(prompts))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=1000.0)
            check(not any(t.is_alive() for t in threads),
                  "a decode stream did not finish")
            check(not errors, f"decode streams failed: {errors}")
            for i, r in enumerate(results):
                check(len(r["tokens"]) == new_tokens[i]
                      and r.get("finish") == "length",
                      f"stream {i} ended early: {r}")
            return prompts, results

        wave(seed=0)                       # compiles every shape once
        c0, m0 = counters(), LOG.mark()
        prompts, results = wave(seed=1)    # the steady window
        check(counter_delta(c0, "executor.cache_misses") == 0
              and LOG.mark()[0] == m0[0],
              "the decode plane recompiled in the steady window")
        z = engine.decodez()
        check(z["joins"] == z["leaves"] == 2 * len(prompt_lens),
              f"joins/leaves do not add up: {z['joins']}/{z['leaves']}")

        # greedy tokens against a full re-forward of prompt + generated
        # tokens (teacher-forced, so one near-tie cannot cascade)
        plist = model.param_list(params)
        width = max(p.size + n for p, n in zip(prompts, new_tokens))
        full = jax.jit(model.full_logits)
        exact = total = 0
        worst_gap = scale = 0.0
        for p, r in zip(prompts, results):
            toks = np.asarray(r["tokens"], np.int32)
            seq = np.zeros((1, width), np.int32)
            seq[0, :p.size + toks.size] = np.concatenate([p, toks])
            logits = np.asarray(full(
                plist, jnp.asarray(seq),
                jnp.asarray([p.size + toks.size], jnp.int32)))[0]
            for k, tok in enumerate(toks):
                row = logits[p.size + k - 1]
                gap = float(row.max() - row[tok])
                exact += int(gap == 0.0)
                total += 1
                worst_gap = max(worst_gap, gap)
                scale = max(scale, float(np.max(np.abs(row))))
        # the incremental path and the re-forward run the same f32 model
        # through differently shaped matmuls (one bf16 pass each at the
        # TPU's default precision), so a near-tie between two logits can
        # resolve differently; an engine token must still be the
        # reference argmax to within 0.5% of the logit scale (0.02% was
        # seen on the v5e), and such near-ties must be rare
        check(worst_gap <= 0.005 * scale,
              f"an engine token trails the reference argmax by "
              f"{worst_gap:.4f} (logit scale {scale:.2f})")
        check(exact >= 0.9 * total,
              f"only {exact}/{total} greedy tokens equal the re-forward")

        # the decode step and one prefill, lowered at the engine's
        # shapes with the pool donated as run_callable donates it: the
        # kernel is Mosaic's, and the pool [L, NB, bs, H*Dh] stays in
        # place — no program copies, slices or relays it, so each
        # needs less scratch than one K pool and holds no copy of the
        # pool's shape
        S, MB = engine.max_slots, engine.max_blocks_per_seq
        Tb = engine.prefill_ladder.sizes[0]
        zi = jnp.zeros((S,), jnp.int32)
        i0, f0, u0 = jnp.int32(0), jnp.float32(0), jnp.uint32(0)
        pool = engine.cache.k
        programs = {
            "step": (
                lambda pl, state, *a: model.decode_step(
                    pl, state, *a, attn_impl=attn_impl),
                (zi, zi, jnp.zeros((S, MB), jnp.int32),
                 zi.astype(jnp.uint32), zi, jnp.zeros((S,), jnp.float32),
                 zi)),
            "prefill": (
                model.prefill,
                (jnp.zeros((1, Tb), jnp.int32), i0,
                 jnp.zeros((MB,), jnp.int32), u0, f0, i0)),
        }
        in_place = {"pool_bytes": int(pool.nbytes)}
        pool_copy = re.compile(
            r"\[%s\]\S* copy\(" % ",".join(map(str, pool.shape)))
        for name, (fn, feed) in programs.items():
            lowered = jax.jit(fn, donate_argnums=(1,)).lower(
                plist, engine.cache.state(), *feed)
            if name == "step":
                has_call = MOSAIC_CALL in lowered.as_text()
            compiled = lowered.compile()
            temp = int(compiled.memory_analysis().temp_size_in_bytes)
            copies = len(pool_copy.findall(compiled.as_text()))
            in_place[name] = {"temp_bytes": temp, "pool_copies": copies}
            if on_chip:   # off the chip the kernel is interpreted: a
                # loop that carries the pool, which XLA does copy
                check(temp < pool.nbytes and copies == 0,
                      f"the decode {name} does not keep the pool in "
                      f"place: {temp} bytes of scratch against a K pool "
                      f"of {pool.nbytes}, {copies} copies of its shape")
        if on_chip:
            check(has_call, "no Mosaic custom call in the decode step")
        return {"attn_impl": attn_impl, "mosaic_custom_call": has_call,
                "transport": transport_backend, "requests": 2 * len(prompt_lens),
                "tokens_checked": total, "tokens_exact": exact,
                "worst_logit_gap": worst_gap, "logit_scale": scale,
                "joins": z["joins"], "steps": z["steps"],
                "pool_in_place": in_place}
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# phase 3b: the latent-attention expert LM through the same engine
# ---------------------------------------------------------------------------

def phase_latent_lm(on_chip=True, vocab=8192, hidden=512, heads=8, nope=128,
                    rope=64, v_dim=128, rank=512, dense=1024, expert=256,
                    experts=16, top_k=4, layers=3, max_seq_len=1024,
                    max_slots=8, block_tokens=16, prefill_bucket=256,
                    prompt_lens=(40, 200, 131, 256, 77),
                    new_tokens=(12, 6, 20, 4, 9), dtype="bfloat16"):
    """``decode.mla.MLATransformerLM`` (DeepSeek-V2's block at its head
    sizes and latent rank, fewer and narrower experts) through
    ``DecodeEngine``: every stream finishes, the greedy tokens are the full
    forward's up to bf16 near-ties, the three new kernels compile with
    Mosaic, the latent pool stays in place, and no kernel fell back."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.decode import DecodeEngine, SamplingParams
    from paddle_tpu.decode.mla import MLAConfig, MLATransformerLM

    cfg = MLAConfig(
        vocab_size=vocab, hidden_size=hidden, num_hidden_layers=layers,
        num_attention_heads=heads, qk_nope_head_dim=nope,
        qk_rope_head_dim=rope, v_head_dim=v_dim, kv_lora_rank=rank,
        intermediate_size=dense, moe_intermediate_size=expert,
        n_routed_experts=experts, num_experts_per_tok=top_k,
        rope_scaling={"factor": 40, "original_max_position_embeddings": 64,
                      "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                      "mscale_all_dim": 0.707},
        max_seq_len=max_seq_len, dtype=dtype)
    model = MLATransformerLM(cfg)
    params = model.init_params(seed=5)
    c0 = counters()
    engine = DecodeEngine(model, params, name="latent", max_slots=max_slots,
                          block_tokens=block_tokens,
                          prefill_buckets=[prefill_bucket],
                          attn_impl="pallas", cache_dtype=dtype,
                          prefix_cache=False, overcommit=False)
    try:
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, vocab, (n,)).astype("int32")
                   for n in prompt_lens]
        handles = [engine.submit(p, SamplingParams(max_new_tokens=n))
                   for p, n in zip(prompts, new_tokens)]
        results = [h.result(timeout=900.0) for h in handles]
        for i, r in enumerate(results):
            check(len(r["tokens"]) == new_tokens[i]
                  and r.get("finish") == "length",
                  f"latent stream {i} ended early: {r}")
        plist = model.param_list(params)
        full = jax.jit(model.full_logits)
        worst_gap = scale = 0.0
        exact = total = 0
        for p, r in zip(prompts, results):
            toks = np.asarray(r["tokens"], np.int32)
            seq = np.zeros((1, max_seq_len), np.int32)
            seq[0, :p.size + toks.size] = np.concatenate([p, toks])
            logits = np.asarray(full(plist, jnp.asarray(seq)))[0]
            for k, tok in enumerate(toks):
                row = logits[p.size + k - 1]
                gap = float(row.max() - row[tok])
                exact += int(gap == 0.0)
                total += 1
                worst_gap = max(worst_gap, gap)
                scale = max(scale, float(np.max(np.abs(row))))
        # bf16 activations through two attention paths and two row orders
        # of the experts: a token must be the full forward's argmax to
        # within 3% of the logit scale, and mostly exactly it
        check(worst_gap <= 0.03 * scale,
              f"a latent-LM token trails the full forward's argmax by "
              f"{worst_gap:.4f} (logit scale {scale:.2f})")
        check(exact >= 0.8 * total,
              f"only {exact}/{total} latent-LM tokens equal the full forward")

        S, MB = engine.max_slots, engine.max_blocks_per_seq
        zi = jnp.zeros((S,), jnp.int32)
        i0, f0, u0 = jnp.int32(0), jnp.float32(0), jnp.uint32(0)
        pool = engine.cache.latent
        programs = {
            "step": (lambda pl, state, *a: model.decode_step(
                         pl, state, *a, attn_impl="pallas"),
                     (zi, zi, jnp.zeros((S, MB), jnp.int32),
                      zi.astype(jnp.uint32), zi, jnp.zeros((S,), jnp.float32),
                      zi), layers + (layers - 1)),
            "prefill": (model.prefill,
                        (jnp.zeros((1, prefill_bucket), jnp.int32), i0,
                         jnp.zeros((MB,), jnp.int32), u0, f0, i0),
                        layers + (layers - 1)),
        }
        in_place = {"pool_bytes": int(pool.nbytes)}
        pool_copy = re.compile(
            r"\[%s\]\S* copy\(" % ",".join(map(str, pool.shape)))
        for name, (fn, feed, kernels) in programs.items():
            lowered = jax.jit(fn, donate_argnums=(1,)).lower(
                plist, engine.cache.state(), *feed)
            calls = lowered.as_text().count(MOSAIC_CALL)
            compiled = lowered.compile()
            copies = len(pool_copy.findall(compiled.as_text()))
            in_place[name] = {"pool_copies": copies, "mosaic_calls": calls}
            if on_chip:     # off the chip the kernels are interpreted
                check(copies == 0, f"the latent {name} copies the pool "
                                   f"{copies} times")
                check(calls == kernels,
                      f"the latent {name} holds {calls} Mosaic calls, not "
                      f"the {kernels} of its attention and expert layers")
        fell = {n: counter_delta(c0, n) for n in LATENT_FALLBACK_COUNTERS}
        check(not any(fell.values()), f"a new kernel fell back: {fell}")
        z = engine.decodez()
        check(z["cache"].get("kind") == "latent",
              f"/decodez does not name the latent pool: {z['cache']}")
        return {"tokens_checked": total, "tokens_exact": exact,
                "worst_logit_gap": worst_gap, "logit_scale": scale,
                "steps": z["steps"], "cache": z["cache"],
                "pool_in_place": in_place, "fallbacks": fell}
    finally:
        engine.close()


def phase_hybrid_lm(vocab=8192, hidden=512, heads=8, kv_heads=4, ffn=1024,
                    layers=8, window=32, max_seq_len=512, max_slots=4,
                    block_tokens=16, prefill_bucket=128, prompt_len=77,
                    new_tokens=48, dtype="bfloat16"):
    """``decode.sambay.SambaYLM`` (Phi-4-mini-flash-reasoning's stack at its
    head width, eight layers so that every kind exists) through
    ``DecodeEngine``: one short stream, past the window, whose logits at
    every generated position are held against the benchmark's plain
    reference; no kernel fell back (on the chip none is interpreted);
    ``/decodez`` shows the three kinds of state."""
    import jax.numpy as jnp
    from benchmark.reference import sambay as reference
    from paddle_tpu.decode import DecodeEngine, SamplingParams
    from paddle_tpu.decode.sambay import SambaYConfig, SambaYLM

    cfg = SambaYConfig(
        vocab_size=vocab, hidden_size=hidden, num_hidden_layers=layers,
        num_attention_heads=heads, num_key_value_heads=kv_heads,
        intermediate_size=ffn, sliding_window=window,
        max_seq_len=max_seq_len, dtype=dtype)
    model = SambaYLM(cfg)
    params = model.init_params(seed=6)
    c0 = counters()
    engine = DecodeEngine(model, params, name="hybrid", max_slots=max_slots,
                          block_tokens=block_tokens,
                          prefill_buckets=[prefill_bucket],
                          capture_logits=True, attn_impl="pallas",
                          cache_dtype=dtype, prefix_cache=False,
                          overcommit=False)
    try:
        prompt = np.random.RandomState(0).randint(
            0, vocab, (prompt_len,)).astype("int32")
        handle = engine.submit(prompt,
                               SamplingParams(max_new_tokens=new_tokens))
        result = handle.result(timeout=900.0)
        toks = np.asarray(result["tokens"], np.int32)
        check(toks.size == new_tokens and result.get("finish") == "length",
              f"the hybrid stream ended early: {result}")
        seq = np.concatenate([prompt, toks[:-1]])
        want, _, _ = reference.forward(
            {k: jnp.asarray(v) for k, v in params.items()}, cfg.to_dict(),
            seq, seq.size, np.arange(prompt_len - 1, seq.size))
        want = np.asarray(want)
        got = np.stack(handle.logits).astype(np.float32)
        err = np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))
        scale = float(np.abs(want).max())
        gap = want.max(-1) - np.take_along_axis(want, toks[:, None], 1)[:, 0]
        # bf16 activations through 8 layers against float32 at the highest
        # precision: a few percent of the logits' norm, and a token at most
        # 5% of the logit scale under the reference's argmax
        check(float(err.max()) <= (0.08 if dtype == "bfloat16" else 1e-3),
              f"hybrid-LM logits are {err.max():.4f} of their norm off the "
              f"reference")
        check(float(gap.max()) <= 0.05 * scale,
              f"a hybrid-LM token trails the reference's argmax by "
              f"{gap.max():.4f} (logit scale {scale:.2f})")
        fell = {n: counter_delta(c0, n) for n in HYBRID_FALLBACK_COUNTERS}
        check(not any(fell.values()), f"a new kernel fell back: {fell}")
        z = engine.decodez()
        cache = z["cache"]
        check(cache.get("kind") == "hybrid" and all(
            cache.get(k, 0) > 0 for k in (
                "kv_pool_bytes", "window_state_bytes",
                "recurrent_state_bytes", "kv_live_tokens")),
              f"/decodez does not show the three kinds of state: {cache}")
        return {"tokens_checked": int(toks.size),
                "tokens_exact": int((gap == 0).sum()),
                "logit_err_max": float(err.max()),
                "worst_logit_gap": float(gap.max()), "logit_scale": scale,
                "steps": z["steps"], "cache": cache, "fallbacks": fell}
    finally:
        engine.close()


def phase_parallel_hybrid_lm(vocab=8192, hidden=512, heads=4, kv_heads=2,
                             head_dim=128, ffn=1024, layers=2, ssm_heads=4,
                             ssm_head_dim=128, groups=2, d_state=128,
                             chunk=128, max_seq_len=512, max_slots=4,
                             block_tokens=16, prefill_bucket=256,
                             prompt_len=150, new_tokens=40, dtype="bfloat16"):
    """``decode.falcon_h1.FalconH1LM`` (Falcon-H1's layer at its head widths,
    two layers, the published multipliers' places with other numbers) through
    ``DecodeEngine``: one short stream — a prompt that ends inside its second
    scan chunk — whose logits at every generated position are held against
    the benchmark's plain reference; no kernel fell back (on the chip none is
    interpreted); ``/decodez`` shows the two kinds of state."""
    import jax.numpy as jnp
    from benchmark.reference import falcon_h1 as reference
    from paddle_tpu.decode import DecodeEngine, SamplingParams
    from paddle_tpu.decode.falcon_h1 import FalconH1Config, FalconH1LM

    cfg = FalconH1Config(
        vocab_size=vocab, hidden_size=hidden, num_hidden_layers=layers,
        num_attention_heads=heads, num_key_value_heads=kv_heads,
        head_dim=head_dim, intermediate_size=ffn,
        mamba_d_ssm=ssm_heads * ssm_head_dim, mamba_n_heads=ssm_heads,
        mamba_d_head=ssm_head_dim, mamba_n_groups=groups,
        mamba_d_state=d_state, mamba_chunk_size=chunk,
        embedding_multiplier=2.0, lm_head_multiplier=0.5,
        attention_out_multiplier=0.75, key_multiplier=0.5,
        ssm_in_multiplier=0.5, ssm_out_multiplier=0.75,
        ssm_multipliers=(0.9, 1.1, 0.8, 1.25, 0.7),
        mlp_multipliers=(1.4, 0.65), max_seq_len=max_seq_len, dtype=dtype)
    model = FalconH1LM(cfg)
    params = model.init_params(seed=7)
    c0 = counters()
    engine = DecodeEngine(model, params, name="parallel_hybrid",
                          max_slots=max_slots, block_tokens=block_tokens,
                          prefill_buckets=[prefill_bucket],
                          capture_logits=True, cache_dtype=dtype,
                          prefix_cache=False, overcommit=False)
    try:
        prompt = np.random.RandomState(0).randint(
            0, vocab, (prompt_len,)).astype("int32")
        handle = engine.submit(prompt,
                               SamplingParams(max_new_tokens=new_tokens))
        result = handle.result(timeout=900.0)
        toks = np.asarray(result["tokens"], np.int32)
        check(toks.size == new_tokens and result.get("finish") == "length",
              f"the parallel-hybrid stream ended early: {result}")
        seq = np.concatenate([prompt, toks[:-1]])
        want, _, _ = reference.forward(
            {k: jnp.asarray(v) for k, v in params.items()}, cfg.to_dict(),
            seq, seq.size, np.arange(prompt_len - 1, seq.size))
        want = np.asarray(want)
        got = np.stack(handle.logits).astype(np.float32)
        err = np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))
        scale = float(np.abs(want).max())
        gap = want.max(-1) - np.take_along_axis(want, toks[:, None], 1)[:, 0]
        # bf16 activations through two layers against float32 at the highest
        # precision: a few percent of the logits' norm, and a token at most
        # 5% of the logit scale under the reference's argmax
        check(float(err.max()) <= (0.06 if dtype == "bfloat16" else 1e-3),
              f"parallel-hybrid-LM logits are {err.max():.4f} of their norm "
              f"off the reference")
        check(float(gap.max()) <= 0.05 * scale,
              f"a parallel-hybrid-LM token trails the reference's argmax by "
              f"{gap.max():.4f} (logit scale {scale:.2f})")
        fell = {n: counter_delta(c0, n)
                for n in PARALLEL_HYBRID_FALLBACK_COUNTERS}
        check(not any(fell.values()), f"a new kernel fell back: {fell}")
        z = engine.decodez()
        cache = z["cache"]
        check(cache.get("kind") == "hybrid" and all(
            cache.get(k, 0) > 0 for k in (
                "kv_pool_bytes", "recurrent_state_bytes", "kv_live_tokens")),
              f"/decodez does not show the two kinds of state: {cache}")
        return {"tokens_checked": int(toks.size),
                "tokens_exact": int((gap == 0).sum()),
                "logit_err_max": float(err.max()),
                "worst_logit_gap": float(gap.max()), "logit_scale": scale,
                "steps": z["steps"], "cache": cache, "fallbacks": fell}
    finally:
        engine.close()


def phase_window_expert_lm(vocab=8192, hidden=512, heads=14, kv_heads=2,
                           head_dim=128, expert_ffn=256, experts=16, top_k=4,
                           layers=4, window=256, max_seq_len=1024,
                           max_slots=4, block_tokens=16, prefill_bucket=512,
                           prompt_len=400, new_tokens=40, dtype="bfloat16"):
    """``decode.smallthinker.SmallThinkerLM`` (SmallThinker's layer at its
    head width and its group of seven, one period: a position-free full
    layer and three window layers with rotary keys, ReLU-gated experts routed
    from the layer's pre-attention input) through ``DecodeEngine``: one
    stream whose prompt is longer than the window and whose ring wraps as it
    decodes, its logits at every generated position held against the
    benchmark's plain reference (given the program's expert choices); no
    kernel fell back (on the chip none is interpreted); ``/decodez`` shows
    the pool and the rings, and no recurrent rows."""
    import jax.numpy as jnp
    from benchmark.reference import smallthinker as reference
    from paddle_tpu.decode import DecodeEngine, SamplingParams
    from paddle_tpu.decode.smallthinker import (SmallThinkerConfig,
                                                SmallThinkerLM)

    layout = (0, 1, 1, 1) * (layers // 4)
    cfg = SmallThinkerConfig(
        vocab_size=vocab, hidden_size=hidden, num_hidden_layers=layers,
        num_attention_heads=heads, num_key_value_heads=kv_heads,
        head_dim=head_dim, moe_ffn_hidden_size=expert_ffn,
        moe_num_primary_experts=experts,
        moe_num_active_primary_experts=top_k, rope_layout=layout,
        sliding_window_layout=layout, sliding_window_size=window,
        max_seq_len=max_seq_len, dtype=dtype)
    model = SmallThinkerLM(cfg)
    params = model.init_params(seed=7)
    c0 = counters()
    engine = DecodeEngine(model, params, name="window_expert",
                          max_slots=max_slots, block_tokens=block_tokens,
                          prefill_buckets=[prefill_bucket],
                          capture_logits=True, cache_dtype=dtype,
                          prefix_cache=False, overcommit=False)
    try:
        prompt = np.random.RandomState(0).randint(
            0, vocab, (prompt_len,)).astype("int32")
        handle = engine.submit(prompt,
                               SamplingParams(max_new_tokens=new_tokens))
        result = handle.result(timeout=900.0)
        toks = np.asarray(result["tokens"], np.int32)
        check(toks.size == new_tokens and result.get("finish") == "length",
              f"the window-expert stream ended early: {result}")
        seq = np.concatenate([prompt, toks[:-1]])
        weights = {k: jnp.asarray(v) for k, v in params.items()}
        at = np.arange(prompt_len - 1, seq.size)
        want, _, _ = reference.forward(weights, cfg.to_dict(), seq, seq.size,
                                       at)
        want = np.asarray(want)
        got = np.stack(handle.logits).astype(np.float32)
        err = np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))
        scale = float(np.abs(want).max())
        gap = want.max(-1) - np.take_along_axis(want, toks[:, None], 1)[:, 0]
        # bf16 activations through four layers against float32 at the highest
        # precision, the reference routing on its own (a near tie turned by
        # bf16 moves one position's logits by more than rounding does): the
        # median a few percent of the logits' norm, and a token at most 5% of
        # the logit scale under the reference's argmax
        check(float(np.median(err)) <= (0.06 if dtype == "bfloat16"
                                        else 1e-3),
              f"window-expert-LM logits are {np.median(err):.4f} of their "
              f"norm off the reference (median)")
        check(float(gap.max()) <= 0.05 * scale,
              f"a window-expert-LM token trails the reference's argmax by "
              f"{gap.max():.4f} (logit scale {scale:.2f})")
        fell = {n: counter_delta(c0, n)
                for n in WINDOW_EXPERT_FALLBACK_COUNTERS
                + ("attn.gqa_decode_fallbacks",)}
        check(not any(fell.values()), f"a new kernel fell back: {fell}")
        z = engine.decodez()
        cache = z["cache"]
        check(cache.get("kind") == "hybrid" and all(
            cache.get(k, 0) > 0 for k in (
                "kv_pool_bytes", "window_state_bytes", "kv_live_tokens"))
              and "recurrent_state_bytes" not in cache,
              f"/decodez does not show the pool and the rings alone: {cache}")
        check(z["step_ring_rows_live"] == (new_tokens - 1) * min(
            window, prompt_len), f"the rings' live rows: {z}")
        return {"tokens_checked": int(toks.size),
                "tokens_exact": int((gap == 0).sum()),
                "logit_err_max": float(err.max()),
                "logit_err_median": float(np.median(err)),
                "worst_logit_gap": float(gap.max()), "logit_scale": scale,
                "steps": z["steps"], "cache": cache, "fallbacks": fell}
    finally:
        engine.close()


def phase_conv_expert_lm(vocab=8192, hidden=512, heads=8, kv_heads=4,
                         head_dim=64, ffn=1024, expert_ffn=256, experts=16,
                         top_k=4, max_seq_len=1024, max_slots=4,
                         block_tokens=16, prefill_bucket=512, prompt_len=400,
                         new_tokens=24, dtype="bfloat16"):
    """``decode.lfm2.LFM2LM`` (LFM2's layers at its head width of 64: one
    dense short-convolution layer, then one period of an attention layer with
    per-head q/k norms and three short-convolution layers, SwiGLU experts
    behind a sigmoid router with a selection bias) through ``DecodeEngine``:
    one stream prefilled into a padded rung and decoded through the pool and
    the convolution tails, its logits at every generated position held
    against the benchmark's plain reference; no kernel fell back (on the
    chip none is interpreted); ``/decodez`` shows the pool and the tails, and
    no rings."""
    import jax.numpy as jnp
    from benchmark.reference import lfm2_moe as reference
    from paddle_tpu.decode import DecodeEngine, SamplingParams
    from paddle_tpu.decode.lfm2 import LFM2Config, LFM2LM

    raw = dict(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=ffn,
        moe_intermediate_size=expert_ffn, num_hidden_layers=5,
        num_attention_heads=heads, num_key_value_heads=kv_heads,
        head_dim=head_dim, num_dense_layers=1, num_experts=experts,
        num_experts_per_tok=top_k, norm_topk_prob=True, use_expert_bias=True,
        routed_scaling_factor=1.0, norm_eps=1e-5, conv_L_cache=3,
        conv_bias=False,
        layer_types=["conv", "full_attention", "conv", "conv", "conv"],
        rope_parameters={"rope_theta": 1e6})
    cfg = LFM2Config.from_dict({**raw, "max_seq_len": max_seq_len,
                                "dtype": dtype})
    model = LFM2LM(cfg)
    params = model.init_params(seed=7)
    c0 = counters()
    engine = DecodeEngine(model, params, name="conv_expert",
                          max_slots=max_slots, block_tokens=block_tokens,
                          prefill_buckets=[prefill_bucket],
                          capture_logits=True, cache_dtype=dtype,
                          prefix_cache=False, overcommit=False)
    try:
        prompt = np.random.RandomState(0).randint(
            0, vocab, (prompt_len,)).astype("int32")
        handle = engine.submit(prompt,
                               SamplingParams(max_new_tokens=new_tokens))
        result = handle.result(timeout=900.0)
        toks = np.asarray(result["tokens"], np.int32)
        check(toks.size == new_tokens and result.get("finish") == "length",
              f"the conv-expert stream ended early: {result}")
        seq = np.concatenate([prompt, toks[:-1]])
        weights = {k: jnp.asarray(v) for k, v in params.items()}
        at = np.arange(prompt_len - 1, seq.size)
        want, _, _ = reference.forward(weights, raw, seq, seq.size, at)
        want = np.asarray(want)
        got = np.stack(handle.logits).astype(np.float32)
        err = np.sqrt(((got - want) ** 2).sum(-1) / (want ** 2).sum(-1))
        scale = float(np.abs(want).max())
        gap = want.max(-1) - np.take_along_axis(want, toks[:, None], 1)[:, 0]
        # bf16 activations through five layers against float32 at the highest
        # precision, the reference routing on its own: the median a few
        # percent of the logits' norm, and a token at most 5% of the logit
        # scale under the reference's argmax
        check(float(np.median(err)) <= (0.06 if dtype == "bfloat16"
                                        else 1e-3),
              f"conv-expert-LM logits are {np.median(err):.4f} of their "
              f"norm off the reference (median)")
        check(float(gap.max()) <= 0.05 * scale,
              f"a conv-expert-LM token trails the reference's argmax by "
              f"{gap.max():.4f} (logit scale {scale:.2f})")
        fell = {n: counter_delta(c0, n) for n in CONV_EXPERT_FALLBACK_COUNTERS}
        check(not any(fell.values()), f"a new kernel fell back: {fell}")
        z = engine.decodez()
        cache = z["cache"]
        check(cache.get("kind") == "hybrid" and all(
            cache.get(k, 0) > 0 for k in (
                "kv_pool_bytes", "recurrent_state_bytes", "kv_live_tokens"))
              and "window_state_bytes" not in cache,
              f"/decodez does not show the pool and the tails alone: {cache}")
        return {"tokens_checked": int(toks.size),
                "tokens_exact": int((gap == 0).sum()),
                "logit_err_max": float(err.max()),
                "logit_err_median": float(np.median(err)),
                "worst_logit_gap": float(gap.max()), "logit_scale": scale,
                "steps": z["steps"], "cache": cache, "fallbacks": fell}
    finally:
        engine.close()


# ---------------------------------------------------------------------------
# phase 3f: the prefill form of the grouped expert kernel against its fallback
# ---------------------------------------------------------------------------

def phase_expert_walk(on_chip=True, tokens=2048, top_k=6, experts=64,
                      hidden=2048, expert_ffn=1408, tol=1e-2):
    """``kernels/moe.py grouped_glu`` on a prefill's plan (128-row tiles: an
    expert a grid step, its rows copied in and out by the kernel) against
    ``grouped_glu_xla`` — three ``lax.ragged_dot``, called directly, so no
    fallback is counted — at DeepSeek-V2-Lite's expert shapes: the plan a
    2,048-token prompt's top-6 of 64 makes, and one with every assignment on
    ONE expert.  The comparison a kernel's first benchmark run waits for:
    interpret mode cannot see what the TPU's compiler does (PERF.md §6, PR
    44).  Returns the largest difference of each plan over the reference's
    scale, and which walk the lowerings took."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels import moe

    tile = moe.row_tile(tokens, jnp.bfloat16)
    check(tile == 128, f"{tokens} tokens plan {tile}-row tiles, not 128")
    keys = jax.random.split(jax.random.PRNGKey(45), 5)
    wg, wu = (jax.random.normal(k, (experts, hidden, expert_ffn),
                                jnp.bfloat16) * 0.03 for k in keys[:2])
    wd = jax.random.normal(keys[2], (experts, expert_ffn, hidden),
                           jnp.bfloat16) * 0.03
    x = jax.random.normal(keys[3], (tokens, hidden), jnp.bfloat16)
    ids, _ = moe.route_topk(
        jax.random.normal(keys[4], (tokens, experts), jnp.float32), top_k)
    valid = jnp.ones((tokens,), bool)
    x_pad = jnp.concatenate([x, jnp.zeros((1, hidden), x.dtype)])

    kernel = jax.jit(lambda rows, plan: moe.grouped_glu(
        rows, wg, wu, wd, plan, tile))
    fallback = jax.jit(lambda rows, plan: moe.grouped_glu_xla(
        rows, wg, wu, wd, plan))
    before, out = counters(), {}
    for name, chosen in (("prompt", ids), ("one_expert", ids * 0 + 3)):
        plan = moe.plan_groups(chosen, valid, experts, tile)
        rows = x_pad[plan.row_token]
        if on_chip:
            text = kernel.lower(rows, plan).compile().as_text()
            check(MOSAIC_CALL in text and "moe_grouped_swiglu" in text,
                  "no Mosaic call named moe_grouped_swiglu in the program")
        live = int(np.sum(np.asarray(plan.padded_sizes)))
        got = np.asarray(kernel(rows, plan)[:live], np.float32)
        want = np.asarray(fallback(rows, plan)[:live], np.float32)
        scale = float(np.abs(want).max())
        out[name] = {"rows": live, "assignments": int(plan.load[0]),
                     "experts_touched": int(plan.load[1]),
                     "max_diff": float(np.abs(got - want).max()),
                     "scale": scale}
        check(np.isfinite(got).all() and scale > 0
              and out[name]["max_diff"] <= tol * scale,
              f"the expert walk differs from the fallback by "
              f"{out[name]['max_diff']:.4g} of a scale of {scale:.4g} on "
              f"the {name} plan")
    out["walks"] = {w: counter_delta(before, f"moe.grouped_swiglu_{w}")
                    for w in ("expert_walks", "tile_walks", "fallbacks")}
    check(out["walks"]["expert_walks"] > 0 and not out["walks"]["tile_walks"]
          and not out["walks"]["fallbacks"],
          f"a 128-row plan was not walked by expert: {out['walks']}")
    return out


# ---------------------------------------------------------------------------
# phase 3g: the experts' plan against a stable sort, at the four cells' shapes
# ---------------------------------------------------------------------------

def sorted_plan(ids, valid, experts, tile, first, rows):
    """The six fields of ``kernels/moe.py GroupPlan`` by a NumPy stable sort:
    the valid assignments to experts ``first … first + experts − 1`` in expert
    order, each expert's run padded to the tile."""
    T, K = ids.shape
    local = ids.astype(np.int64) - first
    key = np.where(valid[:, None] & (local >= 0) & (local < experts), local,
                   experts).reshape(-1)
    order = np.argsort(key, kind="stable")
    counts = np.bincount(key, minlength=experts + 1)[:experts]
    padded = -(-counts // tile) * tile
    pend = np.cumsum(padded)
    row_token, row_of, at = np.full(rows, T), np.full(T * K, rows), 0
    for e in range(experts):
        mine = order[at:at + counts[e]]
        run = pend[e] - padded[e] + np.arange(counts[e])
        row_token[run], row_of[mine] = mine // K, run
        at += counts[e]
    tiles = np.repeat(np.arange(experts), padded // tile)
    tiles = np.concatenate([tiles, np.full(
        rows // tile - len(tiles), tiles[-1] if len(tiles) else experts - 1)])
    return [a.astype(np.int32) for a in (
        row_token, row_of.reshape(T, K), tiles,
        np.asarray([max(pend[-1] // tile, 1)]),
        padded, np.asarray([counts.sum(), (counts > 0).sum(), counts.max()]))]


# (tokens, choices a token, the router's width, first held expert): a decode
# step's 64 slots and the 2,048 / 8,192 / 16,384 rungs of the four expert
# cells, whose stacks hold 64 experts a layer
EXPERT_PLAN_SHAPES = (
    ("a_step_top4", 64, 4, 64, 0), ("a_step_top6", 64, 6, 64, 0),
    ("a_step_top8_share", 64, 8, 256, 0),
    ("2048_top6", 2048, 6, 64, 0), ("8192_top6", 8192, 6, 64, 0),
    ("8192_top4", 8192, 4, 64, 0), ("16384_top8_share", 16384, 8, 256, 0))


def phase_expert_plan(shapes=EXPERT_PLAN_SHAPES, experts=64, hidden=2048,
                      expert_ffn=1408):
    """``kernels/moe.py plan_groups`` — the plan by counting — against
    :func:`sorted_plan` in all six fields, and ``routed_experts`` against
    ``planned_experts`` under the sorted plan, equal to the bit, at the expert
    cells' real shapes: uniform choices with a padded tail, and every
    assignment on ONE expert.  On the chip because the TPU's compiler has
    returned a wrong result for correct XLA code before (PERF.md §6, PR 44)
    and a plan that is off by one row is a wrong token, not a slow one."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels import moe

    keys = jax.random.split(jax.random.PRNGKey(52), 3)
    wg, wu = (jax.random.normal(k, (experts, hidden, expert_ffn),
                                jnp.bfloat16) * 0.03 for k in keys[:2])
    wd = jax.random.normal(keys[2], (experts, expert_ffn, hidden),
                           jnp.bfloat16) * 0.03
    # the stacks are ARGUMENTS: closed over, their 1.1 GB would be constants
    # of every shape's executables
    stacks = (wg, wu, wd)
    routed = jax.jit(lambda x, ids, w, valid, first, stacks:
                     moe.routed_experts(x, ids, w, valid, *stacks,
                                        first=first)[0])
    planned = jax.jit(lambda x, w, plan, stacks, tile: moe.planned_experts(
        x, w, moe.GroupPlan(*plan), *stacks, tile), static_argnums=4)
    rng, out = np.random.RandomState(52), {}
    for name, tokens, top_k, wide, first in shapes:
        tile = moe.row_tile(tokens, jnp.bfloat16)
        rows = moe.plan_rows(tokens, top_k, experts, tile)
        x = jnp.asarray(rng.randn(tokens, hidden), jnp.bfloat16)
        w = jnp.asarray(rng.rand(tokens, top_k), jnp.float32)
        valid = np.arange(tokens) < tokens - tokens // 7
        uniform = np.argsort(rng.rand(tokens, wide), axis=1)[:, :top_k]
        for case, ids in (("uniform", uniform),
                          ("one_expert", np.full_like(uniform, first + 3))):
            ids = ids.astype(np.int32)
            got = moe.plan_groups(jnp.asarray(ids), jnp.asarray(valid),
                                  experts, tile, jnp.int32(first))
            want = sorted_plan(ids, valid, experts, tile, first, rows)
            differ = [f for f, a, b in zip(moe.GroupPlan._fields, got, want)
                      if a.dtype != jnp.int32 or not np.array_equal(
                          np.asarray(a), b.reshape(a.shape))]
            check(not differ, f"the plan of {name} / {case} is not the "
                              f"stable sort's in {differ}")
            y = np.asarray(routed(x, jnp.asarray(ids), w, jnp.asarray(valid),
                                  jnp.int32(first), stacks))
            y_ref = np.asarray(planned(
                x, w, tuple(jnp.asarray(a) for a in want), stacks, tile))
            check(np.isfinite(y).all() and np.array_equal(y, y_ref),
                  f"routed_experts of {name} / {case} is not what the sorted "
                  f"plan gives: {np.abs(y - y_ref).max():.4g} apart")
            out[f"{name}/{case}"] = {
                "rows": rows, "assignments": int(want[5][0]),
                "experts_touched": int(want[5][1]),
                "output_scale": float(np.abs(y).max())}
    return out


def phase_group_flash(on_chip=True, tokens=12288, window=4096, block=1024,
                      layouts=(("group_of_7", 28, 4, 128),
                               ("pairs_of_64", 32, 8, 64)), tol=2e-2):
    """``kernels/gqa.py group_prefill_attention`` against
    ``prefill_attention_xla`` (dense float32 scores, called directly a block
    of queries at a time, so no fallback is counted) at the serve cells' real
    shapes: SmallThinker's group of seven 128-wide heads with and without
    its window, LFM2's pairs of 64-wide K/V heads, each at the longest rung
    with a real length short of it.  Every real row within ``tol`` of its
    own scale in the reference (bf16 probabilities into the value product
    read 0.6–1.0e-2 on the v5e), query tiles of padding exactly zero.
    Interpret mode cannot see what the TPU's compiler does round a kernel
    (PERF.md §6, PR 44).  Returns each case's largest difference and its
    plan of tiles."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.kernels import gqa

    length = int(0.87 * tokens)
    dense = jax.jit(gqa.prefill_attention_xla, static_argnums=(2, 3, 4))
    before, out = counters(), {}
    for layout, nh, n_kv, dh in layouts:
        kq, kr = jax.random.split(jax.random.PRNGKey(46 + dh))
        q = jax.random.normal(kq, (tokens, nh, dh), jnp.bfloat16)
        rows = jax.random.normal(kr, (tokens, 2 * n_kv * dh), jnp.bfloat16)
        for w in ((window, None) if dh == 128 else (None,)):
            name = f"{layout}_{'window' if w else 'full'}"
            kernel = jax.jit(lambda q, r, n, w=w: gqa.group_prefill_attention(
                q, r, n_kv, w, n))
            if on_chip:
                text = kernel.lower(q, rows, jnp.int32(length)
                                    ).compile().as_text()
                check(MOSAIC_CALL in text and "flash_fwd" in text,
                      f"no Mosaic flash forward in {name}'s program")
            got = np.asarray(kernel(q, rows, jnp.int32(length)))
            want = np.concatenate([
                np.asarray(dense(q[t:t + block], rows[:t + block], n_kv, w,
                                 t)) for t in range(0, length, block)]
            )[:length]
            bq, bk, n_kw = gqa.flash_plan(tokens, w)
            skipped = -(-length // bq) * bq
            # a row far into the prompt is a mean of thousands of values: a
            # difference is held against the ROW's own scale, so that a key
            # tile left out of a late row cannot hide under an early row's
            diff = np.abs(got[:length] - want).max(axis=(1, 2)) \
                / np.abs(want).max(axis=(1, 2))
            out[name] = {"plan": [bq, bk, n_kw],
                         "max_row_diff": float(diff.max()),
                         "pad_rows_zero": not got[skipped:].any()}
            check(np.isfinite(got).all() and out[name]["max_row_diff"] <= tol,
                  f"{name}: a row of the flash forward differs from dense "
                  f"attention by {out[name]['max_row_diff']:.4g} of its scale")
            check(out[name]["pad_rows_zero"],
                  f"{name}: a query tile of padding is not zeros")
    out["fallbacks"] = counter_delta(before,
                                     "attn.gqa_window_prefill_fallbacks")
    check(not out["fallbacks"], "the group flash forward fell back to XLA")
    return out


def ms_a_call_between(run, calls) -> float:
    """ms a call of ``run(n)`` — N calls inside ONE program, blocking — as
    the difference of two lengths ``calls`` (the best of two timings each),
    because a dispatch costs 0.6 ms (PR 52)."""
    run(1)
    best = {}
    for n in calls * 2:
        t0 = time.perf_counter()
        run(n)
        best[n] = min(best.get(n, np.inf), time.perf_counter() - t0)
    lo, hi = calls
    return 1e3 * (best[hi] - best[lo]) / (hi - lo)


# ---------------------------------------------------------------------------
# phase 3i: the output projection that keeps the loss's log-sum-exp
# ---------------------------------------------------------------------------

def phase_proj_xent(on_chip=True, batch=96, seq=256, d=512, vocab=37000,
                    tiles=((2048, 512), (1024, 1024), (1024, 512),
                           (2048, 1024), (2048, 2048), (512, 1024),
                           (4096, 512), (1024, 2048)),
                    calls=(4, 24), lse_rtol=1e-6):
    """``kernels/xent.py proj_xent_fwd`` against ``proj_xent_xla``
    (``jnp.matmul`` + ``logsumexp``, called directly, so no fallback is
    counted) at the train cells' head — 96 sequences of 256 float32 rows, a
    bf16 weight — compared ON the device: the logits to the product's last
    bit (the two round the rows to bf16 alike and sum 512 products in their
    own orders: at most the accumulation's few ulps of the LARGEST logit),
    each row's log-sum-exp within ``lse_rtol``.  Interpret mode cannot see
    what the TPU's compiler does round a kernel (PERF.md §6, PR 44).  Then
    the kernel alone, ms a call for each of ``tiles`` (row block, vocabulary
    tile; the first, the module's own, is compared) beside XLA's two
    operations: N calls inside
    ONE program (a loop whose step perturbs the rows, so nothing is hoisted,
    and keeps both outputs alive), the difference of two lengths, because a
    dispatch costs 0.6 ms (PR 52).  The rows reach the kernel as the op
    hands them over, rounded to bf16 by XLA."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from paddle_tpu.kernels import xent

    rows = batch * seq
    kx, kw = jax.random.split(jax.random.PRNGKey(60))
    x = jax.random.normal(kx, (rows, d), jnp.float32)
    w = (jax.random.normal(kw, (d, vocab), jnp.float32)
         * d ** -0.5).astype(jnp.bfloat16)

    def kernel_of(tm, tn):
        return lambda x, w: xent.proj_xent_fwd(
            x.astype(jnp.bfloat16), w, seq=seq, tm=tm, tn=tn,
            logits_dtype=jnp.float32)

    # compared as [rows, vocab]; TIMED as the views the kernel hands on (a
    # sequence's positions along the lanes: row-major they cost a transpose
    # of the 3.64 GB, 11 ms, which no program that reads them pays)
    kernel = jax.jit(lambda x, w: tuple(
        o.reshape(rows, -1) for o in kernel_of(*tiles[0])(x, w)))
    if on_chip:
        text = kernel.lower(x, w).compile().as_text()
        check(MOSAIC_CALL in text and "proj_xent_fwd" in text,
              "no Mosaic call named proj_xent_fwd in the program")

    @jax.jit
    def compare(got, want):
        (a, la), (b, lb) = got, want
        diff = jnp.abs(a - b)
        return {"logits_max_diff": diff.max(), "scale": jnp.abs(b).max(),
                "logits_differing": (diff > 0).sum(),
                "lse_max_rel": (jnp.abs(la - lb) / jnp.abs(lb)).max(),
                "lse_mean": lb.mean()}

    out = {k: float(v) for k, v in compare(
        kernel(x, w), jax.jit(xent.proj_xent_xla)(x, w)).items()}
    ulp = out["scale"] * 2.0 ** -23
    out["logits_max_diff_ulps_of_scale"] = out["logits_max_diff"] / ulp
    check(np.isfinite(out["logits_max_diff"]) and out["scale"] > 0
          and out["logits_max_diff"] <= (4 * ulp if on_chip
                                         else 2e-2 * out["scale"]),
          f"the kernel's logits differ from the product by "
          f"{out['logits_max_diff']:.4g} at a scale of {out['scale']:.4g}")
    check(out["lse_max_rel"] <= (lse_rtol if on_chip else 1e-2),
          f"a row's log-sum-exp differs by {out['lse_max_rel']:.3g} of "
          f"itself")

    def ms_a_call(fn):
        @jax.jit
        def loop(x, w, n):
            def step(_, acc):
                logits, lse = lax.optimization_barrier(fn(x + acc * 1e-30, w))
                return acc + lse.reshape(-1)[0] + logits.reshape(-1)[0]
            return lax.fori_loop(0, n, step, jnp.float32(0))

        return ms_a_call_between(
            lambda n: loop(x, w, n).block_until_ready(), calls)

    table = {"xla: matmul + logsumexp": ms_a_call(xent.proj_xent_xla),
             "xla: matmul alone": ms_a_call(lambda x, w: (
                 jnp.matmul(x, w), jnp.zeros((rows, 1), jnp.float32)))}
    for tm, tn in tiles:
        table[f"kernel {tm} x {tn}"] = ms_a_call(kernel_of(tm, tn))
    out["ms_a_call"] = {k: round(v, 3) for k, v in table.items()}
    # what the device was handed for each call, by the shapes
    out["gflop_a_call"] = 2e-9 * rows * d * vocab
    out["logits_gb"] = 4e-9 * rows * vocab
    return out


# ---------------------------------------------------------------------------
# phase 3j: the latent decode walk alone, at the three latent cells' shapes
# ---------------------------------------------------------------------------

def phase_latent_walk(on_chip=True, slots=64, heads=(32, 16), rank=512,
                      rope=64, block=16, layers=2,
                      pools=(("7k", 1088, 7268), ("2k", 512, 2300)),
                      calls=(8, 40), tol=2e-2):
    """``kernels/mla.py decode_attention`` (``mla_paged_decode_attn``: one
    grid step a slot, live blocks only, the next fetch in flight) alone, at
    the three latent cells' shapes: ``slots`` absorbed queries of 32
    (``kl48b_longdoc_sat``, ``xg29b_doc_sat``) and 16 (``dsv2l_doc_sat``)
    heads against a pool of 640-lane bf16 rows; a pool is (name, table
    blocks, mean live tokens a slot), the contexts log-normal about the mean
    as the cells' prompts are, each slot's blocks scattered over the pool.
    First against ``decode_attention_xla`` ON the device (every head within
    ``tol`` of the result's scale: the kernel rounds its weights to the
    pool's bf16 for the value product, the lowering keeps float32).  Then ms
    a call (``ms_a_call_between``, the layers in turn) as it is and with the
    copies taken out (the schedule's ``start`` / ``wait`` made empty: what
    the scalar core and the products cost with every fetch hidden), beside
    the bytes' time at the HBM peak; a tree whose kernel has no schedule to
    empty (before PR 62) is timed as it is.  The table in the kernel's
    docstring is this phase's."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from paddle_tpu.kernels import diffattn, mla

    W = mla.row_width(rank, rope)
    rng = np.random.RandomState(62)
    blocks = 1 + slots * max(mb for _, mb, _ in pools)
    pool = jax.random.normal(jax.random.PRNGKey(62),
                             (layers, blocks, block, W), jnp.bfloat16)
    scale = (rank // 4 + rope) ** -0.5

    def case(H, mb, mean):
        cl = np.clip(rng.lognormal(np.log(mean) - 0.18, 0.6, slots),
                     1, mb * block).astype(np.int32)
        bt = (1 + rng.permutation(slots * mb)).reshape(slots, mb)
        q = np.zeros((slots, H, W), np.float32)
        q[..., :rank + rope] = rng.randn(slots, H, rank + rope) * 0.5
        return (jnp.asarray(q, jnp.bfloat16), jnp.asarray(bt, jnp.int32),
                jnp.asarray(cl))

    def walk(q, pool, bt, cl, layer):
        return mla.decode_attention(q, pool, bt, cl, layer, rank, scale)

    kernel = jax.jit(walk)
    lowering = jax.jit(lambda q, pool, bt, cl: mla.decode_attention_xla(
        q, pool, bt, cl, 1, rank, scale))

    def ms_a_call(q, bt, cl):
        @jax.jit
        def loop(q, pool, bt, cl, n):
            def step(i, acc):
                out = walk(q + (acc * 1e-30).astype(q.dtype), pool, bt, cl,
                           i % layers)
                return acc + out[0, 0, 0]
            return lax.fori_loop(0, n, step, jnp.float32(0))

        return ms_a_call_between(
            lambda n: loop(q, pool, bt, cl, n).block_until_ready(), calls)

    def copies_taken_out(*args, **kwargs):
        live_blocks, *_ = schedule(*args, **kwargs)
        return (live_blocks,) + (lambda *a: None,) * 3

    schedule = getattr(diffattn, "walk_schedule", None)
    before, out = counters(), {}
    for H in heads:
        for name, mb, mean in pools:
            q, bt, cl = case(H, mb, mean)
            if on_chip:
                text = kernel.lower(q, pool, bt, cl, 1).compile().as_text()
                check(MOSAIC_CALL in text and "mla_paged_decode_attn" in text,
                      "no Mosaic call named mla_paged_decode_attn")
            got = np.asarray(kernel(q, pool, bt, cl, 1))
            want = np.asarray(lowering(q, pool, bt, cl))
            live = int(np.asarray(cl).sum())
            row = {"live_tokens": live, "max_diff": float(
                np.abs(got - want).max()), "scale": float(np.abs(want).max())}
            check(np.isfinite(got).all() and row["scale"] > 0
                  and row["max_diff"] <= tol * row["scale"],
                  f"the walk differs from its lowering by "
                  f"{row['max_diff']:.4g} of a scale of {row['scale']:.4g} "
                  f"at {H} heads over the {name} pool")
            row["ms_a_call"] = round(ms_a_call(q, bt, cl), 4)
            if schedule is not None:
                diffattn.walk_schedule = copies_taken_out
                mla._walk_call.clear_cache()
                try:
                    row["ms_copies_taken_out"] = round(
                        ms_a_call(q, bt, cl), 4)
                finally:
                    diffattn.walk_schedule = schedule
                    mla._walk_call.clear_cache()
            # the roofline's count: rank + rope numbers a live token
            row["ms_at_hbm_peak"] = round(
                1e3 * live * (rank + rope) * 2 / 819e9, 4)
            out[f"{H}_heads_{name}"] = row
    out["fallbacks"] = counter_delta(before, "mla.decode_attn_fallbacks")
    check(out["fallbacks"] == 0, "the latent walk fell back to XLA")
    return out


# ---------------------------------------------------------------------------
# phase 4: four chips
# ---------------------------------------------------------------------------

def phase_four_chip(place, devices, ref_first_loss, batch=32, max_len=256,
                    vocab=32000, d_model=512, n_head=8, d_ffn=2048,
                    n_layer=6, dtype="bfloat16", dropout=0.1, steps=3):
    """The trainer phase's program (same seed, same global batch) through
    ``ParallelExecutor`` on a dp=2 x mp=2 mesh, then one ZeRO step on
    dp=4."""
    import paddle_tpu as fluid
    from paddle_tpu.core.executor import Scope
    from paddle_tpu.models import transformer
    from paddle_tpu.parallel import (BuildStrategy, ParallelExecutor,
                                     ReduceStrategy)

    devices = list(devices)[:4]
    check(len(devices) == 4, "four_chip needs four devices")
    feed = transformer_feed(batch, max_len, vocab)

    def build():
        return transformer_program(vocab, max_len, d_model, n_head, d_ffn,
                                   n_layer, dtype, dropout, warmup_steps=8)

    prog, startup, (_, loss, _) = build()
    scope = Scope()
    fluid.Executor(place).run(startup, scope=scope)
    pe = ParallelExecutor(
        loss_name=loss.name, main_program=prog, scope=scope, places=devices,
        build_strategy=BuildStrategy(
            mesh_shape={"dp": 2, "mp": 2},
            sharding_rules=transformer.tp_sharding_rules()))
    losses = [float(pe.run(feed=feed, fetch_list=[loss.name])[0])
              for _ in range(steps)]
    check(all(np.isfinite(losses)), f"non-finite mesh loss {losses}")
    # same seed, same global batch: the sharded step computes the same
    # function; bf16 activations and the mp partial sums reassociate
    rel = abs(losses[0] - ref_first_loss) / abs(ref_first_loss)
    check(rel <= 1e-2, f"first-step loss {losses[0]:.5f} on the mesh vs "
                       f"{ref_first_loss:.5f} on one chip (rel {rel:.2e})")
    w = scope.find_var("enc.0.ffn.fc1.w")
    check("mp" in tuple(w.sharding.spec), f"fc1 sharding {w.sharding.spec}")
    shard_devs = {s.device for s in w.addressable_shards}
    check(len(shard_devs) == 4,
          f"fc1 shards sit on {len(shard_devs)} device(s), not 4")
    in_use = {str(d): (d.memory_stats() or {}).get("bytes_in_use")
              for d in devices}
    if all(v is not None for v in in_use.values()):   # CPU reports none
        check(all(v > 0 for v in in_use.values()),
              f"a device holds nothing after the steps: {in_use}")
    pe.close()
    del pe, scope, w
    gc.collect()

    prog, startup, (_, loss, _) = build()
    scope = Scope()
    fluid.Executor(place).run(startup, scope=scope)
    pe = ParallelExecutor(
        loss_name=loss.name, main_program=prog, scope=scope, places=devices,
        build_strategy=BuildStrategy(
            mesh_shape={"dp": 4}, reduce_strategy=ReduceStrategy.kReduce))
    zero_loss = float(pe.run(feed=feed, fetch_list=[loss.name])[0])
    check(np.isfinite(zero_loss), f"non-finite ZeRO loss {zero_loss}")
    w = scope.find_var("enc.0.ffn.fc1.w")
    check(tuple(w.sharding.spec)[:1] == ("dp",)
          and len({s.device for s in w.addressable_shards}) == 4,
          f"ZeRO state not dp-sharded over 4 devices: {w.sharding.spec}")
    pe.close()
    return {"mesh_first_loss": losses[0], "one_chip_first_loss":
            ref_first_loss, "rel_diff": rel, "mesh_losses": losses,
            "zero_loss": zero_loss, "bytes_in_use": in_use,
            "device_coords": [getattr(d, "coords", None) for d in devices]}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

LOG = None  # the CompileLog of this process, set by main()/the tests


def run_phase(report, name, fn, *args, **kwargs):
    """Run one phase; a failure is recorded and the run goes on, so one
    report names every phase that failed."""
    t0, m0 = time.perf_counter(), LOG.mark()
    try:
        facts = fn(*args, **kwargs)
        status = "ok"
    except Exception as e:  # recorded with its traceback; run() fails
        traceback.print_exc()
        facts, status = {"error": repr(e)[:600]}, "failed"
    m1 = LOG.mark()
    report["phases"][name] = {
        "status": status, "wall_s": round(time.perf_counter() - t0, 2),
        "compile_s": round(m1[1] - m0[1], 2), "compiles": m1[0] - m0[0],
        "cache_hits": m1[2] - m0[2], **facts}
    gc.collect()
    return facts if status == "ok" else None


def result_line(report) -> str:
    """The last line of stdout: exactly ``ok`` and the device as JAX
    reports it — the detail lives in the report line before it."""
    d = report["device"]
    return json.dumps({"ok": bool(report["ok"]),
                       "device": {"platform": str(d["platform"]),
                                  "kind": str(d["kind"]),
                                  "count": int(d["count"])}})


def main() -> int:
    import jax

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX found platform {backend!r}, not a TPU — "
              "this script only runs on the chip", file=sys.stderr)
        return 2

    import jaxlib
    import paddle_tpu as fluid
    from paddle_tpu import platform
    from paddle_tpu.core import compile_cache

    global LOG
    LOG = CompileLog()
    devices = jax.devices()
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    peaks = platform.platform_peaks(devices[0])
    report = {
        "ok": False,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
        "env": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": libtpu_version,
                "python": sys.version.split()[0]},
        "compile_cache_dir": compile_cache.wire_jax_cache(),
        "peaks": peaks,
        "phases": {},
    }
    print("chip_smoke:", json.dumps({k: report[k] for k in
                                     ("device", "env", "compile_cache_dir")}),
          flush=True)
    failures = []
    if not peaks["flops"] or peaks["nominal"]:
        failures.append(f"no row in platform.PLATFORM_PEAKS for "
                        f"device_kind {devices[0].device_kind!r}")

    place = fluid.TPUPlace()
    trainer = run_phase(report, "trainer", phase_trainer, place)
    run_phase(report, "kernels", phase_kernels, place)
    run_phase(report, "decode_server", phase_decode_server)
    run_phase(report, "latent_lm", phase_latent_lm)
    run_phase(report, "hybrid_lm", phase_hybrid_lm)
    run_phase(report, "parallel_hybrid_lm", phase_parallel_hybrid_lm)
    run_phase(report, "window_expert_lm", phase_window_expert_lm)
    run_phase(report, "conv_expert_lm", phase_conv_expert_lm)
    run_phase(report, "expert_walk", phase_expert_walk)
    run_phase(report, "expert_plan", phase_expert_plan)
    run_phase(report, "group_flash", phase_group_flash)
    run_phase(report, "proj_xent", phase_proj_xent)
    run_phase(report, "latent_walk", phase_latent_walk)
    if len(devices) >= 4 and trainer is not None:
        run_phase(report, "four_chip", phase_four_chip, place, devices,
                  trainer["first_loss"])
    elif len(devices) >= 4:
        report["phases"]["four_chip"] = {
            "status": "failed", "error": "needs the trainer phase's loss"}
    else:
        report["phases"]["four_chip"] = {
            "status": f"not run: {len(devices)} device"}

    c = counters()
    report["fallback_counters"] = {
        n: int(c.get(n, 0))
        for n in (FALLBACK_COUNTERS + LATENT_FALLBACK_COUNTERS
                  + HYBRID_FALLBACK_COUNTERS
                  + PARALLEL_HYBRID_FALLBACK_COUNTERS
                  + WINDOW_EXPERT_FALLBACK_COUNTERS
                  + CONV_EXPERT_FALLBACK_COUNTERS)}
    report["jax_cache"] = {"hits": LOG.cache_hits, "compiles": LOG.compiles,
                           "compile_s": round(LOG.compile_s, 2)}
    failures += [f"phase {n}: {p.get('error')}"
                 for n, p in report["phases"].items()
                 if p["status"] == "failed"]
    failures += [f"fallback counter {n} = {v}"
                 for n, v in report["fallback_counters"].items() if v]
    report["ok"] = not failures
    if failures:
        report["failures"] = failures
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "chip_smoke_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "last_run.json"), "w") as f:
        json.dump(report, f, indent=1)
    print("chip_smoke report:", json.dumps(report), flush=True)
    print(result_line(report), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
