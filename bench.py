"""Benchmark: all five BASELINE configs on one chip, one JSON line.

The parent process NEVER imports jax (one process per chip: a parent
that touched JAX would hold the device its workers need).  It (1) runs
a device check in a kill-able subprocess and records the platform,
device_kind and device count it found in the artifact, (2) runs the
configs in a worker subprocess that prints one flushed partial JSON
line per completed config (an external timeout therefore loses at most
the in-flight config, not the finished ones), (3) enforces a total
wall-clock budget (PADDLE_TPU_BENCH_BUDGET_S, default 1200 s) and a
per-config deadline — a hung config is killed, marked
{"error": "timeout"}, and the worker is restarted on the remaining
configs (a killed worker releases the chip for its successor: checked
on the v5e, PERF.md Bring-up), (4) always prints the final combined
JSON line itself, with explicit {"skipped": "budget"} markers for
anything not run; chip configs FAIL ({"error": ...}) when the device
check found no TPU,
(5) writes a per-config runtime-telemetry artifact (step_stats.json;
path override PADDLE_TPU_BENCH_STATS_PATH, empty disables):
compile-cache hits/misses, lowering + XLA compile time and feed/fetch
bytes from paddle_tpu.observability, so a BENCH_r*.json regression
carries its own explanation.  The rpc_transport config additionally
writes a sampled-trace artifact (bench_trace.json; path override
PADDLE_TPU_BENCH_TRACE_PATH, empty disables): one traced batched round
as a Chrome/Perfetto trace, so the wire spans are inspectable per run.
Role analogue: the reference benchmark driver emits numbers as it goes
(benchmark/fluid/fluid_benchmark.py:295 print_train_time), not at exit.

Round 7 adds the perf-attribution chain: each bench_program config AOT
lower()+compile()s its executable (the same one jax.jit would build) so
XLA ``cost_analysis()`` flops/bytes land next to the measured rate as a
``roofline`` entry (achieved vs peak FLOP/s and GB/s,
compute-vs-memory-bound — observability/perf.py arithmetic), and the
final summary auto-compares against the last *measured* BENCH_r*.json
round via tools/bench_compare.py, recording per-config deltas with
noise bands and a regression verdict under ``comparison``
(PADDLE_TPU_BENCH_COMPARE_PREV pins a baseline, empty disables).

Primary metric (the BASELINE.json headline): ResNet-50 train images/sec/
chip (bf16, batch 256) vs an A100 mixed-precision baseline (~2,500
img/s).  The ``configs`` field carries the other four:

- transformer: Transformer-base at seq 256 with attention-prob dropout
  (auto attention impl: XLA fused attention at this length — the Pallas
  flash kernel takes over at seq >= 2048 where O(T^2) scores would
  dominate HBM), tokens/sec vs A100 ~50k
- stacked_lstm: 3-layer LSTM sentiment net over padded length-128
  sequences, tokens/sec
- deepfm: CTR model with a 1M-row sparse (SelectedRows) embedding table,
  samples/sec
- mnist: convnet, images/sec

Each config reports an approximate model-FLOPs utilization (``mfu_est``)
against the bf16 peak of the device it ran on (``platform.PLATFORM_PEAKS``
by the ``device_kind`` JAX reports) where the arithmetic is dense enough
for the estimate to mean something.

All models run through the full paddle_tpu program stack (layer DSL →
append_backward → optimizer ops → whole-block XLA lowering); the bench
drives the jitted step directly with device-resident donated state, the
steady-state training loop.
"""
from __future__ import annotations

import json
import time

import numpy as np

WARMUP = 3
STEPS = 12

# set by bench_program from the AOT-compiled executable's XLA
# cost_analysis + the measured dispatch time; _take_roofline() moves it
# into the finishing config's result so every BENCH_r*.json throughput
# number ships with flops/bytes attribution and a roofline position
_LAST_ROOFLINE = None


def _bf16_peak():
    """Dense bf16 peak FLOP/s of the device under test, looked up in
    ``platform.PLATFORM_PEAKS`` by the ``device_kind`` JAX reports.  A
    device with no row (or only the nominal CPU envelope) is an error,
    not a default."""
    from paddle_tpu.platform import platform_peaks

    pk = platform_peaks()
    if not pk["flops"] or pk["nominal"]:
        raise RuntimeError(
            f"no peak row for device_kind {pk['device_kind']!r} "
            f"(platform {pk['platform']!r}) in platform.PLATFORM_PEAKS")
    return pk["flops"]


def _take_roofline():
    global _LAST_ROOFLINE
    r, _LAST_ROOFLINE = _LAST_ROOFLINE, None
    return r


def _harvest_roofline(compiled, seconds_per_dispatch):
    """XLA cost attribution for one timed executable: flops + bytes
    accessed from ``cost_analysis()`` and the achieved-vs-peak roofline
    numbers (observability/perf.py arithmetic — per-dispatch flops over
    per-dispatch seconds, so the K-step scan normalization cancels).
    Attribution must never take the bench down."""
    global _LAST_ROOFLINE
    try:
        from paddle_tpu.observability import perf as _perf
        cost = _perf.cost_dict(compiled)
        flops = float(cost.get("flops", 0.0) or 0.0)
        bytes_acc = float(cost.get("bytes accessed", 0.0) or 0.0)
        rf = {"flops_per_dispatch": flops,
              "bytes_per_dispatch": bytes_acc}
        rf.update(_perf.roofline_numbers(flops, bytes_acc,
                                         seconds_per_dispatch))
        _LAST_ROOFLINE = rf
    except Exception:
        _LAST_ROOFLINE = None


def two_point_fit(timed):
    """Per-dispatch device time from a two-point fit that cancels the
    fixed per-readback cost.

    Back-to-back dispatches pipeline on device and only the final
    readback pays the fixed cost, so t(n calls) = fixed + n*t_dispatch
    and the n=3 minus n=1 difference is 2 dispatches of pure device
    time.  ``timed(n)`` runs n back-to-back dispatches and returns wall
    seconds.  (Built when a readback cost 1.4 s; with the chip local
    the fixed cost is small and ROADMAP S2(b) replaces this with the
    host clock around ``block_until_ready``.)

    Reps: each point takes the MIN over several samples, interleaved
    (1,3,1,3,...) so a slow window hits both points rather than biasing
    one side of the fit."""
    t1s, t3s = [], []
    for _ in range(3):
        t1s.append(timed(1))
        t3s.append(timed(3))
    t1s.append(timed(1))
    t1, t3 = min(t1s), min(t3s)
    dt = t3 - t1
    if dt <= 0:  # noise swamped the fit; conservative fallback
        return t3 / 3
    return dt / 2


def bench_program(prog, startup, feed, fetch_names, steps=STEPS,
                  warmup=WARMUP, scan_steps=None):
    """Steady-state steps/sec for one program (donated device state).

    ``scan_steps=K`` runs K optimizer steps per dispatch via ``lax.scan``
    (the device-side training loop — amortizes host dispatch the way a
    production TPU loop double-buffers it away); per-step RNG still
    advances so dropout differs step to step.  When ``scan_steps`` is
    set, ``steps``/``warmup`` are ignored — timing is 1 warmup dispatch
    plus two_point_fit's interleaved sample schedule (4x n=1 and 3x n=3
    timed dispatch batches, min-per-point, n=3 minus n=1 fit).
    """
    import jax
    from jax import lax
    from paddle_tpu.core.executor import (Executor, Scope, _as_device_array,
                                          scope_guard)
    from paddle_tpu.core.lowering import analyze_block, build_block_fn

    scope = Scope()
    exe = Executor()
    with scope_guard(scope):
        exe.run(startup)

        ordered = sorted(feed)
        plan = analyze_block(prog, 0, ordered, list(fetch_names))
        fn = build_block_fn(prog, plan)
        refeed = plan.donated_write_indices

        block = prog.global_block
        feeds = [jax.device_put(
            _as_device_array(feed[n], block.var_or_none(n)))
            for n in ordered]
        donated = [jax.device_put(np.asarray(scope.find_var(n)))
                   for n in plan.donated_reads]
        const = [jax.device_put(np.asarray(scope.find_var(n)))
                 for n in plan.const_reads]
        rng = jax.random.PRNGKey(0)

        if scan_steps:
            K = scan_steps

            def multi(feeds, donated, const, rng):
                def one(carry, _):
                    donated, rng = carry
                    fetches, new_state, rng = fn(feeds, donated, const, rng)
                    return ([new_state[i] for i in refeed], rng), fetches[0]
                (donated, rng), ls = lax.scan(
                    one, (donated, rng), None, length=K)
                return ls[-1], donated, rng

            # AOT lower+compile the SAME executable jax.jit would build:
            # the compiled handle exposes cost_analysis() for the
            # roofline attribution the summary carries per config
            compiled = jax.jit(multi, donate_argnums=(1,)).lower(
                feeds, donated, const, rng).compile()

            def step(donated, rng):
                return compiled(feeds, donated, const, rng)

            l, donated, rng = step(donated, rng)  # warmup: settle + K steps
            float(np.asarray(l))

            def timed(n):
                nonlocal donated, rng
                t0 = time.perf_counter()
                l = None
                for _ in range(n):
                    l, donated, rng = step(donated, rng)
                float(np.asarray(l))
                return time.perf_counter() - t0

            dt = two_point_fit(timed)
            _harvest_roofline(compiled, dt)
            return K / dt

        compiled = jax.jit(fn, donate_argnums=(1,)).lower(
            feeds, donated, const, rng).compile()  # AOT: analyzable handle

        def step(donated, rng):
            fetches, new_state, rng = compiled(feeds, donated, const, rng)
            return fetches[0], [new_state[i] for i in refeed], rng

        l = None
        for _ in range(warmup):
            l, donated, rng = step(donated, rng)
        if l is not None:
            float(np.asarray(l))  # hard sync (readback)
        t0 = time.perf_counter()
        for _ in range(steps):
            l, donated, rng = step(donated, rng)
        float(np.asarray(l))
        dt = time.perf_counter() - t0
        _harvest_roofline(compiled, dt / steps)
    return steps / dt


def _fresh(build_fn, seed=1):
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.program import Program, program_guard

    prog, startup = Program(), Program()
    prog.random_seed = seed
    with program_guard(prog, startup), unique_name.guard():
        out = build_fn()
    return prog, startup, out


def bench_resnet50():
    from paddle_tpu.models import resnet

    B = 256  # best measured batch for v5e-1 (128: 2.1k, 512: 2.4k img/s)
    prog, startup, (feeds, loss, acc) = _fresh(
        lambda: resnet.build(dtype="bfloat16", lr=0.1, layout="NHWC"))
    rng = np.random.RandomState(0)
    feed = {"data": rng.randn(B, 3, 224, 224).astype("float32"),
            "label": rng.randint(0, 1000, (B, 1)).astype("int64")}
    sps = bench_program(prog, startup, feed, [loss.name], steps=96,
                        scan_steps=96)
    img_s = sps * B
    flops_per_img = 3 * 3.8e9  # fwd 3.8 GF @224 x ~3 for fwd+bwd
    return {"images_per_sec": round(img_s, 1),
            "mfu_est": round(img_s * flops_per_img / _bf16_peak(), 3)}


def bench_transformer():
    from paddle_tpu.models import transformer

    B, T, V, D, L = 32, 256, 32000, 512, 6
    prog, startup, (feeds, loss, _) = _fresh(
        lambda: transformer.build(src_vocab=V, tgt_vocab=V, max_len=T,
                                  dropout=0.1, dtype="bfloat16",
                                  attention_impl="auto"))
    rng = np.random.RandomState(0)
    mask = np.ones((B, T), "float32")
    feed = {"src_ids": rng.randint(0, V, (B, T)).astype("int64"),
            "tgt_ids": rng.randint(0, V, (B, T)).astype("int64"),
            "lbl_ids": rng.randint(0, V, (B, T)).astype("int64"),
            "src_mask": mask, "tgt_mask": mask}
    sps = bench_program(prog, startup, feed, [loss.name], steps=24,
                        scan_steps=24)
    tok_s = sps * B * T
    # ~63M non-embedding params; attention scores: 18 attn blocks
    flops_per_step = (6 * 63e6 * B * T * 2  # enc+dec streams share tokens
                      + 12 * 18 * B * T * T * D)
    return {"tokens_per_sec": round(tok_s, 1),
            "mfu_est": round(sps * flops_per_step / _bf16_peak(), 3)}


def bench_stacked_lstm():
    from paddle_tpu.models import stacked_lstm

    B, T = 128, 128
    prog, startup, (feeds, loss, acc) = _fresh(
        lambda: stacked_lstm.build(dict_dim=30000, emb_dim=512, hid_dim=512,
                                   stacked_num=3))
    rng = np.random.RandomState(0)
    feed = {"words": rng.randint(0, 30000, (B, T, 1)).astype("int64"),
            "words@LEN": np.full((B,), T, "int64"),
            "label": rng.randint(0, 2, (B, 1)).astype("int64")}
    sps = bench_program(prog, startup, feed, [loss.name], steps=24,
                        scan_steps=24)
    tok_s = sps * B * T
    # per token per layer: 8*H*H matmul flops, x3 train
    flops_per_step = 3 * 2 * (8 * 512 * 512) * 3 * B * T
    return {"tokens_per_sec": round(tok_s, 1),
            "mfu_est": round(sps * flops_per_step / _bf16_peak(), 3)}


def bench_deepfm():
    from paddle_tpu.models import deepfm

    B = 2048
    rows = 1_000_000
    prog, startup, (feeds, loss, _) = _fresh(
        lambda: deepfm.build(sparse_dim=rows))
    rng = np.random.RandomState(0)
    feed = {"dense": rng.randn(B, 13).astype("float32"),
            "sparse": rng.randint(0, rows, (B, 26)).astype("int64"),
            "label": rng.randint(0, 2, (B, 1)).astype("float32")}
    sps = bench_program(prog, startup, feed, [loss.name], steps=24,
                        scan_steps=24)
    out = {"samples_per_sec": round(sps * B, 1), "table_rows": rows}
    out["raw_jax_floor_samples_per_sec"] = _deepfm_scatter_floor(B, rows)
    out["vs_floor"] = round(out["samples_per_sec"]
                            / max(out["raw_jax_floor_samples_per_sec"], 1), 3)
    return out


def _deepfm_scatter_floor(B, rows, emb_dim=10, slots=26, K=24):
    """Raw-JAX floor for the sparse part of the CTR step, WORKLOAD-
    MATCHED to the model: BOTH tables ([rows, emb] second-order and
    [rows, 1] first-order) each do an embedding gather over the same
    B*slots ids + a grad scatter — the irreducible per-step table
    traffic with no framework anywhere (the r3 floor used ONE table and
    so overstated the gap ~1.26x).  Same K-scan + two-point RTT fit as
    bench_program."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    rng = np.random.RandomState(1)
    t_emb = jnp.asarray(rng.randn(rows, emb_dim) * 0.01, jnp.float32)
    t_w1 = jnp.asarray(rng.randn(rows, 1) * 0.01, jnp.float32)
    flat = jnp.asarray(rng.randint(0, rows, (B * slots,)))

    @jax.jit
    def multi(state):
        def body(state, _):
            t_emb, t_w1 = state
            e = t_emb[flat]                          # gather [B*slots, emb]
            e1 = t_w1[flat]
            t_emb = t_emb.at[flat].add(-0.01 * 2.0 * e)  # scatter-SGD
            t_w1 = t_w1.at[flat].add(-0.01 * 2.0 * e1)
            return (t_emb, t_w1), None
        state, _ = lax.scan(body, state, None, length=K)
        return state

    r = multi((t_emb, t_w1))
    float(np.asarray(r[0][0, 0]))

    def timed(n):
        nonlocal r
        t0 = time.perf_counter()
        for _ in range(n):
            r = multi(r)
        float(np.asarray(r[0][0, 0]))
        return time.perf_counter() - t0

    dt = two_point_fit(timed) / K
    return round(B / dt, 1)


def bench_deepfm_fused():
    """ISSUE 10 / ROADMAP 3(c): the fused Pallas sparse-embedding path
    (FLAGS_sparse_fused_kernel — one multi-table gather launch + one
    row-wise update launch per table, kernels/sparse.py) vs the
    masked-dense baseline vs the workload-matched raw-JAX two-table
    floor, ``vs_floor`` inline.  On-chip target: >= 400k samples/s,
    >= 0.8x the floor-band center (PERF.md §11).

    Off-TPU this config cannot measure the claim (interpret-mode grids
    are ~600 us/row on CPU), so it degrades to a structural analysis
    artifact labeled ``analysis: true``: the whole-step scatter-class /
    pallas-launch census plus a small-shape fused-vs-unfused parity
    check — the shape of the evidence; the on-chip number is not
    measured (ROADMAP S1/S3)."""
    import jax

    if jax.default_backend() != "tpu":
        return _deepfm_fused_analysis()

    from paddle_tpu.core import flags as _flags
    from paddle_tpu.models import deepfm

    B = 2048
    rows = 1_000_000
    rng = np.random.RandomState(0)
    feed = {"dense": rng.randn(B, 13).astype("float32"),
            "sparse": rng.randint(0, rows, (B, 26)).astype("int64"),
            "label": rng.randint(0, 2, (B, 1)).astype("float32")}

    def run(flag):
        _flags.set_flags({"sparse_fused_kernel": flag})
        try:
            prog, startup, (feeds, loss, _) = _fresh(
                lambda: deepfm.build(sparse_dim=rows))
            return bench_program(prog, startup, feed, [loss.name], steps=24,
                                 scan_steps=24)
        finally:
            _flags.set_flags({"sparse_fused_kernel": False})

    dense_sps = run(False)
    fused_sps = run(True)  # last: the harvested roofline is the fused step
    floor = _deepfm_scatter_floor(B, rows)
    return {
        "fused_samples_per_sec": round(fused_sps * B, 1),
        "masked_dense_samples_per_sec": round(dense_sps * B, 1),
        "table_rows": rows,
        "raw_jax_floor_samples_per_sec": floor,
        "vs_floor": round(fused_sps * B / max(floor, 1), 3),
        "vs_masked_dense": round(fused_sps / max(dense_sps, 1e-9), 3),
    }


def _deepfm_fused_analysis():
    """CPU degrade of ``bench_deepfm_fused``: structural evidence only."""
    import jax

    from paddle_tpu.core import flags as _flags
    from paddle_tpu.core.executor import Executor, Scope, scope_guard
    from paddle_tpu.core.lowering import analyze_block, build_block_fn
    from paddle_tpu.core.program import Program, program_guard
    from paddle_tpu.core import unique_name
    from paddle_tpu.models import deepfm

    from paddle_tpu.kernels.sparse import jaxpr_census as census

    B, rows = 8, 512

    def step(flag, n_steps=0):
        _flags.set_flags({"sparse_fused_kernel": flag})
        try:
            prog, startup = Program(), Program()
            prog.random_seed = 3
            with program_guard(prog, startup), unique_name.guard():
                feeds, loss, _ = deepfm.build(sparse_dim=rows, lr=1e-2)
            rng = np.random.RandomState(0)
            feed = {"dense": rng.rand(B, 13).astype("float32"),
                    "sparse": rng.randint(0, rows, (B, 26)).astype("int64"),
                    "label": (rng.rand(B, 1) > 0.5).astype("float32")}
            exe = Executor()
            sc = Scope()
            with scope_guard(sc):
                exe.run(startup)
                plan = analyze_block(prog, 0, sorted(feeds), [loss.name])
                fn = build_block_fn(prog, plan, training=True)
                fv = [feed[n] for n in sorted(feeds)]
                donated = [np.asarray(sc.find_var(n))
                           for n in plan.donated_reads]
                const = [np.asarray(sc.find_var(n))
                         for n in plan.const_reads]
                jaxpr = jax.make_jaxpr(fn)(fv, donated, const,
                                           jax.random.PRNGKey(0))
                table = None
                for _ in range(n_steps):
                    exe.run(prog, feed=feed, fetch_list=[loss.name])
                if n_steps:
                    table = np.asarray(sc.find_var("ctr.sparse_emb")).copy()
            return census(jaxpr.jaxpr), table
        finally:
            _flags.set_flags({"sparse_fused_kernel": False})

    (sc_on, pl_on), t_on = step(True, n_steps=2)
    (sc_off, pl_off), t_off = step(False, n_steps=2)
    return {
        "analysis": True,
        "note": "CPU structural run: interpret-mode kernels cannot measure "
                "the on-chip rate (not measured)",
        "scatter_ops_flag_on": sc_on,
        "scatter_ops_flag_off": sc_off,
        "pallas_launches_flag_on": pl_on,
        "fused_parity_maxdiff": float(np.max(np.abs(t_on - t_off))),
        "table_rows": rows,
    }


def bench_resnet50_datapath():
    """ResNet-50 with the DATA LAYER on the hot path: batches flow
    native RecordIO file -> C MPMC queue -> DataLoader (device_prefetch
    one batch ahead) -> per-step async ``exe.run`` — the reference's
    double-buffer reader train loop
    (operators/reader/create_double_buffer_reader_op.cc,
    benchmark/fluid/fluid_benchmark.py:137).

    The host->device link rate is measured inline below (a serial
    ``jax.device_put`` of the same bytes); pre-staged feeds are how the
    main bench isolates device throughput.  The meaningful metric here
    is pipeline efficiency: measured datapath rate vs that raw
    ``jax.device_put`` ceiling.  >=0.8 means RecordIO+queue+decode+
    dispatch add <20% on top of the link."""
    import os
    import tempfile

    import jax

    from paddle_tpu.core.executor import Executor, Scope, scope_guard
    from paddle_tpu.data.loader import DataLoader
    from paddle_tpu.data.recordio_utils import reader_creator, write_recordio
    from paddle_tpu.models import resnet

    B, n_batches, steps = 32, 4, 20
    rng = np.random.RandomState(0)
    batches = [(rng.randn(B, 3, 224, 224).astype("float32"),
                rng.randint(0, 1000, (B, 1)).astype("int64"))
               for _ in range(n_batches)]

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "resnet.recordio")

        def sample_reader():
            for img, lbl in batches:
                for i in range(B):
                    yield (img[i], lbl[i])

        write_recordio(sample_reader, path)

        def batch_reader():
            while True:  # cycle forever; bench takes `steps` batches
                buf = []
                for sample in reader_creator(path)():
                    buf.append(sample)
                    if len(buf) == B:
                        yield buf
                        buf = []

        prog, startup, (feeds, loss, acc) = _fresh(
            lambda: resnet.build(dtype="bfloat16", lr=0.1, layout="NHWC"))
        scope = Scope()
        exe = Executor()
        with scope_guard(scope):
            exe.run(startup)
            loader = DataLoader(feed_list=["data", "label"],
                                reader=batch_reader, capacity=2,
                                program=prog)
            it = iter(loader)
            # warmup: compile + settle the queue
            feed = next(it)
            l, = exe.run(prog, feed=feed, fetch_list=[loss.name])
            float(np.asarray(l))

            t0 = time.perf_counter()
            last = None
            for _ in range(steps):
                feed = next(it)
                last, = exe.run(prog, feed=feed, fetch_list=[loss.name])
            float(np.asarray(last))      # one batched flush (async run)
            dt = time.perf_counter() - t0
        datapath_img_s = steps * B / dt

        # raw link ceiling: device_put the same bytes, nothing else
        arrs = [b[0] for b in batches]
        d = jax.device_put(arrs[0])
        float(np.asarray(d.ravel()[0]))
        t0 = time.perf_counter()
        ds = [jax.device_put(arrs[i % n_batches]) for i in range(steps)]
        for d in ds:
            d.block_until_ready()
        float(np.asarray(ds[-1].ravel()[0]))
        link_img_s = steps * B / (time.perf_counter() - t0)

    return {"images_per_sec": round(datapath_img_s, 1),
            "link_serial_put_images_per_sec": round(link_img_s, 1),
            "pipeline_vs_link": round(datapath_img_s / link_img_s, 3),
            "note": "pipeline_vs_link >= 1 means RecordIO+queue+decode+"
                    "async-dispatch saturate the host->device link "
                    "(overlapped transfers beat the serial device_put "
                    "probe) — the data layer is not the bound"}


def bench_mnist():
    from paddle_tpu.models import mnist

    B = 512
    prog, startup, (feeds, loss, acc) = _fresh(lambda: mnist.build())
    rng = np.random.RandomState(0)
    feed = {"pixel": rng.randn(B, 1, 28, 28).astype("float32"),
            "label": rng.randint(0, 10, (B, 1)).astype("int64")}
    # K=384: the mnist step is ~0.3 ms, so short scans leave the fit
    # dominated by dispatch jitter (r3/r4 runs swung 0.8-1.7M img/s);
    # a longer in-jit scan amortizes it to band noise
    sps = bench_program(prog, startup, feed, [loss.name], steps=384,
                        scan_steps=384)
    return {"images_per_sec": round(sps * B, 1)}


def bench_flash_attention_long():
    """Long-context attention: Pallas flash fwd+bwd at seq 8192 (XLA's
    materialized-scores path fails to compile at this length on v5e —
    flash is the only viable kernel; its O(block) memory is the
    long-context story).

    Two shapes at equal FLOPs / model width: H=8,D=64 and the TPU-native
    H=4,D=128 (head_dim = MXU lane width halves the per-score VPU
    softmax work).  Timing: K-step in-jit scan, n=3 minus n=1 dispatch
    fit (see bench_program)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from paddle_tpu.kernels.attention import flash_attention

    T, K = 8192, 12
    out = {"seq_len": T}
    best = 0.0
    for tag, (B, H, D) in {"h8_d64": (4, 8, 64),
                           "h4_d128": (4, 4, 128)}.items():
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)
        k = jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)
        v = jnp.asarray(rng.randn(B, H, T, D), jnp.bfloat16)

        def loss(q, k, v):
            return (flash_attention(q, k, v, None, True, None)
                    .astype(jnp.float32) ** 2).sum()

        grad = jax.grad(loss, (0, 1, 2))

        def multi(q, k, v):
            def body(carry, _):
                q, k, v = carry
                dq, dk, dv = grad(q, k, v)
                eps = jnp.bfloat16(1e-8)
                return (q + dq * eps, k + dk * eps, v + dv * eps), None
            (q, k, v), _ = lax.scan(body, (q, k, v), None, length=K)
            return q
        step = jax.jit(multi)
        r = step(q, k, v)
        float(np.asarray(r[0, 0, 0, 0]))

        def timed(n):
            t0 = time.perf_counter()
            for _ in range(n):
                r = step(q, k, v)
            float(np.asarray(r[0, 0, 0, 0]))
            return time.perf_counter() - t0

        dt = two_point_fit(timed) / K
        flops = 3.5 * 2 * B * H * T * T * D / 2  # causal fwd+bwd
        tf = flops / dt / 1e12
        out[tag] = {"tokens_per_sec": round(B * T / dt, 1),
                    "tflops": round(tf, 1)}
        best = max(best, tf)

    # numerics cross-check at the full 8k length: chunked-jnp reference
    # (XLA's one-shot attention fails to compile at this T) on one
    # batch-head, bf16 tolerance
    @jax.jit
    def ref_slice(q, k, v):
        sm = 1.0 / np.sqrt(q.shape[-1])

        def chunk(i):
            c = lax.dynamic_slice_in_dim(q, i * 1024, 1024, 0)
            s = (c @ k.T).astype(jnp.float32) * sm
            qi = jnp.arange(1024)[:, None] + i * 1024
            s = jnp.where(qi >= jnp.arange(T)[None, :], s, -1e30)
            p = jax.nn.softmax(s, axis=-1)
            return p.astype(v.dtype) @ v
        return jnp.concatenate([chunk(i) for i in range(T // 1024)], 0)

    fl = jax.jit(lambda q, k, v: flash_attention(q, k, v, None, True, None))
    o_flash = fl(q, k, v)[0, 0]
    o_ref = ref_slice(q[0, 0], k[0, 0], v[0, 0])
    maxdiff = float(jnp.max(jnp.abs(o_flash.astype(jnp.float32)
                                    - o_ref.astype(jnp.float32))))
    assert maxdiff < 0.05, f"flash vs chunked-jnp at 8k: {maxdiff}"
    out["crosscheck_maxdiff_8k"] = round(maxdiff, 5)
    out["tflops"] = round(best, 1)
    out["tokens_per_sec"] = out["h4_d128"]["tokens_per_sec"]

    # seq-32k single-chip entry: the long-context point the ring path's
    # per-shard compute inherits (flash is O(block) memory — 32k never
    # materializes scores; XLA's chain cannot compile this length here)
    T32, K32 = 32768, 4
    B, H, D = 1, 4, 128
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, T32, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, H, T32, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, H, T32, D), jnp.bfloat16)

    def loss32(q, k, v):
        return (flash_attention(q, k, v, None, True, None)
                .astype(jnp.float32) ** 2).sum()

    grad32 = jax.grad(loss32, (0, 1, 2))

    def multi32(q, k, v):
        def body(carry, _):
            q, k, v = carry
            dq, dk, dv = grad32(q, k, v)
            eps = jnp.bfloat16(1e-8)
            return (q + dq * eps, k + dk * eps, v + dv * eps), None
        (q, k, v), _ = lax.scan(body, (q, k, v), None, length=K32)
        return q
    step32 = jax.jit(multi32)
    r = step32(q, k, v)
    float(np.asarray(r[0, 0, 0, 0]))

    def timed32(n):
        t0 = time.perf_counter()
        for _ in range(n):
            r = step32(q, k, v)
        float(np.asarray(r[0, 0, 0, 0]))
        return time.perf_counter() - t0

    dt = two_point_fit(timed32) / K32
    flops32 = 3.5 * 2 * B * H * T32 * T32 * D / 2
    out["seq32k_h4_d128"] = {"tokens_per_sec": round(B * T32 / dt, 1),
                             "tflops": round(flops32 / dt / 1e12, 1)}
    return out


def bench_ring_shard():
    """Per-shard-pair Pallas workload at the ring path's shard shapes
    (VERDICT r4 #7): with seq-parallel degree sp over global S=16384,
    each device holds S/sp=4096 queries and, per ring hop, runs flash
    against one 4096-key shard — causal-masked on the diagonal hop
    (kv_index == q_index), full unmasked on off-diagonal hops where
    kv_index < q_index.  Measuring both hop kinds on the real chip
    gives the sp-scaling story a per-shard rate: a full ring step is
    1 diagonal + (sp-1 on average /2...) — we report each hop's rate
    and the implied per-device rate for sp=4.  Correctness of the
    ring composition itself is pinned by the CPU-mesh parity tests
    (tests/test_attention.py); this entry is the missing perf anchor."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from paddle_tpu.kernels.attention import flash_attention

    S, B, H, D, K = 4096, 1, 4, 128, 8
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(B, H, S, D), jnp.bfloat16)

    out = {"shard_len": S, "heads": H, "head_dim": D}
    for tag, causal in [("diagonal_hop_causal", True),
                        ("offdiag_hop_full", False)]:
        def loss(q, k, v, causal=causal):
            return (flash_attention(q, k, v, None, causal, None)
                    .astype(jnp.float32) ** 2).sum()

        grad = jax.grad(loss, (0, 1, 2))

        def multi(q, k, v):
            def body(carry, _):
                q, k, v = carry
                dq, dk, dv = grad(q, k, v)
                eps = jnp.bfloat16(1e-8)
                return (q + dq * eps, k + dk * eps, v + dv * eps), None
            (q, k, v), _ = lax.scan(body, (q, k, v), None, length=K)
            return q

        step = jax.jit(multi)
        r = step(q, k, v)
        float(np.asarray(r[0, 0, 0, 0]))

        def timed(n):
            t0 = time.perf_counter()
            for _ in range(n):
                r = step(q, k, v)
            float(np.asarray(r[0, 0, 0, 0]))
            return time.perf_counter() - t0

        dt = two_point_fit(timed) / K
        frac = 0.5 if causal else 1.0  # causal computes half the scores
        flops = 3.5 * 2 * B * H * S * S * D * frac
        out[tag] = {"pair_ms": round(dt * 1e3, 2),
                    "tflops": round(flops / dt / 1e12, 1)}

    # implied per-device ring step at sp=4 (1 diagonal + 1.5 avg
    # off-diagonal hops under causal load balance): tokens/s per device
    d_ms = out["diagonal_hop_causal"]["pair_ms"]
    o_ms = out["offdiag_hop_full"]["pair_ms"]
    step_ms = d_ms + 1.5 * o_ms
    out["implied_sp4_tokens_per_sec_per_device"] = round(
        B * S / (step_ms * 1e-3), 1)
    return out


def bench_rpc_transport():
    """Var-transport hot path on a loopback pserver (no TPU needed):
    measures the batched/striped/zero-copy wire (SEND_VARS/GET_VARS,
    ``FLAGS_rpc_conns_per_endpoint`` striping, sendmsg/iovec
    scatter-gather serde) against the pre-change transport shape
    (per-var SEND_VAR/GET_VAR round trips over one lock-serialized
    connection, concat-copy serde) — same server, same sockets, so the
    ratio isolates the transport work.

    Two scaling axes, two-point-fit style (min over reps):
    - ``storm_256``: 256 small dense vars per round — round-trip-count
      scaling (the many-sections model shape); metric vars/s.
    - ``dense_64mb``: one 64 MB gradient per round — copy/bandwidth
      scaling; metric effective MB/s.
    """
    import threading

    import paddle_tpu as fluid
    from paddle_tpu.distributed import serde, transport

    class _VarStore:
        """Minimal pserver-shaped service: var table behind one lock
        (the PServerLoop per-frame lock acquisition), both legacy and
        batched message types."""

        def __init__(self):
            self.vars = {}
            self.lock = threading.Lock()

        def handle(self, msg_type, tid, name, payload):
            if msg_type == transport.SEND_VAR:
                v = serde.loads_value(payload)
                with self.lock:
                    self.vars[name] = v
                return transport.OK, b""
            if msg_type == transport.SEND_VARS:
                pairs = serde.loads_batch(payload, copy=False)
                with self.lock:
                    for n, v in pairs:
                        self.vars[n] = v
                return transport.OK, b""
            if msg_type == transport.GET_VAR:
                with self.lock:
                    v = self.vars[name]
                return transport.OK, serde.dumps_value(v)
            if msg_type == transport.GET_VARS:
                names = [n for n, _ in serde.loads_batch(payload)]
                with self.lock:
                    pairs = [(n, self.vars[n]) for n in names]
                return transport.OK, serde.dumps_batch_vec(pairs)
            return transport.OK, b""

    LEGACY = {"rpc_batch_vars": 0, "rpc_vectored_io": 0,
              "rpc_conns_per_endpoint": 1, "rpc_stripe_chunk_bytes": 0}
    NEW = {"rpc_batch_vars": 1, "rpc_vectored_io": 1,
           "rpc_conns_per_endpoint": 4,
           "rpc_stripe_chunk_bytes": 8 << 20}

    def timed_min(fn, reps):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    def run_mode(flags, out, tag):
        fluid.set_flags(flags)
        srv = transport.RPCServer("127.0.0.1:0", _VarStore())
        srv.start()
        ep = f"127.0.0.1:{srv.port}"
        client = transport.RPCClient(0)
        try:
            rng = np.random.RandomState(0)
            small = [(f"v{i}", rng.randn(16).astype("float32"))
                     for i in range(256)]
            names = [n for n, _ in small]
            big = rng.randn(64 << 18).astype("float32")  # 64 MB

            def storm_send():
                if flags["rpc_batch_vars"]:
                    client.send_vars(ep, small)
                else:
                    client.parallel([(client.send_var, ep, n, v)
                                     for n, v in small])

            def storm_get():
                if flags["rpc_batch_vars"]:
                    client.get_vars(ep, names)
                else:
                    client.parallel([(client.get_var, ep, n)
                                     for n in names])

            def dense_send():
                if flags["rpc_batch_vars"]:
                    client.send_vars(ep, [("big", big)])
                else:
                    client.send_var(ep, "big", big)

            storm_send(), storm_get(), dense_send()  # warmup/connect
            t_storm = timed_min(storm_send, 5) + timed_min(storm_get, 5)
            t_dense = timed_min(dense_send, 5)
            out[f"{tag}_storm_vars_per_sec"] = round(512 / t_storm, 1)
            out[f"{tag}_dense_mb_per_sec"] = round(64 / t_dense, 1)
        finally:
            srv.stop()

    def traced_round(flags):
        """One sampled batched round AFTER timing (sampling must not
        pollute the measured numbers): the PR-3 wire spans —
        rpc.client/rpc.server send_vars/get_vars — land in the span
        ring, which _write_bench_trace turns into the trace artifact."""
        from paddle_tpu.observability import trace as _trace

        fluid.set_flags(dict(flags, trace_sample_rate=1.0))
        try:
            _trace.clear_spans()
            srv = transport.RPCServer("127.0.0.1:0", _VarStore())
            srv.start()
            ep = f"127.0.0.1:{srv.port}"
            client = transport.RPCClient(0)
            try:
                rng = np.random.RandomState(0)
                small = [(f"v{i}", rng.randn(16).astype("float32"))
                         for i in range(32)]
                with _trace.start_span("bench::rpc_round", cat="bench"):
                    client.send_vars(ep, small)
                    client.get_vars(ep, [n for n, _ in small])
            finally:
                srv.stop()
        finally:
            fluid.set_flags({"trace_sample_rate": 0.0})

    saved = fluid.get_flags(list(LEGACY) + ["trace_sample_rate"])
    out = {"storm_vars": 256, "dense_bytes": 64 << 20}
    try:
        run_mode(LEGACY, out, "legacy")
        run_mode(NEW, out, "batched")
        traced_round(NEW)
    finally:
        fluid.set_flags(saved)
    out["storm_speedup"] = round(out["batched_storm_vars_per_sec"]
                                 / out["legacy_storm_vars_per_sec"], 2)
    out["dense_speedup"] = round(out["batched_dense_mb_per_sec"]
                                 / out["legacy_dense_mb_per_sec"], 2)
    _write_bench_trace(out)
    return out


def _write_bench_trace(out):
    """Sampled-trace artifact next to step_stats.json
    (PADDLE_TPU_BENCH_TRACE_PATH overrides, empty disables): the span
    ring of the traced rpc_transport round as a Chrome/Perfetto trace,
    so the batched-wire spans are *visible* in the bench artifact, not
    just summarized."""
    import os

    path = os.environ.get("PADDLE_TPU_BENCH_TRACE_PATH", "bench_trace.json")
    if not path:
        return
    try:
        from paddle_tpu.observability import trace as _trace

        snap = _trace.local_trace_snapshot()
        if not snap["spans"]:
            return
        with open(path, "w") as f:
            json.dump(_trace.stitch_chrome_trace({"bench": snap}), f)
        out["trace_path"] = path
        out["trace_spans"] = len(snap["spans"])
    except Exception as e:  # telemetry must never take the bench down
        out["trace_error"] = repr(e)[:200]


def _serving_predictor(kind, seed=1, int8=False):
    """Forward-only predictor for the serving bench (in-process).
    ``int8=True`` runs the fusion + quantize_int8 calibration passes
    (the create_predictor enable_int8() pipeline) on the built
    program before wrapping it."""
    from paddle_tpu.core.executor import Executor, Scope, scope_guard
    from paddle_tpu.inference.predictor import Predictor

    import paddle_tpu as fluid

    if kind == "mnist":
        from paddle_tpu.models.mnist import cnn_model

        def build():
            x = fluid.layers.data("pixel", [1, 28, 28])
            return ["pixel"], cnn_model(x)
        nhwc = True  # the serving analysis pipeline's layout pass (the
        # repo's TPU-native conv layout; NCHW↔NHWC parity is pinned by
        # test_inference.py::test_convert_to_nhwc_pass_preserves_outputs)
    else:  # tiny transformer: serving-shaped, tier-1-speed geometry
        from paddle_tpu.models.transformer import transformer

        def build():
            T = 16
            src = fluid.layers.data("src_ids", [T], dtype="int64")
            tgt = fluid.layers.data("tgt_ids", [T], dtype="int64")
            sm = fluid.layers.data("src_mask", [T])
            tm = fluid.layers.data("tgt_mask", [T])
            logits = transformer(src, tgt, sm, tm, src_vocab=512,
                                 tgt_vocab=512, max_len=T, d_model=64,
                                 n_head=4, d_ffn=128, n_layer=2,
                                 dropout=0.0)
            return ["src_ids", "tgt_ids", "src_mask", "tgt_mask"], logits
        nhwc = False

    prog, startup, (feed_names, out) = _fresh(build, seed=seed)
    scope, exe = Scope(), Executor()
    with scope_guard(scope):
        exe.run(startup)
        from paddle_tpu.inference import passes as P
        if nhwc:
            P.convert_to_nhwc(prog, scope, keep_vars=[out.name])
        if int8:
            # the enable_int8() pipeline order: fusion first so the
            # int8 epilogue absorbs bias + activation
            P.fuse_fc_act(prog, scope, keep_vars=[out.name])
            P.quantize_int8(prog, scope, keep_vars=[out.name])
    return Predictor(prog, feed_names, [out.name], scope)


def _serving_request(kind, rng, rows=1):
    if kind == "mnist":
        return {"pixel": rng.randn(rows, 1, 28, 28).astype("float32")}
    T = 16
    return {"src_ids": rng.randint(0, 512, (rows, T)).astype("int64"),
            "tgt_ids": rng.randint(0, 512, (rows, T)).astype("int64"),
            "src_mask": np.ones((rows, T), "float32"),
            "tgt_mask": np.ones((rows, T), "float32")}


def _serving_load(submit_fn, requests, n_clients, window: int = 1):
    """Load generator: ``n_clients`` threads each drive its share of
    ``requests`` through ``submit_fn(feed)``.  ``window=1``:
    closed-loop synchronous (submit_fn blocks until the reply).
    ``window>1``: submit_fn returns a Future and each client keeps up
    to ``window`` requests outstanding — many concurrent remote users
    modeled with few generator threads, so the load generator's GIL
    time does not starve the 2-core bench host's XLA threads.  Returns
    (qps, p50_ms, p99_ms, errors)."""
    import threading

    lat, errors = [], []
    lock = threading.Lock()
    shards = [requests[i::n_clients] for i in range(n_clients)]

    def client(shard):
        mine = []
        pend = []
        it = iter(shard)
        done = False
        while not done or pend:
            while not done and len(pend) < window:
                feed = next(it, None)
                if feed is None:
                    done = True
                    break
                t0 = time.perf_counter()
                try:
                    r = submit_fn(feed)
                except Exception as e:
                    with lock:
                        errors.append(repr(e)[:120])
                    continue
                if window == 1:
                    mine.append((time.perf_counter() - t0) * 1e3)
                else:
                    pend.append((t0, r))
            if pend:
                t0, fut = pend.pop(0)
                try:
                    fut.result(timeout=600)
                    mine.append((time.perf_counter() - t0) * 1e3)
                except Exception as e:
                    with lock:
                        errors.append(repr(e)[:120])
        with lock:
            lat.extend(mine)

    threads = [threading.Thread(target=client, args=(s,)) for s in shards]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    lat.sort()

    def pct(p):
        return round(lat[min(int(p * len(lat)), len(lat) - 1)], 3) \
            if lat else None
    return round(len(lat) / dt, 1), pct(0.5), pct(0.99), errors


def _exec_counters():
    from paddle_tpu import observability as obs
    d = obs.stats.default_registry().to_dict()
    return {k: d.get(k, 0) for k in
            ("executor.cache_misses", "executor.shape_recompiles",
             "executor.persistent_misses")}


def bench_serving():
    """Continuous-batching serving plane vs the sequential baseline
    (paddle_tpu/serving; CPU loopback, in-process — labeled as such:
    the ratio isolates the batching/dispatch policy; on-chip: not
    measured).

    Per model (mnist convnet — NHWC analysis layout — and a tiny
    serving-shaped transformer):

    - ``seq``: the pre-serving shape — a server answering one request
      at a time, one ``Predictor.run`` dispatch + readback per request,
      under closed-loop concurrent clients; QPS and p50/p99 at
      saturation (p99 is dominated by queue wait, as it is for any
      serial server under load).
    - ``batched``: the same predictor behind the continuous batcher
      (warmed bucket ladder), offered ~96 outstanding requests via 8
      windowed generator threads: QPS and p50/p99.

    Plus the swap acceptance: a hot-swap under full load must complete
    with zero dropped requests and zero executor recompiles/misses in
    the post-warm serving window, and the cold vs warm-pool first-reply
    latency shows what the warm ladder buys."""
    from paddle_tpu.core import flags as _flags

    # latency anatomy + saturation anatomy ride the measured window:
    # both are host-side monotonic stamps (no device syncs); per-phase
    # p99s AND utilization/headroom land in the artifact so a tail
    # regression names its phase and a capacity shift is visible
    # round-over-round (finally-restored: a mid-bench error must not
    # leave the flags on to skew every later config in this process)
    # the golden canary probes ride the measured window too
    # (FLAGS_canary_probe at a bench cadence): goldens are recorded
    # against the live manager before load starts, so the artifact
    # carries canary_overhead_frac (what correctness probing costs) and
    # canary_failures (0 on a healthy build — a secondary gate in
    # tools/bench_compare.py)
    _flags.set_flags({"phase_attribution": True,
                      "capacity_attribution": True,
                      "canary_probe": True,
                      "canary_interval_s": 0.25})
    try:
        return _bench_serving_inner()
    finally:
        _flags.set_flags({"phase_attribution": False,
                          "capacity_attribution": False,
                          "canary_probe": False,
                          "canary_interval_s": 5.0})
        from paddle_tpu.observability import canary as _canary
        from paddle_tpu.observability import capacity as _capacity
        _canary.reset()
        _capacity.reset()


def _bench_serving_inner():
    import threading

    from paddle_tpu.serving import ModelManager

    SEQ_CLIENTS = 32
    GEN_CLIENTS, WINDOW = 8, 12
    N_REQ = {"mnist": 2560, "transformer": 640}
    BUCKETS = (1, 2, 4, 8, 16, 32)
    out = {"note": "CPU loopback, in-process (no sockets): isolates the "
                   "batching policy; on-chip: not measured",
           "seq_clients": SEQ_CLIENTS,
           "gen_clients": GEN_CLIENTS, "window": WINDOW,
           "buckets": list(BUCKETS)}
    rng = np.random.RandomState(0)

    for kind in ("mnist", "transformer"):
        pred = _serving_predictor(kind)
        requests = [_serving_request(kind, rng) for _ in range(64)]
        reqs = [requests[i % 64] for i in range(N_REQ[kind])]

        # cold first reply: fresh batcher, nothing warmed
        mgr_cold = ModelManager()
        mgr_cold.load(kind, "cold", predictor=pred, warm=False,
                      buckets=BUCKETS, activate=True, max_delay_ms=4.0)
        t0 = time.perf_counter()
        mgr_cold.infer(kind, reqs[0], timeout=600)
        cold_ms = (time.perf_counter() - t0) * 1e3
        mgr_cold.close()

        # sequential baseline: a serial server, one request start to
        # finish at a time (dispatch + readback inside the lock)
        for feed in reqs[:4]:
            np.asarray(pred.run(feed)[0])  # warm the batch-1 executable
        seq_lock = threading.Lock()

        def seq_submit(feed):
            with seq_lock:
                return np.asarray(pred.run(feed)[0])
        seq_qps, seq_p50, seq_p99, seq_err = _serving_load(
            seq_submit, reqs[:SEQ_CLIENTS * 15], SEQ_CLIENTS)

        # warm pool + continuous batching
        mgr = ModelManager()
        sm = mgr.load(kind, "1", predictor=pred, warm=True, buckets=BUCKETS,
                      activate=True, max_delay_ms=4.0,
                      max_queue_rows=8192)
        t0 = time.perf_counter()
        mgr.infer(kind, reqs[0], timeout=600)
        warm_ms = (time.perf_counter() - t0) * 1e3

        # golden canary in-window: record 2 goldens against the live
        # manager (trusted by construction: same build, same params),
        # then let the prober replay them through the REAL batcher
        # submit path concurrently with the measured load — probes are
        # tenant-tagged __canary__ so metering excludes them
        from paddle_tpu.observability import canary as _canary
        fetch = mgr.fetch_names(kind)
        cases = []
        for feed in reqs[:2]:
            outs = mgr.infer(kind, feed, timeout=600,
                             tenant=_canary.CANARY_TENANT)
            cases.append({"feeds": dict(feed),
                          "expect": list(zip(fetch, outs))})
        cp = _canary.prober()
        if cp is not None:
            cp.goldens.models[kind] = {"rtol": None, "cases": cases}
        _canary.register_target(
            f"bench/{kind}", kind,
            lambda feeds, tenant, _k=kind, _m=mgr, _f=fetch: list(zip(
                _f, _m.infer(_k, feeds, timeout=600, tenant=tenant))))
        _canary.maybe_start_from_flags()

        bat_qps, bat_p50, bat_p99, bat_err = _serving_load(
            lambda feed: mgr.submit(kind, feed),
            reqs, GEN_CLIENTS, window=WINDOW)
        # the swap below flips to a DIFFERENT predictor version — the
        # v1 goldens would (correctly) fail against v2, so the target
        # retires with its window
        _canary.unregister_target(f"bench/{kind}")

        res = {
            "seq_qps": seq_qps, "seq_p50_ms": seq_p50,
            "seq_p99_ms": seq_p99,
            "batched_qps": bat_qps, "batched_p50_ms": bat_p50,
            "batched_p99_ms": bat_p99,
            "speedup": round(bat_qps / max(seq_qps, 1e-9), 2),
            "cold_first_reply_ms": round(cold_ms, 1),
            "warm_pool_first_reply_ms": round(warm_ms, 1),
            "warm_pool": sm.warm_info,
            "dropped": len(seq_err) + len(bat_err),
        }
        rec = sm.batcher.stats.phases()
        if rec is not None:
            # where the batched p99 went: per-phase p99 + the slowest-
            # phase attribution (queue/assemble/dispatch/device/reply),
            # from ONE consistent snapshot of the live recorder
            psnap = rec.snapshot()
            res["phase_p99_ms"] = {name: ent["p99_ms"]
                                   for name, ent in psnap["phases"].items()}
            res["slowest_phase"] = psnap["slowest_phase"]
        cap = sm.batcher.stats.capacity()
        if cap is not None:
            # saturation anatomy over the measured window: which phase
            # binds, how utilized it ran, and the operational-law
            # ceiling the run implies (informational in bench_compare)
            csnap = cap.snapshot()
            res["utilization"] = csnap.get("utilization")
            res["headroom_frac"] = csnap.get("headroom_frac")
            res["binding_phase"] = csnap.get("binding_phase")
            res["predicted_max_qps"] = csnap.get("predicted_max_qps")

        if kind == "mnist":
            # hot-swap acceptance under full load: v2 warms, router
            # flips, v1 drains — zero drops, zero recompiles/misses in
            # the serving window (the warm phase compiles OUTSIDE the
            # counted window by design: warm_start entries install
            # without touching the miss counters)
            pred2 = _serving_predictor(kind, seed=2)
            before = _exec_counters()
            stop = threading.Event()
            swap_err = []
            n_ok = [0]

            def client_loop():
                i = 0
                while not stop.is_set():
                    try:
                        mgr.infer(kind, requests[i % 64], timeout=600)
                        n_ok[0] += 1
                    except Exception as e:
                        swap_err.append(repr(e)[:120])
                        return
                    i += 1
            ts = [threading.Thread(target=client_loop)
                  for _ in range(GEN_CLIENTS)]
            for t in ts:
                t.start()
            time.sleep(0.2)
            swap_info = mgr.swap(kind, "2", predictor=pred2,
                                 buckets=BUCKETS, max_delay_ms=4.0,
                                 max_queue_rows=8192)
            time.sleep(0.2)
            stop.set()
            for t in ts:
                t.join()
            after = _exec_counters()
            res["swap"] = {
                "served_during_swap": n_ok[0],
                "dropped": len(swap_err),
                "swap_ms": swap_info["ms"],
                "drained": swap_info["drained"],
                "recompiles_delta": {
                    k.split(".", 1)[1]: after[k] - before[k]
                    for k in after},
            }
        mgr.close()
        out[kind] = res

    # headline for tools/bench_compare.py: sustained batched QPS on the
    # mnist predictor (the ≥4×-vs-sequential acceptance metric)
    out["batched_qps"] = out["mnist"]["batched_qps"]
    out["speedup_vs_sequential"] = out["mnist"]["speedup"]
    out["serving_phase_p99_ms"] = out["mnist"].get("phase_p99_ms")
    # informational capacity keys (bench_compare carries headroom_frac
    # without gating on it)
    for k in ("utilization", "headroom_frac", "binding_phase",
              "predicted_max_qps"):
        if out["mnist"].get(k) is not None:
            out[k] = out["mnist"][k]
    # correctness-in-window headline: what the canary cost
    # (informational) and whether any probe mismatched (a secondary
    # gate — 0 on a healthy build)
    from paddle_tpu.observability import canary as _canary
    cp = _canary.prober(create=False)
    out["canary_overhead_frac"] = round(_canary.overhead_frac(), 6)
    out["canary_failures"] = (sum(
        s["failures"] for s in cp.streaks().values()) if cp else 0)

    # -- int8 serving arm (fused-dequant quantized matmul) ----------------
    # same two models through the quantize_int8 calibration pipeline:
    # accuracy parity (argmax agreement vs the f32 predictor — the
    # declared bar below), batched QPS, and the zero-steady-state-
    # recompile pin.  quant_accuracy_delta gates as a secondary in
    # tools/bench_compare.py (lower-better: a parity collapse is a
    # regression even when QPS holds)
    from paddle_tpu.kernels import quant as _quant
    INT8_PARITY_BAR = 0.05
    int8_res = {}
    worst = 0.0
    for kind in ("mnist", "transformer"):
        pred_f = _serving_predictor(kind)
        pred_q = _serving_predictor(kind, int8=True)
        reqs = [_serving_request(kind, rng) for _ in range(64)]
        agree, total = 0, 0
        for feed in reqs:
            a = np.asarray(pred_f.run(feed)[0])
            b = np.asarray(pred_q.run(feed)[0])
            ia = a.reshape(-1, a.shape[-1]).argmax(-1)
            ib = b.reshape(-1, b.shape[-1]).argmax(-1)
            agree += int((ia == ib).sum())
            total += ia.size
        delta = 1.0 - agree / max(total, 1)
        worst = max(worst, delta)
        mgr8 = ModelManager()
        mgr8.load(f"{kind}_int8", "1", predictor=pred_q, warm=True,
                  buckets=BUCKETS, activate=True, max_delay_ms=4.0,
                  max_queue_rows=8192)
        mgr8.infer(f"{kind}_int8", reqs[0], timeout=600)
        before = _exec_counters()
        qps8, p508, p998, err8 = _serving_load(
            lambda feed, _k=kind: mgr8.submit(f"{_k}_int8", feed),
            [reqs[i % 64] for i in range(256)], GEN_CLIENTS,
            window=WINDOW)
        after = _exec_counters()
        rec8 = {k.split(".", 1)[1]: after[k] - before[k] for k in after}
        mgr8.close()
        assert all(v == 0 for v in rec8.values()), rec8
        int8_res[kind] = {
            "batched_qps": qps8, "p50_ms": p508, "p99_ms": p998,
            "argmax_delta": round(delta, 4), "dropped": len(err8),
            "recompiles_in_window": rec8,
        }
    # fallback counters over the whole arm: how many quantized matmuls
    # launched vs fell back (quant.* — the /quantz payload's counters)
    int8_res["quant_counters"] = dict(_quant._COUNTERS)
    assert worst <= INT8_PARITY_BAR, (worst, INT8_PARITY_BAR)
    out["int8"] = int8_res
    out["quant_accuracy_delta"] = round(worst, 4)
    out["quant_parity_bar"] = INT8_PARITY_BAR
    return out


def bench_decode():
    """Autoregressive decode plane (paddle_tpu/decode) vs the naive
    re-prefill-every-token baseline.

    Model: a tiny decoder-only TransformerLM (serving-shaped geometry,
    tier-1 speed).  Two ways to generate the same greedy tokens:

    - ``reprefill``: the pre-decode-plane shape — every generated token
      re-runs the FULL causal forward over the whole prefix (padded to
      the prefill bucket ladder so the baseline also never recompiles),
      one request at a time.  This is what PR-8-style one-shot serving
      would do for generative traffic; per-token cost grows with the
      prefix.
    - ``continuous``: the DecodeEngine — paged KV cache, token-level
      continuous batching over ``max_slots`` slots, split
      prefill/decode dispatch — offered all requests at once
      (saturation: more requests than slots, so the batch runs full and
      join/leave churns at token granularity).

    Reported: tokens/s for both, per-token p99 (client-perceived
    inter-token interval for the engine; measured per-token wall for
    the baseline), the engine's zero-recompile pin over the serving
    window, and a greedy-parity artifact (engine tokens vs re-prefill
    argmax on shared prompts) — the acceptance's exactness evidence
    riding the same artifact as its speedup.  Off-TPU the whole config
    is CPU-measured policy evidence and labels itself ``analysis:
    true`` (the deepfm_fused precedent); the on-chip capture is ROADMAP
    item 1's ``decode`` row."""
    from paddle_tpu.core import flags as _flags

    # token-level tail anatomy (TTFT/TBT histograms, goodput, phases)
    # plus capacity attribution ride the saturation window — host-side
    # stamps, no device syncs (finally-restored like bench_serving)
    # golden canary rides the continuous window too (bench_serving
    # precedent): a recorded greedy completion replayed through the
    # real engine submit path, costed as canary_overhead_frac and
    # gated as canary_failures in tools/bench_compare.py
    # memory anatomy rides the same window: the engine registers its KV
    # block pool on the ledger, so the artifact carries the measured
    # bytes-per-token cost and the reconciliation residual
    _flags.set_flags({"phase_attribution": True,
                      "capacity_attribution": True,
                      "canary_probe": True,
                      "canary_interval_s": 0.25,
                      "memory_attribution": True})
    try:
        return _bench_decode_inner()
    finally:
        _flags.set_flags({"phase_attribution": False,
                          "capacity_attribution": False,
                          "canary_probe": False,
                          "canary_interval_s": 5.0,
                          "memory_attribution": False})
        from paddle_tpu.observability import canary as _canary
        from paddle_tpu.observability import capacity as _capacity
        from paddle_tpu.observability import memory as _memory
        _canary.reset()
        _capacity.reset()
        _memory.reset()


def _bench_decode_inner():
    import jax

    from paddle_tpu.core.executor import Executor
    from paddle_tpu.decode import (DecodeEngine, LMConfig, SamplingParams,
                                   TransformerLM)
    from paddle_tpu.serving import BucketLadder

    cfg = LMConfig(vocab=256, d_model=64, n_head=4, d_ffn=128, n_layer=2,
                   max_seq_len=128)
    lm = TransformerLM(cfg)
    params = lm.init_params(seed=7)
    BUCKETS = (32, 64, 128)
    SLOTS = 16
    rng = np.random.RandomState(0)
    # generative traffic shape: prompts 8..64 tokens, outputs 16..32 —
    # long enough that the baseline's per-token full re-forward over
    # the growing prefix pays its quadratic bill
    reqs = [(rng.randint(0, cfg.vocab, int(rng.randint(8, 64))).astype(
        "int32"), int(rng.randint(16, 33))) for _ in range(36)]
    total_tokens = sum(m for _, m in reqs)

    # -- re-prefill baseline ------------------------------------------------
    exe = Executor(training=False)
    plist = lm.param_list(params)

    ladder = BucketLadder(BUCKETS)

    def full_bucket(prefix):
        return ladder.snap(len(prefix))

    def build_full():
        def fn(feed, state, const):
            logits = lm.full_logits(const, feed[0], feed[1])
            return [logits], []
        return fn

    def reprefill_one(prompt, max_new):
        toks = list(int(t) for t in prompt)
        lats = []
        for _ in range(max_new):
            t0 = time.perf_counter()
            b = full_bucket(toks)
            padded = np.zeros((1, b), np.int32)
            padded[0, :len(toks)] = toks
            (lg,), _ = exe.run_callable(
                f"bench/reprefill/{b}", build_full,
                [padded, np.asarray([len(toks)], np.int32)], [], plist)
            last = np.asarray(lg)[0, len(toks) - 1]
            toks.append(int(last.argmax()))
            lats.append((time.perf_counter() - t0) * 1e3)
        return toks[len(prompt):], lats

    # warm the baseline ladder outside the measured window (prompt of
    # b-2 tokens snaps to bucket b)
    for b in BUCKETS:
        reprefill_one(np.zeros(b - 2, np.int32), 1)
    t0 = time.perf_counter()
    base_tokens = {}
    base_lats = []
    for i, (p, m) in enumerate(reqs):
        toks, lats = reprefill_one(p, m)
        base_tokens[i] = toks
        base_lats.extend(lats)
    base_wall = time.perf_counter() - t0
    base_tps = total_tokens / base_wall

    # -- continuous decode batching ----------------------------------------
    eng = DecodeEngine(lm, params, name="bench", max_slots=SLOTS,
                       block_tokens=16, prefill_buckets=BUCKETS,
                       max_queue=len(reqs) + 4,
                       # off-TPU the Pallas kernel runs in interpret
                       # mode — honest CPU policy numbers use the XLA
                       # gather path (the counted-fallback twin); on
                       # TPU the kernel path is the measured one
                       attn_impl=("xla" if jax.default_backend() != "tpu"
                                  else None))
    # warm: one request per prefill bucket + the decode step
    for b in BUCKETS:
        eng.generate(np.zeros(b - 2, np.int32), max_new_tokens=2)

    # golden canary in-window: record one greedy completion against the
    # warmed engine, then let the prober replay it through the REAL
    # submit path concurrently with the continuous window (probes are
    # __canary__-tenant streams, excluded from user metering)
    from paddle_tpu.observability import canary as _canary
    g_prompt, g_new = reqs[0][0], 8
    g_toks = eng.generate(g_prompt, max_new_tokens=g_new)["tokens"]
    cp = _canary.prober()
    if cp is not None:
        cp.goldens.models["bench"] = {"rtol": None, "cases": [{
            "feeds": {"prompt": np.asarray(g_prompt, np.int32),
                      "max_new_tokens": np.asarray(g_new, np.int32)},
            "expect": [("tokens", np.asarray(g_toks, np.int32))]}]}

    def _canary_decode(feeds, tenant, _eng=eng):
        h = _eng.submit(
            np.asarray(feeds["prompt"], np.int32),
            SamplingParams(max_new_tokens=int(
                np.asarray(feeds["max_new_tokens"]))),
            tenant=tenant)
        return [("tokens",
                 np.asarray(h.result(timeout=600)["tokens"], np.int32))]

    _canary.register_target("bench/decode", "bench", _canary_decode)
    _canary.maybe_start_from_flags()

    before = _exec_counters()
    t0 = time.perf_counter()
    handles = [eng.submit(p, SamplingParams(max_new_tokens=m))
               for p, m in reqs]
    results = [h.result(timeout=600) for h in handles]
    cont_wall = time.perf_counter() - t0
    after = _exec_counters()
    cont_tps = total_tokens / cont_wall
    token_p99 = eng.stats.token_ms.percentile(0.99)
    token_p50 = eng.stats.token_ms.percentile(0.50)
    lat = eng.stats.lat
    ttft_p99 = lat.ttft_ms.percentile(0.99) if lat else None
    ttft_p50 = lat.ttft_ms.percentile(0.50) if lat else None
    tbt_p99 = lat.tbt_ms.percentile(0.99) if lat else None
    goodput = lat.goodput() if lat else None
    phase_p99 = lat.phases.phase_p99_ms() if lat else None
    # capacity snapshot BEFORE close() (close unregisters the tracker)
    cap = eng.stats.capacity()
    cap_snap = cap.snapshot() if cap is not None else {}
    # memory ledger BEFORE close() (close unregisters the KV pool):
    # measured per-token KV cost + the reconciliation residual
    from paddle_tpu.observability import memory as _memory
    kv_bytes_per_token = round(
        eng._block_bytes / max(eng.cache.block_tokens, 1), 3)
    led = _memory.ledger(set_gauges=False)
    unattributed = sum(
        int(d.get("unattributed_bytes") or 0)
        for d in (led.get("devices") or {}).values())

    # greedy parity: continuous tokens == re-prefill argmax tokens
    mismatches = sum(1 for i, r in enumerate(results)
                    if r["tokens"] != base_tokens[i])
    # retire the canary target BEFORE close (a probe against a closed
    # engine would read as a correctness failure)
    _canary.unregister_target("bench/decode")
    canary_overhead = round(_canary.overhead_frac(), 6)
    canary_failures = (sum(s["failures"] for s in cp.streaks().values())
                       if cp else 0)
    eng.close()

    base_lats.sort()
    out = {
        "note": "CPU in-process: isolates the cache/batching policy; "
                "on-chip: not measured (ROADMAP S1)",
        "model": cfg.to_dict(),
        "requests": len(reqs), "total_tokens": total_tokens,
        "slots": SLOTS, "prefill_buckets": list(BUCKETS),
        "reprefill_tokens_per_sec": round(base_tps, 1),
        "reprefill_token_p50_ms": round(
            base_lats[len(base_lats) // 2], 3),
        "reprefill_token_p99_ms": round(
            base_lats[min(int(0.99 * len(base_lats)),
                          len(base_lats) - 1)], 3),
        "decode_tokens_per_sec": round(cont_tps, 1),
        "decode_token_p50_ms": token_p50,
        "decode_token_p99_ms": token_p99,
        # token-level tail SLOs (gated like throughput by
        # tools/bench_compare.py: decode_ttft_ms_p99 is lower-better)
        "decode_ttft_ms_p50": ttft_p50,
        "decode_ttft_ms_p99": ttft_p99,
        "decode_tbt_ms_p99": tbt_p99,
        "goodput": goodput,
        "phase_p99_ms": phase_p99,
        # saturation anatomy over the continuous window (informational
        # in bench_compare: headroom_frac never gates)
        "utilization": cap_snap.get("utilization"),
        "headroom_frac": cap_snap.get("headroom_frac"),
        "binding_phase": cap_snap.get("binding_phase"),
        "predicted_max_qps": cap_snap.get("predicted_max_qps"),
        # correctness-in-window: probe cost (informational) + mismatch
        # count (secondary gate, 0 on a healthy build)
        "canary_overhead_frac": canary_overhead,
        "canary_failures": canary_failures,
        # memory anatomy over the same window (informational in
        # bench_compare; kv_bytes_per_token is lower-better)
        "kv_bytes_per_token": kv_bytes_per_token,
        "unattributed_bytes": unattributed,
        "speedup_vs_reprefill": round(cont_tps / max(base_tps, 1e-9), 2),
        "parity": {"greedy_mismatched_requests": mismatches,
                   "requests_compared": len(reqs)},
        "recompiles_in_window": {
            k.split(".", 1)[1]: after[k] - before[k] for k in after},
    }
    assert mismatches == 0, out["parity"]
    if jax.default_backend() != "tpu":
        out["analysis"] = True
    return out


def bench_decode_prefix():
    """Prefix caching + overcommit (the refcounted block lifecycle,
    ``prefix_cache=True`` / ``overcommit=True``) vs the
    single-owner baseline, two legs:

    - **shared prefix**: 64 requests sharing an 87% system prompt
      (416 of 480 tokens), offered to a prefix-on engine vs the same
      engine with the flag off.  The prefix-on run prefills the shared
      blocks ONCE (request 0), every later admission reuses them and
      prefills only its 64-token suffix — ``saved_prefill_tokens`` must
      equal the analytic count EXACTLY (63 x 416) and the greedy tokens
      must match the prefix-off run per request.  Headline:
      ``decode_tokens_per_sec`` over the offered window plus mean TTFT
      both ways; ``prefix_hit_rate`` gates as a secondary in
      tools/bench_compare.py (a hit rate collapse is a regression even
      if throughput holds).  Zero recompiles in both measured windows
      (suffix lengths ride the resume bucket ladder).
    - **overcommit**: a block pool sized for HALF the offered streams'
      full reservation.  The reservation baseline can only run as many
      slots as full reservations fit; overcommit admits on the prompt
      footprint, grows block-by-block, and preempts the newest stream
      under pressure (token-exact re-prefill resume).  Measured: slot
      occupancy over the loaded window (queue nonempty) both ways —
      the overcommit run must hold >= 0.9 with >= 1 real preemption —
      completion of ALL streams, and zero token divergence between
      preempted-and-resumed streams and the reservation run.

    Off-TPU both legs are CPU policy evidence (``analysis: true``, the
    bench_decode precedent)."""
    from paddle_tpu.core import flags as _flags

    # token-level anatomy (TTFT histograms + goodput lane counters —
    # the occupancy evidence) rides both legs, finally-restored; memory
    # attribution rides too so the artifact carries measured KV cost
    _flags.set_flags({"phase_attribution": True,
                      "memory_attribution": True})
    try:
        return _bench_decode_prefix_inner()
    finally:
        _flags.set_flags({"phase_attribution": False,
                          "memory_attribution": False})
        from paddle_tpu.observability import memory as _memory
        _memory.reset()


def _bench_decode_prefix_inner():
    import threading

    import jax

    from paddle_tpu.decode import (DecodeEngine, LMConfig, SamplingParams,
                                   TransformerLM)

    impl = "xla" if jax.default_backend() != "tpu" else None

    # -- leg 1: shared-prefix prefill dedup --------------------------------
    # heavier geometry than bench_decode: the full prefill runs 512
    # dense rows where the suffix path runs 64, so model cost widens
    # the gap the cache exploits.  max_new=1: the first token samples
    # inside the prefill dispatch, so the window isolates exactly what
    # the prefix cache accelerates (decode-step throughput is
    # bench_decode's row; the overcommit leg below runs
    # decode-step-heavy traffic on a smaller model)
    cfg = LMConfig(vocab=256, d_model=192, n_head=4, d_ffn=768, n_layer=3,
                   max_seq_len=512)
    lm = TransformerLM(cfg)
    params = lm.init_params(seed=7)
    BS, SLOTS, N, MAX_NEW = 32, 16, 64, 1
    SHARED, UNIQ = 416, 64           # 13 shared blocks, 87% of the prompt
    BUCKETS = (512,)
    rng = np.random.RandomState(3)
    shared = rng.randint(0, cfg.vocab, SHARED).astype("int32")
    prompts = [np.concatenate([shared,
                               rng.randint(0, cfg.vocab, UNIQ).astype(
                                   "int32")]) for _ in range(N)]

    def run_shared(prefix_on):
        eng = DecodeEngine(lm, params,
                           name="bpx_on" if prefix_on else "bpx_off",
                           max_slots=SLOTS, block_tokens=BS,
                           prefill_buckets=BUCKETS, max_queue=N + 4,
                           attn_impl=impl, prefix_cache=prefix_on,
                           overcommit=False)
        # warm out-of-window: the full-prefill bucket + the decode step,
        # and (prefix on) the suffix executable — a second warm prompt
        # sharing the first one's block prefix dispatches prefill_sfx
        # on the same resume bucket the measured suffixes snap to
        w1 = np.full(510, 1, np.int32)
        eng.generate(w1, max_new_tokens=2)
        if prefix_on:
            w2 = w1.copy()
            w2[448:] = 2             # diverge at block 14: 62-token suffix
            eng.generate(w2, max_new_tokens=2)
        ps = eng._pstats
        saved0 = ps.saved_prefill_tokens.value if ps else 0
        hits0 = ps.prefix_hits.value if ps else 0
        lk0 = ps.prefix_lookups.value if ps else 0
        before = _exec_counters()
        ttfts = [0.0] * N
        threads = []

        def first_tok(i, h, t0):
            h.next_token(timeout=600)
            ttfts[i] = (time.perf_counter() - t0) * 1e3

        t_start = time.perf_counter()
        # request 0 goes first and we WAIT for its first token: its
        # prefill registers the shared blocks, so every later request
        # hits them — the analytic saved-token count stays exact.  The
        # prefix-off run follows the same staged protocol for fairness.
        h0 = eng.submit(prompts[0], SamplingParams(max_new_tokens=MAX_NEW))
        h0.next_token(timeout=600)
        ttfts[0] = (time.perf_counter() - t_start) * 1e3
        handles = [h0]
        for i in range(1, N):
            t0 = time.perf_counter()
            h = eng.submit(prompts[i],
                           SamplingParams(max_new_tokens=MAX_NEW))
            th = threading.Thread(target=first_tok, args=(i, h, t0))
            th.start()
            threads.append(th)
            handles.append(h)
        results = [h.result(timeout=600) for h in handles]
        for th in threads:
            th.join()
        wall = time.perf_counter() - t_start
        after = _exec_counters()
        z = eng.decodez()
        leaked = eng.cache.allocator.leaked(
            eng.prefix.parked_blocks if eng.prefix else 0)
        out = {
            "tps": (N * MAX_NEW) / wall,
            "ttft_mean_ms": sum(ttfts) / N,
            "tokens": [r["tokens"] for r in results],
            "saved": (ps.saved_prefill_tokens.value - saved0) if ps else 0,
            "hits": (ps.prefix_hits.value - hits0) if ps else 0,
            "lookups": (ps.prefix_lookups.value - lk0) if ps else 0,
            "leaked": leaked,
            "prefix_card": z.get("prefix_cache"),
            "recompiles": {k.split(".", 1)[1]: after[k] - before[k]
                           for k in after},
        }
        # memory ledger BEFORE close() (close unregisters the KV pool)
        from paddle_tpu.observability import memory as _memory
        out["kv_bytes_per_token"] = round(
            eng._block_bytes / max(eng.cache.block_tokens, 1), 3)
        led = _memory.ledger(set_gauges=False)
        out["unattributed_bytes"] = sum(
            int(d.get("unattributed_bytes") or 0)
            for d in (led.get("devices") or {}).values())
        eng.close()
        return out

    off = run_shared(False)
    on = run_shared(True)
    assert on["tokens"] == off["tokens"], \
        "prefix-on greedy tokens diverged from prefix-off"
    expect_saved = (N - 1) * SHARED
    assert on["saved"] == expect_saved, (on["saved"], expect_saved)
    assert on["leaked"] == 0 and off["leaked"] == 0, (on["leaked"],
                                                      off["leaked"])
    for leg in (off, on):
        assert all(v == 0 for v in leg["recompiles"].values()), \
            leg["recompiles"]
    hit_rate = on["hits"] / max(on["lookups"], 1)

    # -- leg 2: overcommit + preemption under a half-sized pool ------------
    # smaller model (decode steps dominate this leg, the policy under
    # test is block accounting, not matmul throughput)
    cfg2 = LMConfig(vocab=256, d_model=128, n_head=4, d_ffn=256,
                    n_layer=2, max_seq_len=512)
    lm2 = TransformerLM(cfg2)
    params2 = lm2.init_params(seed=11)
    BS2, SLOTS2, N2, M2, P2 = 16, 16, 24, 112, 16
    FULL = (P2 + M2 + BS2 - 1) // BS2          # reservation: 8 blocks
    POOL = 1 + (N2 // 2) * FULL                # half the offered streams
    BUCKETS2 = (16, 32, 64, 128)
    prompts2 = [rng.randint(0, cfg2.vocab, P2).astype("int32")
                for _ in range(N2)]

    def run_overcommit(overcommit_on):
        eng = DecodeEngine(lm2, params2,
                           name="boc_on" if overcommit_on else "boc_off",
                           max_slots=SLOTS2, block_tokens=BS2,
                           num_blocks=POOL, prefill_buckets=BUCKETS2,
                           max_queue=N2 + 4, attn_impl=impl,
                           prefix_cache=False, overcommit=overcommit_on)
        # warm every prefill bucket: preemption re-prefill lengths
        # (P2..P2+M2-1) snap onto the same ladder, so the churny
        # window stays recompile-free too
        for b in BUCKETS2:
            eng.generate(np.full(b - 2, 1, np.int32), max_new_tokens=2)
        lat = eng.stats.lat
        before = _exec_counters()
        live0, pad0 = lat.live_slot_steps.value, lat.pad_slot_steps.value
        loaded = {"live": live0, "pad": pad0}
        done = threading.Event()

        def monitor():
            # loaded-window occupancy: lane counters at the LAST
            # instant the queue was nonempty (the drain tail, where
            # slots empty because no work is left, must not read as
            # an occupancy loss)
            while not done.is_set():
                if eng.stats.queue.value > 0:
                    loaded["live"] = lat.live_slot_steps.value
                    loaded["pad"] = lat.pad_slot_steps.value
                time.sleep(0.002)

        mon = threading.Thread(target=monitor)
        mon.start()
        t0 = time.perf_counter()
        handles = [eng.submit(p, SamplingParams(max_new_tokens=M2))
                   for p in prompts2]
        results = [h.result(timeout=600) for h in handles]
        wall = time.perf_counter() - t0
        done.set()
        mon.join()
        after = _exec_counters()
        lw, pw = loaded["live"] - live0, loaded["pad"] - pad0
        ps = eng._pstats
        leaked = eng.cache.allocator.leaked()
        out = {
            "tps": sum(r["n_tokens"] for r in results) / wall,
            "occupancy": lw / max(lw + pw, 1),
            "tokens": [r["tokens"] for r in results],
            "completed": sum(1 for r in results
                             if r["finish"] == "length"),
            "preempts": ps.preempts.value if ps else 0,
            "resumes": ps.preempt_resumes.value if ps else 0,
            "reprefill_tokens": ps.reprefill_tokens.value if ps else 0,
            "leaked": leaked,
            "recompiles": {k.split(".", 1)[1]: after[k] - before[k]
                           for k in after},
        }
        eng.close()
        return out

    oc_off = run_overcommit(False)
    oc_on = run_overcommit(True)
    # token-exactness across preemption: greedy decode is per-stream
    # deterministic, so the reservation run IS the uninterrupted truth
    divergent = sum(1 for a, b in zip(oc_on["tokens"], oc_off["tokens"])
                    if a != b)
    assert divergent == 0, f"{divergent} preempted streams diverged"
    assert oc_on["completed"] == N2 and oc_off["completed"] == N2
    assert oc_on["preempts"] >= 1, "overcommit leg saw no preemption"
    assert oc_on["leaked"] == 0 and oc_off["leaked"] == 0
    assert all(v == 0 for v in oc_on["recompiles"].values()), \
        oc_on["recompiles"]

    out = {
        "note": "CPU in-process: isolates the block-lifecycle policy "
                "(prefix dedup, COW, preemption); on-chip: not "
                "measured (ROADMAP S1)",
        "model": cfg.to_dict(),
        "overcommit_model": cfg2.to_dict(),
        "requests": N, "shared_prefix_tokens": SHARED,
        "unique_tail_tokens": UNIQ, "max_new": MAX_NEW,
        "slots": SLOTS, "block_tokens": BS,
        # headline (gated by tools/bench_compare.py METRIC_KEYS)
        "decode_tokens_per_sec": round(on["tps"], 1),
        "prefix_off_tokens_per_sec": round(off["tps"], 1),
        "prefix_speedup": round(on["tps"] / max(off["tps"], 1e-9), 2),
        "ttft_mean_ms_prefix_on": round(on["ttft_mean_ms"], 2),
        "ttft_mean_ms_prefix_off": round(off["ttft_mean_ms"], 2),
        "ttft_speedup": round(off["ttft_mean_ms"] /
                              max(on["ttft_mean_ms"], 1e-9), 2),
        # secondary gate (bench_compare SECONDARY_GATE_KEYS): a hit
        # rate collapse is a regression even when throughput holds
        "prefix_hit_rate": round(hit_rate, 4),
        # memory anatomy over the prefix-on window (informational in
        # bench_compare; kv_bytes_per_token is lower-better)
        "kv_bytes_per_token": on["kv_bytes_per_token"],
        "unattributed_bytes": on["unattributed_bytes"],
        "saved_prefill_tokens": on["saved"],
        "saved_prefill_tokens_expected": expect_saved,
        "prefix_cache": on["prefix_card"],
        "recompiles_in_window": on["recompiles"],
        "overcommit": {
            "offered_streams": N2, "slots": SLOTS2,
            "pool_blocks": POOL, "full_blocks_per_stream": FULL,
            "overcommit_tokens_per_sec": round(oc_on["tps"], 1),
            "reservation_tokens_per_sec": round(oc_off["tps"], 1),
            "occupancy_overcommit": round(oc_on["occupancy"], 4),
            "occupancy_reservation": round(oc_off["occupancy"], 4),
            "preempts": oc_on["preempts"],
            "resumes": oc_on["resumes"],
            "reprefill_tokens": oc_on["reprefill_tokens"],
            "divergent_streams": divergent,
            "completed_streams": oc_on["completed"],
        },
    }
    assert out["prefix_speedup"] >= 2.0, out["prefix_speedup"]
    assert out["ttft_speedup"] >= 2.0, out["ttft_speedup"]
    assert oc_on["occupancy"] >= 0.9, oc_on["occupancy"]
    if jax.default_backend() != "tpu":
        out["analysis"] = True
    return out


def bench_decode_kv_int8():
    """Quantized KV residency (``cache_dtype="int8"``) vs the
    fp32 cache at the SAME pool byte budget, under overcommit.

    The int8 cache stores paged blocks as int8 codes plus a
    per-block-per-head scale pool, cutting bytes-per-block ~4x
    (codes are a quarter of f32; the scale rows are noise), so the same
    HBM budget holds ~4x the blocks and overcommit admits far more
    resident sequences before preempting.  Two legs, identical offered
    load and identical pool BYTES (the int8 engine gets the block count
    that budget buys):

    - measured: decode tokens/s, mean resident sequences per decode
      step over the run (live-lane counters), kv_bytes_per_token
      (dtype-aware: engine block bytes include the scale pools), and
      greedy divergence vs the fp32 run — the first token must match
      (prefill attention runs on fresh f32 K/V either way) and the
      per-stream matched-prefix fraction is reported (quantization
      noise compounds over a greedy chain; the BOUND is the exact
      first token + the reported tail).
    - pinned: byte ratio <= 0.55, resident-sequence gain >= 1.8, all
      streams complete both ways, zero steady-state recompiles, zero
      leaked blocks.

    Off-TPU this is CPU policy evidence (``analysis: true``, the
    bench_decode precedent — the paged kernel's VMEM dequant is the
    on-chip capture, ROADMAP item 1 'decode_kv_int8' row)."""
    from paddle_tpu.core import flags as _flags

    _flags.set_flags({"phase_attribution": True,
                      "memory_attribution": True})
    try:
        return _bench_decode_kv_int8_inner()
    finally:
        _flags.set_flags({"phase_attribution": False,
                          "memory_attribution": False})
        from paddle_tpu.observability import memory as _memory
        _memory.reset()


def _bench_decode_kv_int8_inner():
    import threading

    import jax

    from paddle_tpu.decode import (DecodeEngine, LMConfig, SamplingParams,
                                   TransformerLM)
    from paddle_tpu.decode.cache import PagedKVCache

    impl = "xla" if jax.default_backend() != "tpu" else None
    cfg = LMConfig(vocab=256, d_model=128, n_head=4, d_ffn=256, n_layer=2,
                   max_seq_len=256)
    lm = TransformerLM(cfg)
    params = lm.init_params(seed=5)
    BS, SLOTS, N, M, P = 16, 16, 24, 48, 16
    FULL = (P + M + BS - 1) // BS              # 4 blocks per full stream
    POOL_F32 = 1 + 4 * FULL                    # fp32: ~4 resident streams
    BUCKETS = (16, 32, 64)
    rng = np.random.RandomState(9)
    prompts = [rng.randint(0, cfg.vocab, P).astype("int32")
               for _ in range(N)]

    def run(dtype, num_blocks):
        eng = DecodeEngine(lm, params, name=f"bkv_{dtype}",
                           max_slots=SLOTS, block_tokens=BS,
                           num_blocks=num_blocks,
                           prefill_buckets=BUCKETS, max_queue=N + 4,
                           attn_impl=impl, prefix_cache=False,
                           overcommit=True, cache_dtype=dtype)
        # warm every prefill bucket (preemption re-prefill lengths
        # P..P+M-1 snap onto the same ladder) plus the decode step
        for b in BUCKETS:
            eng.generate(np.full(b - 2, 1, np.int32), max_new_tokens=2)
        lat = eng.stats.lat
        before = _exec_counters()
        live0 = lat.live_slot_steps.value
        steps0 = eng.stats.steps.value
        t0 = time.perf_counter()
        handles = [eng.submit(p, SamplingParams(max_new_tokens=M))
                   for p in prompts]
        results = [h.result(timeout=600) for h in handles]
        wall = time.perf_counter() - t0
        after = _exec_counters()
        steps = eng.stats.steps.value - steps0
        out = {
            "tps": sum(r["n_tokens"] for r in results) / wall,
            "tokens": [r["tokens"] for r in results],
            "completed": sum(1 for r in results
                             if r["finish"] == "length"),
            # mean live slots per decode step: the residency the pool
            # byte budget actually sustained over the run
            "resident_mean": ((lat.live_slot_steps.value - live0)
                              / max(steps, 1)),
            "kv_bytes_per_token": round(eng._block_bytes / BS, 3),
            "pool_bytes": eng.cache.nbytes,
            "num_blocks": eng.cache.num_blocks,
            "preempts": eng._pstats.preempts.value,
            "leaked": eng.cache.allocator.leaked(),
            "recompiles": {k.split(".", 1)[1]: after[k] - before[k]
                           for k in after},
        }
        eng.close()
        return out

    f32 = run("float32", POOL_F32)
    # same byte budget: how many int8 blocks (codes + scale rows) the
    # fp32 pool's bytes buy
    probe = PagedKVCache(cfg.n_layer, cfg.n_head, cfg.head_dim, 2, BS,
                         dtype="int8")
    POOL_I8 = max(int(f32["pool_bytes"] // (probe.nbytes // 2)), 2)
    q = run("int8", POOL_I8)

    assert q["pool_bytes"] <= f32["pool_bytes"], (q["pool_bytes"],
                                                  f32["pool_bytes"])
    assert f32["completed"] == N and q["completed"] == N
    assert f32["leaked"] == 0 and q["leaked"] == 0
    for leg in (f32, q):
        assert all(v == 0 for v in leg["recompiles"].values()), \
            leg["recompiles"]
    byte_ratio = q["kv_bytes_per_token"] / f32["kv_bytes_per_token"]
    resident_gain = q["resident_mean"] / max(f32["resident_mean"], 1e-9)
    # greedy divergence vs the fp32 run (the uninterrupted truth:
    # preemption resume is token-exact).  The first token samples
    # inside prefill on fresh f32 K/V, so it is exact by construction;
    # later tokens read the quantized cache and may drift
    matched = []
    first_mismatch = 0
    for a, b in zip(q["tokens"], f32["tokens"]):
        if a[:1] != b[:1]:
            first_mismatch += 1
        m = 0
        for x, y in zip(a, b):
            if x != y:
                break
            m += 1
        matched.append(m / max(len(b), 1))
    assert first_mismatch == 0, \
        f"{first_mismatch} streams diverged at the (exact) first token"

    out = {
        "note": "CPU in-process: isolates the quantized-cache residency "
                "policy; on-chip: not measured (ROADMAP S1)",
        "model": cfg.to_dict(),
        "requests": N, "prompt_tokens": P, "max_new": M,
        "slots": SLOTS, "block_tokens": BS,
        "pool_bytes": f32["pool_bytes"],
        "blocks_fp32": f32["num_blocks"],
        "blocks_int8": q["num_blocks"],
        # headline
        "decode_tokens_per_sec": round(q["tps"], 1),
        "fp32_tokens_per_sec": round(f32["tps"], 1),
        # lower-better + informational in bench_compare
        "kv_bytes_per_token": q["kv_bytes_per_token"],
        "kv_bytes_per_token_fp32": f32["kv_bytes_per_token"],
        "kv_byte_ratio": round(byte_ratio, 4),
        "resident_mean_int8": round(q["resident_mean"], 2),
        "resident_mean_fp32": round(f32["resident_mean"], 2),
        "resident_gain": round(resident_gain, 2),
        "preempts_fp32": f32["preempts"],
        "preempts_int8": q["preempts"],
        "greedy_divergence": {
            "first_token_mismatches": first_mismatch,
            "matched_prefix_frac_mean": round(
                sum(matched) / max(len(matched), 1), 4),
            "matched_prefix_frac_min": round(min(matched), 4),
            "fully_matched_streams": sum(1 for m in matched if m >= 1.0),
        },
        "recompiles_in_window": q["recompiles"],
    }
    assert byte_ratio <= 0.55, byte_ratio
    assert resident_gain >= 1.8, resident_gain
    if jax.default_backend() != "tpu":
        out["analysis"] = True
    return out


A100_RESNET50_IMG_S = 2500.0
A100_TRANSFORMER_TOK_S = 50000.0


def _compile_cache_child_main():
    """Grandchild for bench_compile_cache: one fresh process builds the
    LeNet train program and reports its time-to-first-step (startup →
    first trained batch readback) plus the persistent-cache counters
    that explain it.  FLAGS_compile_cache_dir comes in via env."""
    import os
    import sys

    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu as fluid  # noqa: F401
    from paddle_tpu.core.executor import Executor, Scope
    from paddle_tpu.models import mnist

    B = 64
    prog, startup, (feeds, loss, acc) = _fresh(lambda: mnist.build())
    rng = np.random.RandomState(0)
    feed = {"pixel": rng.randn(B, 1, 28, 28).astype("float32"),
            "label": rng.randint(0, 10, (B, 1)).astype("int64")}
    scope = Scope()
    exe = Executor()
    exe.run(startup, scope=scope)
    t0 = time.perf_counter()
    if os.environ.get("PADDLE_TPU_BENCH_CC_WARMSTART"):
        # the elastic-rejoin shape: hydrate explicitly, then step
        exe.warm_start(prog, feed_specs=feed, fetch_list=[loss.name],
                       scope=scope)
    (lv,) = exe.run(prog, feed=feed, fetch_list=[loss.name], scope=scope)
    float(np.asarray(lv))
    ttfs = time.perf_counter() - t0
    from paddle_tpu import observability as obs
    c = obs.stats.default_registry().to_dict()
    print("CCCHILD=" + json.dumps({
        "ttfs_s": round(ttfs, 4),
        "persistent_hits": c.get("executor.persistent_hits", 0),
        "persistent_misses": c.get("executor.persistent_misses", 0)}),
        flush=True)
    sys.stdout.flush()


def bench_compile_cache():
    """Cold-process vs warm-process time-to-first-step for the LeNet
    train program (CPU backend, no TPU needed): process A compiles with
    ``FLAGS_compile_cache_dir`` set and serializes its executables;
    process B — a fresh interpreter, the elastic-restart/bench-respawn
    shape — hydrates them from disk.  ``baseline`` runs with the cache
    disabled (the pre-change behavior); cold-vs-baseline bounds the
    store overhead, cold/warm is the restart win the persistent cache
    exists for."""
    import os
    import subprocess
    import sys
    import tempfile

    here = os.path.dirname(os.path.abspath(__file__))

    def child(cache_dir, warm_start=False):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        # this config times the framework's own .ptcc tier: jax's
        # persistent cache (always on otherwise, at a fixed path) would
        # serve the "cold" child from an earlier bench run
        env["JAX_ENABLE_COMPILATION_CACHE"] = "0"
        env.pop("FLAGS_compile_cache_dir", None)
        env.pop("PADDLE_TPU_BENCH_CC_WARMSTART", None)
        if cache_dir:
            env["FLAGS_compile_cache_dir"] = cache_dir
        if warm_start:
            env["PADDLE_TPU_BENCH_CC_WARMSTART"] = "1"
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--compile-cache-child"],
            env=env, cwd=here, capture_output=True, text=True, timeout=600)
        for line in out.stdout.splitlines():
            if line.startswith("CCCHILD="):
                return json.loads(line[len("CCCHILD="):])
        raise RuntimeError(
            f"compile-cache child failed rc={out.returncode}: "
            f"{out.stderr[-400:]}")

    with tempfile.TemporaryDirectory(prefix="ptcc_bench_") as d:
        baseline = child(None)
        cold = child(d)
        warm = child(d)
        warm_api = child(d, warm_start=True)

    assert warm["persistent_hits"] > 0, warm
    assert cold["persistent_misses"] > 0, cold
    speedup = cold["ttfs_s"] / max(warm["ttfs_s"], 1e-9)
    return {
        "baseline_ttfs_s": baseline["ttfs_s"],
        "cold_ttfs_s": cold["ttfs_s"],
        "warm_ttfs_s": warm["ttfs_s"],
        "warm_api_ttfs_s": warm_api["ttfs_s"],
        "warm_persistent_hits": warm["persistent_hits"],
        "cold_vs_warm_speedup": round(speedup, 2),
    }


def _checkpoint_child_main():
    """Child for bench_checkpoint: one train loop measured three ways —
    no checkpointing (baseline), ASYNC sharded snapshots every step
    (paddle_tpu/checkpoint/ — the no-pause path under test), and
    pause-the-world ``io.save_persistables`` every step (the legacy
    discipline).  The headline ``ckpt_overhead_frac`` is the async
    path's relative step-wall cost over baseline; the counters prove
    the step loop never blocked on serialization (zero faults, commits
    happened on the background thread, inflight pressure degrades to
    skipped snapshots — never to a stalled step)."""
    import os
    import sys
    import tempfile

    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu as fluid
    import paddle_tpu.checkpoint as pckpt
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.executor import Executor, Scope
    from paddle_tpu.core.program import Program, program_guard

    B, H = 2048, 512
    steps = int(os.environ.get("PADDLE_TPU_BENCH_CKPT_STEPS", "60"))
    # snapshot cadence: every N steps.  The overhead fraction is only
    # meaningful at a cadence where the ~state-size background write
    # fits inside its window — snapshotting 3 MB of state every 3 ms
    # step would measure CPU contention of a nonsense configuration,
    # not the async design.  10 steps of this model ≈ an order of
    # magnitude above the measured save wall.
    every = int(os.environ.get("PADDLE_TPU_BENCH_CKPT_EVERY", "10"))

    def build():
        prog, startup = Program(), Program()
        with program_guard(prog, startup), unique_name.guard():
            x = fluid.layers.data("x", [H])
            y = fluid.layers.data("y", [1])
            h = fluid.layers.fc(x, H, act="relu")
            pred = fluid.layers.fc(h, 1)
            diff = fluid.layers.elementwise_sub(pred, y)
            loss = fluid.layers.mean(fluid.layers.square(diff))
            fluid.optimizer.Adam(1e-3).minimize(loss)
        return prog, startup, loss

    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(B, H).astype("float32"),
            "y": rng.randn(B, 1).astype("float32")}

    class Mode:
        """One measured training context.  The three modes run
        INTERLEAVED in chunks of ``every`` steps — a sequential
        block-per-mode layout lets ambient load drift on a shared CI
        box land entirely on one mode and masquerade as (or mask) the
        checkpoint overhead; rotating chunks spreads it evenly."""

        def __init__(self, kind):
            self.kind = kind
            self.prog, startup, self.loss = build()
            self.scope, self.exe = Scope(), Executor()
            self.exe.run(startup, scope=self.scope)
            (lv,) = self.exe.run(self.prog, feed=feed,
                                 fetch_list=[self.loss], scope=self.scope)
            float(np.asarray(lv))                 # warmup compile
            self.snap = None
            self.dir = None
            if kind == "async":
                self.dir = tempfile.mkdtemp(prefix="ptckpt_bench_")
                self.snap = pckpt.scope_snapshotter(self.dir, self.prog,
                                                    self.scope, keep=4)
            elif kind == "pause":
                self.dir = tempfile.mkdtemp(prefix="ptckpt_pause_")
            self.walls = []
            self.n = 0

        def chunk(self):
            for _ in range(every):
                self.n += 1
                t0 = time.perf_counter()
                (lv,) = self.exe.run(self.prog, feed=feed,
                                     fetch_list=[self.loss],
                                     scope=self.scope)
                float(np.asarray(lv))             # per-step readback
                if self.n % every == 0:
                    if self.kind == "async":
                        self.snap.snapshot(self.n)
                    elif self.kind == "pause":
                        fluid.io.save_persistables(self.exe, self.dir,
                                                   self.prog)
                self.walls.append(time.perf_counter() - t0)

        def summary(self):
            # FULL mean, deliberately untrimmed: the pause-the-world
            # mode's cost lives entirely in its every-Nth-step spikes —
            # trimming outliers would trim away the measured thing
            mean_ms = sum(self.walls) / len(self.walls) * 1e3
            p99_ms = sorted(self.walls)[min(len(self.walls) - 1,
                                            int(len(self.walls) * 0.99))
                                        ] * 1e3
            stats = {}
            if self.snap is not None:
                self.snap.flush(timeout=60)
                st = self.snap.status()
                stats = {"snapshots": st["snapshots"],
                         "skipped_inflight": st["skipped_inflight"],
                         "faults": st["faults"],
                         "complete_steps": len(
                             pckpt.complete_steps(self.dir)),
                         "last_save_ms": st["save_ms"],
                         "collect_ms": st["collect_ms"],
                         "bytes": st["bytes"]}
                self.snap.close()
            return mean_ms, p99_ms, stats

    modes = [Mode("base"), Mode("async"), Mode("pause")]
    for _ in range(max(1, steps // every)):
        for m in modes:
            m.chunk()
    base_ms, base_p99, _ = modes[0].summary()
    async_ms, async_p99, async_stats = modes[1].summary()
    pause_ms, pause_p99, _ = modes[2].summary()
    out = {
        "steps": steps, "batch": B, "snapshot_every": every,
        "base_step_ms": round(base_ms, 3),
        "async_step_ms": round(async_ms, 3),
        "pause_step_ms": round(pause_ms, 3),
        "base_p99_ms": round(base_p99, 3),
        "async_p99_ms": round(async_p99, 3),
        "pause_p99_ms": round(pause_p99, 3),
        "ckpt_overhead_frac": round(max(0.0, async_ms - base_ms)
                                    / base_ms, 4),
        "pause_overhead_frac": round(max(0.0, pause_ms - base_ms)
                                     / base_ms, 4),
        "async": async_stats,
    }
    assert async_stats["faults"] == 0, out
    assert async_stats["complete_steps"] > 0, out
    print("CKPTBENCH=" + json.dumps(out), flush=True)
    sys.stdout.flush()


def bench_checkpoint():
    """Async-snapshot overhead vs pause-the-world checkpointing on the
    step loop (CPU-measured; no TPU needed).  Subprocess for a clean
    metrics registry.  Headline: ``ckpt_overhead_frac`` — the async
    sharded-snapshot path's step-wall overhead over the no-checkpoint
    baseline (acceptance: < 5%); ``pause_overhead_frac`` shows what the
    legacy synchronous save costs on the same loop."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--checkpoint-child"],
        env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
        capture_output=True, text=True, timeout=600)
    for line in out.stdout.splitlines():
        if line.startswith("CKPTBENCH="):
            return json.loads(line[len("CKPTBENCH="):])
    raise RuntimeError(
        f"checkpoint child failed rc={out.returncode}: "
        f"{out.stderr[-500:]}")


def _recovery_child_main():
    """Child for bench_recovery: MTTR of a pserver hard-kill, measured
    two ways over the SAME tiny sync-mode fleet + deterministic batch
    stream (tests/chaos_runner.py workers):

    - **supervised** — the ``distributed.supervisor`` owns the fleet;
      ps-0 is fault-armed to die mid-round; the supervisor detects the
      death, rolls the group back to the newest COMPLETE sharded
      checkpoint and resumes the trainer at the cut, zero human steps.
    - **manual** — the runner-choreographed baseline (the PR-11 chaos
      discipline): a script polls worker liveness at the 0.5 s cadence
      a shell runner realistically would, tears the fleet down, brings
      a fresh one up on new ports, waits for readiness, restarts the
      trainer at the cut.

    MTTR = the KILL moment (the dying pserver's flight dump stamps its
    ``fault_kill`` wall time — the same anchor in both modes) → first
    post-resume trainer step landing (the progress file's first
    write), with loss-curve parity against the no-fault local run
    asserted in BOTH modes — this measures kill-to-PARITY-resume, not
    kill-to-any-step.  The supervisor's wins are (a) sub-tick death
    detection vs the scripted poll cadence and (b) pipelined respawn:
    the trainer's process/import startup overlaps the replacement
    pservers' (``after_live=False``) instead of serializing behind a
    readiness wait."""
    import glob
    import os
    import subprocess
    import sys
    import tempfile

    import jax
    jax.config.update("jax_platforms", "cpu")

    repo = os.path.dirname(os.path.abspath(__file__))
    tests = os.path.join(repo, "tests")
    sys.path.insert(0, tests)
    runner = os.path.join(tests, "chaos_runner.py")
    pythonpath = os.pathsep.join(
        [repo, tests, os.environ.get("PYTHONPATH", "")])
    total = int(os.environ.get("PADDLE_TPU_BENCH_RECOVERY_STEPS", "10"))
    ckpt_every, kill_round = 2, 6

    from dist_model import build, free_ports, run_local
    local_losses, _ = run_local(total, build_fn=lambda: build(lr=0.05))

    def stitched_ok(progress_paths):
        got = {}
        for p in progress_paths:
            rec = json.load(open(p))
            start = rec["global_step"] - rec["step"]
            for j, l in enumerate(rec["losses"]):
                got[start + j + 1] = l
        if sorted(got) != list(range(1, total + 1)):
            return False
        return bool(np.allclose([got[i] for i in range(1, total + 1)],
                                local_losses, rtol=1e-4, atol=1e-5))

    def watch_first_write(path, deadline_s=300.0):
        """Poll tightly for the file's first complete write; returns
        its wall timestamp (mtime — finer than the poll cadence)."""
        deadline = time.monotonic() + deadline_s
        while time.monotonic() < deadline:
            try:
                json.load(open(path))
                return os.stat(path).st_mtime
            except (OSError, ValueError):
                time.sleep(0.005)
        raise RuntimeError(f"no resume write at {path}")

    def kill_ts(flight_dir):
        """The fault_kill wall time from the dying pserver's flight
        dump — the shared MTTR anchor for both modes."""
        for path in sorted(glob.glob(os.path.join(flight_dir,
                                                  "flight_*.json"))):
            for ev in json.load(open(path)).get("events", ()):
                if ev.get("msg") == "fault_kill":
                    return ev["ts"]
        raise RuntimeError(f"no fault_kill note under {flight_dir}")

    # ---- supervised: the self-healing path ------------------------------
    from paddle_tpu.distributed.supervisor import (FleetSpec, RoleSpec,
                                                   Supervisor)
    sup_tmp = tempfile.mkdtemp(prefix="ptbench_rec_sup_")
    root = os.path.join(sup_tmp, "ck")
    common = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": pythonpath,
              "PADDLE_PSERVER_ENDPOINTS": "{ps_logicals}",
              "FLAGS_pserver_registry": "{registry}",
              "CHAOS_CKPT_DIR": "{checkpoint_root}",
              "CHAOS_CKPT_SHARDED": "1", "CHAOS_OPTIMIZER": "sgd"}
    spec = FleetSpec(
        registry="auto", checkpoint_root=root,
        rollback_roles=["ps", "trainer"], name="bench-recovery",
        roles={
            "ps": RoleSpec(
                count=2, logical="auto", health_role="PSERVER",
                argv=[sys.executable, runner],
                env={**common, "PADDLE_TRAINING_ROLE": "PSERVER",
                     "PADDLE_CURRENT_ENDPOINT": "{logical}",
                     "PADDLE_BIND_ENDPOINT": "127.0.0.1:0",
                     "CHAOS_CKPT_EVERY": str(ckpt_every)},
                env_once={0: {"FLAGS_fault_inject":
                              f"kill_after:apply_round:n={kill_round}",
                              "FLAGS_flight_record_dir": os.path.join(
                                  sup_tmp, "flight")}},
                backoff_s=0.05, action_deadline_s=180.0),
            # after_live=False: the rollback respawns the trainer
            # CONCURRENTLY with the replacement pservers (pipelined
            # recovery) — the registry-polling transport absorbs the
            # ordering, and resume_step is stable while the fleet is
            # down
            "trainer": RoleSpec(
                count=1, after=["ps"], after_live=False, done_ok=True,
                argv=[sys.executable, runner],
                env={**common, "PADDLE_TRAINING_ROLE": "TRAINER",
                     "DIST_TOTAL_STEPS": str(total),
                     "DIST_START_STEP": "{resume_step}",
                     "CHAOS_PROGRESS": os.path.join(
                         sup_tmp, "progress_{spawn}.json")},
                backoff_s=0.05, action_deadline_s=180.0)})
    sup = Supervisor(spec, poll_s=0.05, registry_poll_s=0.1).start()
    # the FIRST post-resume write must be caught LIVE (the trainer
    # rewrites the progress file every step, so a post-hoc mtime would
    # be the END of the run, not the resume) — a watcher thread polls
    # for incarnation 1's first complete write while the fleet runs
    import threading
    first_resume = {}

    def _watch_resume():
        try:
            first_resume["ts"] = watch_first_write(
                os.path.join(sup_tmp, "progress_1.json"))
        except RuntimeError:
            pass
    watcher = threading.Thread(target=_watch_resume, daemon=True)
    watcher.start()
    verdict = sup.wait(timeout=420)
    status = sup.status()
    sup.stop()
    assert verdict == "done", status
    watcher.join(timeout=10)
    assert stitched_ok(sorted(glob.glob(
        os.path.join(sup_tmp, "progress_*.json"))))
    supervised_mttr = first_resume["ts"] - kill_ts(os.path.join(sup_tmp,
                                                                "flight"))

    # ---- manual: the runner-choreographed baseline ----------------------
    man_tmp = tempfile.mkdtemp(prefix="ptbench_rec_man_")
    root_m = os.path.join(man_tmp, "ck")
    ready = os.path.join(man_tmp, "ready")
    poll_s = 0.5   # a scripted runner's realistic liveness cadence

    def spawn(role, env, **extra):
        return subprocess.Popen(
            [sys.executable, runner],
            env={**os.environ, **env, "PADDLE_TRAINING_ROLE": role,
                 **extra},
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)

    def manual_phase(eps, start, extra_ps=None):
        env = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": pythonpath,
               "PADDLE_PSERVER_ENDPOINTS": ",".join(eps),
               "PADDLE_READY_DIR": ready,
               "CHAOS_CKPT_DIR": root_m, "CHAOS_CKPT_SHARDED": "1",
               "CHAOS_CKPT_EVERY": str(ckpt_every),
               "CHAOS_OPTIMIZER": "sgd"}
        pss = [spawn("PSERVER", env, PADDLE_CURRENT_ENDPOINT=ep,
                     **(extra_ps or {}) if i == 0 else {})
               for i, ep in enumerate(eps)]
        from paddle_tpu.distributed import transport
        transport.wait_server_ready(eps, timeout=300, ready_dir=ready)
        progress = os.path.join(man_tmp, f"progress_{start}.json")
        tr = spawn("TRAINER", env, CHAOS_PROGRESS=progress,
                   DIST_TOTAL_STEPS=str(total),
                   DIST_START_STEP=str(start))
        return pss, tr, progress

    pss, tr, prog_a = manual_phase(
        [f"127.0.0.1:{p}" for p in free_ports(2)], 0,
        extra_ps={"FLAGS_fault_inject":
                  f"kill_after:apply_round:n={kill_round}",
                  "FLAGS_flight_record_dir": os.path.join(man_tmp,
                                                          "flight")})
    # the scripted runner's detect loop: poll at its cadence
    while pss[0].poll() is None:
        time.sleep(poll_s)
    # choreography: tear down survivors, restart from the cut
    for p in pss[1:] + [tr]:
        if p.poll() is None:
            p.kill()
        p.wait()
    import paddle_tpu.checkpoint as pckpt
    cut = pckpt.latest_complete_step(root_m) or 0
    pss_b, tr_b, prog_b = manual_phase(
        [f"127.0.0.1:{p}" for p in free_ports(2)], cut)
    resume_m = watch_first_write(prog_b)
    manual_mttr = resume_m - kill_ts(os.path.join(man_tmp, "flight"))
    assert tr_b.wait(timeout=300) == 0
    for p in pss_b:
        assert p.wait(timeout=120) == 0
    assert stitched_ok([prog_a, prog_b])

    out = {
        "steps": total, "ckpt_every_rounds": ckpt_every,
        "kill_round": kill_round,
        # both modes' MTTR floor is worker process startup; on a box
        # with fewer cores than concurrently-respawning workers the
        # supervisor's pipelined overlap buys little (imports contend)
        # — on a real one-worker-per-host fleet it collapses the
        # serial choreography chain.  host_cpus tells the reader which
        # regime this number was measured in (the bench_pipeline
        # precedent).
        "host_cpus": os.cpu_count(),
        "recovery_mttr_s": round(supervised_mttr, 3),
        "supervised_mttr_s": round(supervised_mttr, 3),
        "manual_mttr_s": round(manual_mttr, 3),
        "vs_manual": round(manual_mttr / max(supervised_mttr, 1e-9), 2),
        "supervised_spawns": {w["name"]: w["spawns"]
                              for w in status["workers"]},
        "parity": "rtol 1e-4 vs the no-fault local run, both modes",
    }
    print("RECOVERY=" + json.dumps(out), flush=True)
    sys.stdout.flush()


def bench_recovery():
    """MTTR of a hard-killed pserver: the self-healing supervisor
    (detect → rollback → checkpoint-hydrate → resume, zero human steps)
    vs the manual runner-choreographed restart baseline, on the same
    fleet and data stream, both required to resume at loss parity.
    Headline: ``recovery_mttr_s`` (lower is better — gated in
    tools/bench_compare.py LOWER_BETTER_KEYS).  CPU-measured: the
    control plane under test is transport/process-level, no TPU math
    in the measured window."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--recovery-child"],
        env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
        capture_output=True, text=True, timeout=900)
    for line in out.stdout.splitlines():
        if line.startswith("RECOVERY="):
            return json.loads(line[len("RECOVERY="):])
    raise RuntimeError(
        f"recovery child failed rc={out.returncode}: "
        f"{out.stderr[-800:]}")


def _pipeline_child_main():
    """Child for bench_pipeline: K-stage mnist pipeline on a K-device
    virtual CPU mesh (one stage per device, worker threads overlap).
    GPipe vs 1F1B at M in {4, 8, 16} microbatches vs the naive
    sequential stage-by-stage baseline; reports samples/s, measured +
    slot-grid bubble fraction, and per-stage utilization."""
    import os
    import sys

    import jax
    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu.pipeline as pipe
    from paddle_tpu.models import mnist

    K = int(os.environ.get("PADDLE_TPU_BENCH_PIPE_STAGES", "4"))
    mb = int(os.environ.get("PADDLE_TPU_BENCH_PIPE_MICROBATCH", "32"))
    reps = int(os.environ.get("PADDLE_TPU_BENCH_PIPE_REPS", "3"))
    devices = jax.devices()[:K]
    rng = np.random.RandomState(0)
    # host_cpus bounds the thread-overlap win on the CPU mesh: the
    # sequential baseline already uses every core via XLA intra-op
    # threading, so speedup > 1 here measures pure schedule overlap;
    # on a >=K-core (or multi-chip) host the full GPipe ratio applies
    out = {"stages": K, "microbatch_rows": mb, "host_cpus": os.cpu_count(),
           "device": devices[0].platform, "configs": {}}

    def timed(tr, feed, mode, n):
        t0 = time.perf_counter()
        res = None
        for _ in range(n):
            res = tr.run(feed, mode=mode)
        return time.perf_counter() - t0, res

    for M in (4, 8, 16):
        B = mb * M
        feed = {"pixel": rng.randn(B, 1, 28, 28).astype("float32"),
                "label": rng.randint(0, 10, (B, 1)).astype("int64")}
        cfg = {"batch": B, "microbatches": M,
               "bubble_bound": round(pipe.gpipe_bubble_bound(K, M), 4)}

        prog, startup, (feeds, loss, acc) = _fresh(lambda: mnist.build())
        pp = pipe.PipelineTranspiler().transpile(
            prog, startup, num_stages=K, num_microbatches=M,
            loss_name=loss.name)
        tr = pipe.PipelineTrainer(pp, schedule="gpipe",
                                  devices=devices).init()
        tr.run(feed, mode="sequential")  # warmup: compiles every stage
        dt, _ = timed(tr, feed, "sequential", reps)
        cfg["sequential_samples_per_sec"] = round(B * reps / dt, 1)

        for sched in ("gpipe", "1f1b"):
            trs = pipe.PipelineTrainer(pp, schedule=sched,
                                       devices=devices).init()
            trs.run(feed)  # warmup (slots mode)
            dt, res = timed(trs, feed, None, reps)
            cfg[sched] = {
                "samples_per_sec": round(B * reps / dt, 1),
                "speedup_vs_sequential": round(
                    (B * reps / dt) / cfg["sequential_samples_per_sec"],
                    3),
                "bubble_fraction": round(res.bubble_fraction, 4),
                "bubble_fraction_slots": round(
                    res.bubble_fraction_slots, 4),
                "stage_utilization": [round(u, 3)
                                      for u in res.stage_utilization],
                "stage_activation_bytes": res.stage_activation_bytes,
            }
        out["configs"][f"m{M}"] = cfg

    m8 = out["configs"]["m8"]
    best = max(("gpipe", "1f1b"), key=lambda s: m8[s]["samples_per_sec"])
    out["pipeline_samples_per_sec"] = m8[best]["samples_per_sec"]
    out["best_schedule_m8"] = best
    out["pipeline_vs_sequential_speedup"] = \
        m8[best]["speedup_vs_sequential"]
    out["bubble_fraction_m8"] = m8[best]["bubble_fraction"]
    out["bubble_bound_m8"] = m8["bubble_bound"]
    print("PIPELINE=" + json.dumps(out), flush=True)
    sys.stdout.flush()


def bench_pipeline():
    """Pipeline parallelism machinery: K-stage mnist training, GPipe vs
    1F1B vs naive sequential stage execution at M in {4, 8, 16}
    microbatches.  Subprocess on a virtual K-device CPU mesh (stage
    overlap needs one device per stage, and the CPU device count is
    fixed at backend start) — on a real multi-chip host the same harness
    measures hardware overlap, here it measures the scheduling plane.
    Headline: best-schedule samples/s at M=8, with the measured bubble
    fraction vs the (K-1)/(M+K-1) GPipe model."""
    import os
    import subprocess
    import sys

    K = int(os.environ.get("PADDLE_TPU_BENCH_PIPE_STAGES", "4"))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={K}").strip()
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--pipeline-child"],
        env=env, cwd=os.path.dirname(os.path.abspath(__file__)),
        capture_output=True, text=True, timeout=900)
    for line in out.stdout.splitlines():
        if line.startswith("PIPELINE="):
            return json.loads(line[len("PIPELINE="):])
    raise RuntimeError(
        f"pipeline child failed rc={out.returncode}: {out.stderr[-500:]}")


def bench_scaling():
    """Weak-scaling efficiency on the virtual 8-device CPU mesh (see
    paddle_tpu/parallel/scaling.py — per-device compiled cost, the only
    honest scaling instrument on a 1-core host).  Subprocess because the
    virtual device count is fixed when a process's CPU backend starts."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    code = ("import jax; jax.config.update('jax_platforms', 'cpu'); "
            "import json; from paddle_tpu.parallel.scaling import "
            "scaling_report; print('SCALING=' + "
            "json.dumps(scaling_report(per_device_batch=4, big_dp=8)))")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         cwd=os.path.dirname(os.path.abspath(__file__)),
                         capture_output=True, text=True, timeout=900)
    for line in out.stdout.splitlines():
        if line.startswith("SCALING="):
            rep = json.loads(line[len("SCALING="):])
            assert rep["eff_flops"] >= 0.85, rep
            # analysis-only tagging happens centrally in main() via
            # ANALYSIS_CONFIGS (one policy point, covers error records)
            return rep
    raise RuntimeError(f"scaling child failed: {out.stderr[-500:]}")


# Ordered so the headline + the claims under review land first if the
# budget runs out.  (name, fn, per-config deadline seconds, needs_tpu)
CONFIG_TABLE = [
    ("resnet50", bench_resnet50, 480, True),
    ("deepfm", bench_deepfm, 420, True),
    # needs_tpu=False: off-TPU it self-degrades to an ``analysis: true``
    # structural artifact (the one backend-conditional exception to the
    # static ANALYSIS_CONFIGS tagging); on-chip it is a measured config
    # on the ROADMAP item 5 capture list (DeepFM >= 400k samples/s)
    ("deepfm_fused", bench_deepfm_fused, 420, False),
    ("mnist", bench_mnist, 300, True),
    ("flash_attention_seq8k", bench_flash_attention_long, 600, True),
    ("ring_shard_s4096", bench_ring_shard, 420, True),
    ("transformer_seq256", bench_transformer, 420, True),
    ("stacked_lstm", bench_stacked_lstm, 300, True),
    ("resnet50_datapath", bench_resnet50_datapath, 420, True),
    ("rpc_transport", bench_rpc_transport, 300, False),
    ("serving", bench_serving, 420, False),
    # needs_tpu=False: CPU-measured policy evidence, self-labels
    # ``analysis: true`` off-TPU (the deepfm_fused precedent); the
    # on-chip number is the ROADMAP item 1 'decode' capture row
    ("decode", bench_decode, 420, False),
    # refcounted block lifecycle: shared-prefix dedup + overcommit
    # preemption legs (CPU policy evidence off-TPU, like decode)
    ("decode_prefix", bench_decode_prefix, 420, False),
    # quantized KV cache residency: int8 blocks + scale pools vs fp32
    # at the same pool bytes (CPU policy evidence off-TPU, like decode)
    ("decode_kv_int8", bench_decode_kv_int8, 420, False),
    ("pipeline", bench_pipeline, 900, False),
    ("compile_cache", bench_compile_cache, 600, False),
    ("checkpoint", bench_checkpoint, 600, False),
    # CPU-measured control-plane wall time (like rpc_transport): the
    # supervisor's kill-to-parity-resume MTTR vs the manual baseline
    ("recovery", bench_recovery, 900, False),
    ("scaling_dp8", bench_scaling, 900, False),
]


def _config_table():
    """The real table, or a test-injected one (file exporting
    CONFIG_TABLE) so tests/test_bench_driver.py can exercise the
    orchestrator's timeout/restart/budget paths without a TPU."""
    import importlib.util
    import os

    path = os.environ.get("PADDLE_TPU_BENCH_TEST_TABLE")
    if not path:
        return CONFIG_TABLE
    spec = importlib.util.spec_from_file_location("bench_test_table", path)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m.CONFIG_TABLE


def _device_check_main():
    """Child: initialize the default backend, do one tiny put +
    readback, and report what JAX found — so a sick device is
    diagnosable (and kill-able) from outside the process that holds it."""
    import jax

    t0 = time.perf_counter()
    d = jax.device_put(np.ones((8, 128), np.float32))
    float(np.asarray(d)[0, 0])
    init_s = time.perf_counter() - t0
    dev = jax.devices()
    print("DEVICECHECK=" + json.dumps({
        "ok": True, "backend_init_s": round(init_s, 2),
        "platform": dev[0].platform, "device_kind": dev[0].device_kind,
        "device_count": len(dev)}), flush=True)


def _fleet_aggregator():
    """Multi-host runs: PADDLE_TPU_BENCH_FLEET_ENDPOINTS names the other
    workers' RPC ports (``trainer-0=host:port,trainer-1=host:port`` —
    bare ``host:port`` entries get positional names) and the per-config
    telemetry export then carries a cross-worker ``fleet`` merge with
    per-worker labels (observability/aggregate.py).  Unset (the
    single-host default) adds nothing."""
    spec = os.environ.get("PADDLE_TPU_BENCH_FLEET_ENDPOINTS", "")
    if not spec:
        return None
    workers = {}
    for i, item in enumerate(x.strip() for x in spec.split(",")):
        if not item:
            continue
        name, _, ep = item.rpartition("=")
        workers[name or f"worker-{i}"] = ep
    from paddle_tpu.observability.aggregate import FleetAggregator
    return FleetAggregator(workers)


def _worker_main(names):
    """Child: run the named configs in order, one flushed line each.

    Per config, the runtime telemetry layer is reset before and exported
    after (``BENCHSTATS=`` line), so each config's compile-cache
    hits/misses, lowering/compile time and transfer bytes land in the
    orchestrator's ``step_stats.json`` artifact — a BENCH_r*.json
    regression then comes with the telemetry that explains it."""
    try:
        from paddle_tpu import observability as _obs
    except Exception:  # telemetry must never take the bench down
        _obs = None
    try:
        fleet = _fleet_aggregator() if _obs is not None else None
    except Exception:
        fleet = None
    fns = dict((n, f) for n, f, _, _ in _config_table())
    for name in names:
        print("BENCHSTART=" + name, flush=True)
        if _obs is not None:
            _obs.reset()
        _take_roofline()  # a previous config's attribution must not leak
        try:
            result = fns[name]()
        except Exception as e:  # broken config must not hide the rest
            result = {"error": repr(e)[:200]}
        rf = _take_roofline()
        if rf and isinstance(result, dict) and "error" not in result:
            result.setdefault("roofline", rf)
        print("BENCHRESULT=" + json.dumps({"name": name, "result": result}),
              flush=True)
        if _obs is not None:
            try:
                tele = _obs.export(step_tail=8)
                if fleet is not None:
                    tele["fleet"] = fleet.export()
                print("BENCHSTATS=" + json.dumps(
                    {"name": name, "telemetry": tele}),
                    flush=True)
            except Exception:
                pass


def _run_streaming(cmd, handle_line, deadline_for, kill_grace=5.0):
    """Run cmd, dispatching stdout lines to handle_line.  deadline_for()
    returns the absolute monotonic deadline for the current wait (it can
    move as configs complete).  Returns (rc, timed_out)."""
    import queue
    import subprocess
    import threading

    p = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
    q = queue.Queue()

    def pump():
        for line in p.stdout:
            q.put(line)
        q.put(None)

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    timed_out = False
    while True:
        timeout = deadline_for() - time.monotonic()
        if timeout <= 0:
            timed_out = True
            break
        try:
            line = q.get(timeout=min(timeout, 5.0))
        except queue.Empty:
            continue
        if line is None:
            break
        handle_line(line.rstrip("\n"))
    if timed_out:
        # drain lines that raced the deadline (a result printed just
        # before expiry must not be recorded as a timeout)
        while True:
            try:
                line = q.get_nowait()
            except queue.Empty:
                break
            if line is None:
                break
            handle_line(line.rstrip("\n"))
        p.kill()
    p.wait(timeout=kill_grace if timed_out else None)
    return p.returncode, timed_out


def _device_check(budget_deadline):
    import os
    import sys

    timeout = float(os.environ.get("PADDLE_TPU_BENCH_PROBE_TIMEOUT_S",
                                   "240"))
    deadline = min(time.monotonic() + timeout, budget_deadline)
    result = {}

    def on_line(line):
        if line.startswith("DEVICECHECK="):
            result.update(json.loads(line[len("DEVICECHECK="):]))

    rc, timed_out = _run_streaming(
        [sys.executable, __file__, "--device-check"], on_line,
        lambda: deadline)
    if not result:
        result = {"ok": False,
                  "error": "timeout" if timed_out else f"rc={rc}"}
    return result


# analysis-only entries: cost-model/compiled-cost numbers, not on-chip
# wall time — tagged in the artifact so an all-skip TPU round whose only
# survivors are analysis entries cannot read as a measured round
ANALYSIS_CONFIGS = frozenset({"scaling_dp8"})


def main():
    import os
    import sys

    t_start = time.monotonic()
    budget = float(os.environ.get("PADDLE_TPU_BENCH_BUDGET_S", "1200"))
    budget_deadline = t_start + budget

    def emit_partial(name, result):
        # partials go to STDERR: stdout stays exactly ONE JSON line (the
        # driver contract), while a timeout-killed run still leaves the
        # finished configs readable in the captured stderr tail
        print(json.dumps({"partial": True, "config": name,
                          "result": result}), file=sys.stderr, flush=True)

    check = _device_check(budget_deadline)
    emit_partial("_device_check", check)

    configs = {}
    telemetry = {}
    pending = [(n, dl, tpu) for n, _, dl, tpu in _config_table()]
    if check.get("platform") != "tpu":
        # no chip: a chip config FAILS (it is never re-routed to the
        # CPU and never silently skipped); the CPU-mesh entries still run
        why = (f"no TPU: device check found platform "
               f"{check['platform']!r}" if check.get("ok")
               else f"device check failed ({check.get('error')})")
        for name, _, tpu in pending:
            if tpu:
                configs[name] = {"error": why}
                emit_partial(name, configs[name])
        pending = [p for p in pending if not p[2]]

    _drain_configs(pending, configs, telemetry, budget_deadline,
                   emit_partial)

    for name in ANALYSIS_CONFIGS:
        if isinstance(configs.get(name), dict):
            configs[name].setdefault("analysis", True)

    _emit_summary(configs, telemetry, check, t_start)


def _drain_configs(pending, configs, telemetry, budget_deadline,
                   emit_partial):
    """Run the named configs through restartable worker subprocesses
    (mutates ``configs``/``telemetry``; see main for the contract)."""
    import os
    import sys

    timeouts_in_a_row = 0
    while pending:
        remaining_budget = budget_deadline - time.monotonic()
        if remaining_budget < 60:
            for name, _, _ in pending:
                configs[name] = {"skipped": "budget"}
                emit_partial(name, configs[name])
            break
        if timeouts_in_a_row >= 2:
            # the device went sick mid-run: stop burning budget on chip
            # configs, keep anything CPU-only
            for name, _, tpu in list(pending):
                if tpu:
                    configs[name] = {"skipped":
                                     "2 consecutive config timeouts"}
                    emit_partial(name, configs[name])
            pending = [p for p in pending if not p[2]]
            timeouts_in_a_row = 0
            continue

        names = [n for n, _, _ in pending]
        caps = dict((n, dl) for n, dl, _ in pending)
        state = {"current": None, "started": time.monotonic(),
                 "n_results": 0}

        def on_line(line):
            if line.startswith("BENCHSTART="):
                state["current"] = line[len("BENCHSTART="):]
                state["started"] = time.monotonic()
            elif line.startswith("BENCHRESULT="):
                rec = json.loads(line[len("BENCHRESULT="):])
                configs[rec["name"]] = rec["result"]
                emit_partial(rec["name"], rec["result"])
                state["current"] = None
                # restart the between-configs clock: deadline_for must
                # not judge the NEXT config by the finished one's start
                state["started"] = time.monotonic()
                state["n_results"] += 1
            elif line.startswith("BENCHSTATS="):
                # a worker killed at its deadline can truncate this
                # (multi-KB) line mid-print; telemetry must never take
                # the bench down
                try:
                    rec = json.loads(line[len("BENCHSTATS="):])
                    telemetry[rec["name"]] = rec["telemetry"]
                except (ValueError, KeyError):
                    pass

        def deadline_for():
            cap = caps.get(state["current"], 300) if state["current"] \
                else 120  # startup/import window
            return min(state["started"] + cap, budget_deadline)

        n_done_before = len(configs)
        rc, timed_out = _run_streaming(
            [sys.executable, __file__, "--worker", ",".join(names)],
            on_line, deadline_for)
        if state["n_results"]:
            timeouts_in_a_row = 0  # "consecutive" means no success between
        if timed_out and state["current"]:
            configs[state["current"]] = {"error": "timeout", "after_s":
                                         round(time.monotonic()
                                               - state["started"], 1)}
            emit_partial(state["current"], configs[state["current"]])
            timeouts_in_a_row += 1
        elif timed_out:
            timeouts_in_a_row += 1
        pending = [p for p in pending if p[0] not in configs]
        if not timed_out and rc == 0:
            break  # worker finished the whole list
        if not timed_out and rc != 0 and state["current"]:
            # worker crashed mid-config (not via the per-config except:
            # e.g. a segfault); record it and continue with the rest
            configs[state["current"]] = {"error": f"worker rc={rc}"}
            emit_partial(state["current"], configs[state["current"]])
            pending = [p for p in pending if p[0] not in configs]
        elif not timed_out and rc != 0 and len(configs) == n_done_before:
            # crashed before reaching any config and made no progress —
            # don't crash-loop until the budget runs out
            for name, _, _ in pending:
                configs[name] = {"error": f"worker rc={rc} at startup"}
                emit_partial(name, configs[name])
            break


def _auto_compare(configs):
    """Regression gate on the freshly completed round: compare against
    the last round that actually measured something (timed-out and
    all-skip rounds are passed over) and record the
    verdict in the summary JSON (tools/bench_compare.py is also the
    standalone CI gate).  PADDLE_TPU_BENCH_COMPARE_PREV names a
    specific baseline; set it empty to disable.  Comparison failures
    are recorded, never fatal — the measured numbers always land."""
    import os
    import sys

    prev = os.environ.get("PADDLE_TPU_BENCH_COMPARE_PREV")
    if prev == "":
        return None
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, os.path.join(here, "tools"))
    try:
        import bench_compare
        base_path = prev or bench_compare.find_baseline(here)
        if not base_path:
            return {"skipped": "no measured baseline round found"}
        old = bench_compare.load_round(base_path)
        cmp = bench_compare.compare(old, {"configs": configs})
        cmp["baseline"] = os.path.basename(base_path)
        return cmp
    except Exception as e:
        return {"error": repr(e)[:200]}
    finally:
        sys.path.pop(0)


def _emit_summary(configs, telemetry, check, t_start):
    import os

    # per-config telemetry artifact (cache hits, compile time, transfer
    # bytes — the numbers that EXPLAIN a BENCH trajectory regression);
    # PADDLE_TPU_BENCH_STATS_PATH overrides, empty disables
    stats_path = os.environ.get("PADDLE_TPU_BENCH_STATS_PATH",
                                "step_stats.json")
    if stats_path:
        try:
            with open(stats_path, "w") as f:
                json.dump({"configs": telemetry}, f, indent=2,
                          sort_keys=True)
        except OSError:
            stats_path = None

    primary = configs.get("resnet50", {}).get("images_per_sec", 0.0)
    tfm = configs.get("transformer_seq256", {})
    if tfm.get("tokens_per_sec"):
        configs["transformer_seq256"]["vs_a100"] = round(
            tfm["tokens_per_sec"] / A100_TRANSFORMER_TOK_S, 3)
    # an all-skip/analysis-only round must be legible as one: count the
    # configs that produced a MEASURED number this round
    measured = sum(
        1 for v in configs.values()
        if isinstance(v, dict) and not v.get("skipped")
        and not v.get("error") and not v.get("analysis"))
    comparison = _auto_compare(configs)
    print(json.dumps({
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": primary,
        "unit": "images/sec",
        "vs_baseline": round(primary / A100_RESNET50_IMG_S, 3),
        "device_check": check,
        "measured_configs": measured,
        "elapsed_s": round(time.monotonic() - t_start, 1),
        "step_stats_path": stats_path or None,
        "comparison": comparison,
        "configs": configs,
    }), flush=True)


if __name__ == "__main__":
    import sys

    if len(sys.argv) > 1 and sys.argv[1] == "--device-check":
        _device_check_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "--worker":
        _worker_main(sys.argv[2].split(","))
    elif len(sys.argv) > 1 and sys.argv[1] == "--compile-cache-child":
        _compile_cache_child_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "--checkpoint-child":
        _checkpoint_child_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "--recovery-child":
        _recovery_child_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "--pipeline-child":
        _pipeline_child_main()
    else:
        main()
