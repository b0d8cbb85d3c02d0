"""Low-precision serving (ROADMAP item 3 legs (a)/(b)): the
fused-dequant int8 Pallas matmul + calibration pass round-trip, the
quantized paged KV cache (per-block-per-head scales under prefix
adoption, COW-style block copies and preemption re-prefill), the
counted-fallback contract for every quantized fast path, and the
flags-off byte-identity pins."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.core import flags as _flags
from paddle_tpu.core import unique_name
from paddle_tpu.core.executor import Executor, Scope, scope_guard
from paddle_tpu.core.program import Program, program_guard
from paddle_tpu.decode import (DecodeEngine, LMConfig, SamplingParams,
                               TransformerLM)
from paddle_tpu.decode.cache import PagedKVCache
from paddle_tpu.inference import AnalysisConfig, create_predictor
from paddle_tpu.kernels import attention as A
from paddle_tpu.kernels import quant as Q

from paged_walks import WALKS, dense_reference, walk_case

L = fluid.layers
rng = np.random.RandomState(11)

TINY = LMConfig(vocab=48, d_model=32, n_head=2, d_ffn=48, n_layer=2,
                max_seq_len=32)


@pytest.fixture(scope="module", autouse=True)
def _leave_no_fallback_counts_behind():
    """This file injects kernel build faults, which count
    ``quant.matmul_fallbacks`` process-wide; a later file on the same
    xdist worker that demands "every fallback counter zero"
    (tests/benchmark/test_benchmark_train_loop.py) must not inherit
    them.  Counters only ever feed deltas elsewhere, so zero them all."""
    yield
    from paddle_tpu import observability
    observability.reset()


def _engine(name, **kw):
    lm = TransformerLM(TINY)
    params = lm.init_params(seed=5)
    kw.setdefault("max_slots", 3)
    kw.setdefault("block_tokens", 4)
    kw.setdefault("prefill_buckets", (8, 16))
    return DecodeEngine(lm, params, name=name, **kw)


# ---------------------------------------------------------------------------
# the kernel: fused-dequant int8 matmul
# ---------------------------------------------------------------------------

def test_quantize_weight_per_channel_roundtrip():
    w = rng.randn(24, 10).astype("float32") * np.linspace(0.1, 3.0, 10)
    q, s = Q.quantize_weight(w)
    assert q.dtype == np.int8 and s.shape == (10,)
    # per-column abs-max scales; dequant error bounded by half an lsb
    np.testing.assert_allclose(s, np.abs(w).max(axis=0), rtol=1e-6)
    back = q.astype(np.float32) * s[None, :] / Q.QMAX
    assert np.max(np.abs(back - w)) <= np.max(s) / Q.QMAX
    # an all-zero column still divides cleanly
    w[:, 3] = 0.0
    q2, s2 = Q.quantize_weight(w)
    assert s2[3] == Q.SCALE_EPS and not q2[:, 3].any()
    assert 0.0 <= Q.clip_fraction(q) <= 1.0


@pytest.mark.parametrize("act", ["", "relu"])
def test_int8_fc_kernel_matches_xla_dequant_reference(act):
    """The Pallas launch and the XLA fallback are the SAME quantized
    math: bit-close on identical codes, and both near the f32 truth."""
    x = rng.randn(6, 16).astype("float32")
    w = rng.randn(16, 12).astype("float32")
    b = rng.randn(12).astype("float32")
    w_q, w_s = Q.quantize_weight(w)
    before = dict(Q._COUNTERS)
    got = Q.int8_fc(jnp.asarray(x), jnp.asarray(w_q), jnp.asarray(w_s),
                    0.0, jnp.asarray(b), act)
    assert got is not None
    ref = Q.int8_fc_xla(jnp.asarray(x), jnp.asarray(w_q), jnp.asarray(w_s),
                        0.0, jnp.asarray(b), act)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    f32 = x @ w + b
    f32 = {"": f32, "relu": np.maximum(f32, 0)}[act]
    assert np.max(np.abs(np.asarray(got) - f32)) < 0.15
    assert Q._COUNTERS["matmul_launches"] == \
        before.get("matmul_launches", 0) + 1


def test_int8_fc_build_fault_returns_none_counted(monkeypatch):
    """The counted-fallback contract: a Pallas build fault can never
    fail a dispatch — int8_fc returns None (counted) and the caller's
    XLA dequantized path carries the step."""
    def boom(*a, **k):
        raise RuntimeError("forced build fault")
    monkeypatch.setattr(Q.pl, "pallas_call", boom)
    x = jnp.asarray(rng.randn(4, 8).astype("float32"))
    w_q, w_s = Q.quantize_weight(rng.randn(8, 6).astype("float32"))
    before = Q._COUNTERS.get("matmul_fallbacks", 0)
    assert Q.int8_fc(x, jnp.asarray(w_q), jnp.asarray(w_s)) is None
    assert Q._COUNTERS["matmul_fallbacks"] == before + 1
    out = Q.int8_fc_xla(x, jnp.asarray(w_q), jnp.asarray(w_s))
    assert np.all(np.isfinite(np.asarray(out)))


def test_plan_int8_skips_half_stamped_ops():
    """An op with the attr but missing a sidecar input (or vice versa)
    must lower f32 — the stamp is all-or-nothing."""
    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        x = L.data("x", [8])
        L.fc(x, 4)
    block = prog.global_block
    mul = next(op for op in block.ops if op.type == "mul")
    mul.attrs["quant_int8"] = True          # attr without sidecars
    assert Q.plan_int8(block) is None
    mul.inputs["WInt8"] = ["w@INT8"]        # still missing WScale
    assert Q.plan_int8(block) is None
    mul.inputs["WScale"] = ["w@INT8_SCALE"]
    plan = Q.plan_int8(block)
    assert plan is not None and plan.covers(block.ops.index(mul))


# ---------------------------------------------------------------------------
# calibration round-trip: QAT fake-quant stats -> int8 predictor parity
# ---------------------------------------------------------------------------

def _save_fc_mlp(dirname, seed=3):
    prog, startup = Program(), Program()
    prog.random_seed = seed
    with program_guard(prog, startup), unique_name.guard():
        x = L.data("x", [8])
        h = L.fc(x, 16, act="relu")
        y = L.fc(h, 4)
    scope = Scope()
    exe = Executor()
    with scope_guard(scope):
        exe.run(startup)
        fluid.io.save_inference_model(dirname, ["x"], [y], exe,
                                      main_program=prog)


def test_qat_calibration_roundtrip_parity(tmp_path):
    """The acceptance pin for leg (a): a QAT-trained model (fake-quant
    ops + frozen moving-average scales) served through enable_int8()
    folds every fake-quant op, harvests the calibrated activation
    scale, and reproduces the fake-quant reference output within the
    quantization tolerance."""
    from paddle_tpu.contrib.quantize import QuantizeTranspiler

    prog, startup = Program(), Program()
    prog.random_seed = 4
    with program_guard(prog, startup), unique_name.guard():
        x = L.data("x", [8])
        label = L.data("label", [1], dtype="int64")
        h = L.fc(x, 16, act="relu")
        pred = L.fc(h, 4)       # logits head: softmax stays out of the
        sm = L.softmax(pred)    # saved graph (not an epilogue act)
        loss = L.mean(L.cross_entropy(sm, label))
        t = QuantizeTranspiler(
            activation_quantize_type="moving_average_abs_max")
        t.training_transpile(prog, startup)
        fluid.optimizer.SGD(0.05).minimize(loss)
    exe = Executor()
    scope = Scope()
    d = str(tmp_path / "qat")
    with scope_guard(scope):
        exe.run(startup)
        for _ in range(8):      # advance the moving-average scale state
            xb = rng.randn(16, 8).astype("float32")
            lb = rng.randint(0, 4, (16, 1)).astype("int64")
            exe.run(prog, feed={"x": xb, "label": lb}, fetch_list=[loss])
        infer = prog.clone().prune([pred.name])
        t.freeze_program(infer)
        fluid.io.save_inference_model(d, ["x"], [pred], exe,
                                      main_program=infer)

    ref = create_predictor(AnalysisConfig(d))   # fake-quant reference
    cfg = AnalysisConfig(d)
    cfg.enable_int8()
    assert cfg.int8_enabled()
    q = create_predictor(cfg)
    ops = q.program().global_block.ops
    assert not any(op.type.startswith("fake_") for op in ops)
    stamped = [op for op in ops if op.attrs.get("quant_int8")]
    assert len(stamped) == 2
    # the moving-average running scale was harvested, not left dynamic
    assert any(float(op.attrs["in_scale"]) > 0.0 for op in stamped)
    xv = rng.randn(32, 8).astype("float32")
    (a,) = ref.run({"x": xv})
    (b,) = q.run({"x": xv})
    # per-channel weight codes vs the QAT per-tensor reference: close
    # logits, and argmax-identical on nearly every row
    assert np.max(np.abs(a - b)) < 0.2, np.max(np.abs(a - b))
    agree = np.mean(a.argmax(-1) == b.argmax(-1))
    assert agree >= 0.95, agree


def test_post_training_absmax_without_qat_stats(tmp_path):
    """No QAT graph at all: enable_int8() still calibrates (weight
    abs-max, dynamic activation scale) and stays within the parity
    bar of the f32 predictor."""
    d = str(tmp_path / "ptq")
    _save_fc_mlp(d)
    ref = create_predictor(AnalysisConfig(d))
    cfg = AnalysisConfig(d)
    cfg.enable_int8()
    q = create_predictor(cfg)
    stamped = [op for op in q.program().global_block.ops
               if op.attrs.get("quant_int8")]
    assert len(stamped) == 2
    assert all(float(op.attrs["in_scale"]) == 0.0 for op in stamped)
    xv = rng.randn(32, 8).astype("float32")
    (a,) = ref.run({"x": xv})
    (b,) = q.run({"x": xv})
    assert np.max(np.abs(a - b)) < 0.2, np.max(np.abs(a - b))
    # the calibration left /quantz records for both layers
    names = {r["weight"] for r in Q.quantz()["calibrated_layers"]}
    assert {op.inputs["WInt8"][0][:-5] for op in stamped} <= names


def test_int8_predictor_survives_forced_kernel_fault(tmp_path,
                                                     monkeypatch):
    """A build fault inside the quantized matmul must degrade to the
    XLA dequantized path (counted), never fail the run — and the
    output is the same quantized math."""
    d = str(tmp_path / "fault")
    _save_fc_mlp(d)
    cfg = AnalysisConfig(d)
    cfg.enable_int8()
    good = create_predictor(cfg)
    xv = rng.randn(8, 8).astype("float32")
    (want,) = good.run({"x": xv})

    def boom(*a, **k):
        raise RuntimeError("forced build fault")
    monkeypatch.setattr(Q.pl, "pallas_call", boom)
    cfg2 = AnalysisConfig(d)
    cfg2.enable_int8()
    broken = create_predictor(cfg2)
    before = Q._COUNTERS.get("matmul_fallbacks", 0)
    (got,) = broken.run({"x": xv})
    assert Q._COUNTERS["matmul_fallbacks"] > before
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("int8,scanned", [
    (False, False), (True, False), (True, True),
], ids=["nothing_latched", "run", "run_steps"])
def test_dispatch_fault_relowers_an_int8_entry_once(tmp_path, int8, scanned):
    """The contract's last line, at DISPATCH (a Mosaic fault of a real
    TPU, which the trace-time try/except cannot see): a lazy-jit entry
    whose lowering latched int8 kernels — the block or run_steps' scan
    of it — is re-lowered ONCE without them, counted, and a second fault
    re-raises; an entry that latched none re-raises the fault untouched."""
    from paddle_tpu.observability import stats as obs

    d = str(tmp_path / "dispatch")
    _save_fc_mlp(d)
    cfg = AnalysisConfig(d)
    if int8:
        cfg.enable_int8()
    pred = create_predictor(cfg)
    exe, prog = pred._exe, pred.program()
    xv = rng.randn(8, 8).astype("float32")

    def call():
        if scanned:
            return exe.run_steps(prog, feed={"x": xv[None]},
                                 fetch_list=pred._fetch_names,
                                 scope=pred._scope)[0][0]
        return pred.run({"x": xv})[0]

    def disables():
        return obs.to_dict().get("quant.runtime_disables", 0)

    want = call()
    (entry,) = exe._cache.values()
    build_fn = exe._make_scan_builder(prog, entry.plan) if scanned else None
    before = disables()
    assert entry.fused_used == {"int8_fused": int8}
    if not int8:
        with pytest.raises(RuntimeError, match="injected"):
            exe._recover_disk_entry(entry, prog,
                                    RuntimeError("injected fault"), [])
        assert not entry.fused_disabled and disables() == before
        return
    jitted = exe._recover_disk_entry(entry, prog,
                                     RuntimeError("mosaic fault"), [],
                                     build_fn=build_fn)
    assert entry.jitted is jitted and entry.fused_disabled
    assert disables() == before + 1
    # the stamped ops' own f32 lowering now carries the call
    assert 0 < np.max(np.abs(call() - want)) < 0.05
    assert entry.fused_used == {"int8_fused": False}
    with pytest.raises(RuntimeError, match="again"):
        exe._recover_fused_fault(entry, prog, RuntimeError("faults again"),
                                 [], build_fn=build_fn)
    assert disables() == before + 1


def test_int8_inference_flag_is_the_fleet_default(tmp_path):
    """FLAGS_int8_inference quantizes every predictor as if each config
    called enable_int8(); off (default) no config is touched."""
    d = str(tmp_path / "flag")
    _save_fc_mlp(d)
    assert _flags.get_flags("int8_inference") is False
    assert not AnalysisConfig(d).int8_enabled()
    plain = create_predictor(AnalysisConfig(d))
    assert not any(op.attrs.get("quant_int8")
                   for op in plain.program().global_block.ops)
    _flags.set_flags({"FLAGS_int8_inference": True})
    try:
        pred = create_predictor(AnalysisConfig(d))
        assert any(op.attrs.get("quant_int8")
                   for op in pred.program().global_block.ops)
    finally:
        _flags.set_flags({"FLAGS_int8_inference": False})


# ---------------------------------------------------------------------------
# KV-cache quantization: scale semantics + the quantized attention path
# ---------------------------------------------------------------------------

def test_kv_qdq_roundtrip_error_bound():
    rows = jnp.asarray(rng.randn(5, 3, 4).astype("float32") * 2.0)
    s = Q.kv_head_amax(rows)
    assert s.shape == (5, 3)
    back = Q.kv_dequantize(Q.kv_quantize(rows, s), s)
    # per-element error bounded by half an lsb of that head's scale
    bound = np.asarray(s)[..., None] / Q.QMAX
    assert np.all(np.abs(np.asarray(back) - np.asarray(rows)) <= bound)


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_quantized_paged_attention_pallas_matches_xla(layer):
    """int8 pool in the f32 pool's layout ([L, NB, bs, H*D] codes,
    [L, NB, H] scales), handed whole to the kernel with a static layer;
    slot 0 holds one token and a block table full of trash block 0."""
    L, S, H, D, NB, bs, MB = 3, 3, 4, 16, 12, 8, 4
    r = np.random.RandomState(7)
    kf = jnp.asarray(r.randn(L, NB, bs, H, D).astype("float32"))
    vf = jnp.asarray(r.randn(L, NB, bs, H, D).astype("float32"))
    ks = jnp.max(jnp.abs(kf), axis=(2, 4))               # [L, NB, H]
    vs = jnp.max(jnp.abs(vf), axis=(2, 4))
    pool = (L, NB, bs, H * D)
    kq = Q.kv_quantize(kf, ks[:, :, None, :]).reshape(pool)
    vq = Q.kv_quantize(vf, vs[:, :, None, :]).reshape(pool)
    kf, vf = kf.reshape(pool), vf.reshape(pool)
    q = jnp.asarray(r.randn(S, H, D).astype("float32"))
    bt = r.randint(1, NB, (S, MB)).astype("int32")
    bt[0, :] = 0
    bt = jnp.asarray(bt)
    cl = jnp.asarray(np.array([1, 17, 30], np.int32))
    ref = A.decode_attention(q, kf, vf, bt, cl, layer, impl="xla")
    x_q = A.decode_attention(q, kq, vq, bt, cl, layer, impl="xla",
                             k_scale=ks, v_scale=vs)
    p_q = A.decode_attention(q, kq, vq, bt, cl, layer, impl="pallas",
                             k_scale=ks, v_scale=vs)
    # the kernel dequantizes in VMEM to the same math as the gather path
    np.testing.assert_allclose(np.asarray(p_q), np.asarray(x_q),
                               rtol=1e-5, atol=1e-5)
    # and quantization error vs f32 stays small
    assert np.max(np.abs(np.asarray(x_q) - np.asarray(ref))) < 0.1
    # the layer index is read: the next layer's codes answer differently
    other = A.decode_attention(q, kq, vq, bt, cl, (layer + 1) % L,
                               impl="pallas", k_scale=ks, v_scale=vs)
    assert np.max(np.abs(np.asarray(other) - np.asarray(p_q))) > 1e-3


@pytest.mark.parametrize("MB", [20, 5])
@pytest.mark.parametrize("walk", list(WALKS))
def test_quantized_paged_attention_walks_live_blocks_in_chunks(walk, MB):
    """The int8 pool through the kernel's chunked walk (a slot's scale rows
    come in whole, one a table position): contexts that end on, one past
    and far from a chunk's edge, idle slots and the full table, every
    layer of a 3-layer pool, against the gather path."""
    bs, H, D, L = 4, 4, 64, 3
    chunk = min(A._DECODE_CHUNK_BLOCKS, MB)
    contexts = WALKS[walk](chunk * bs, MB * bs)
    q, kf, vf, bt, cl = walk_case(np.random.RandomState(11), contexts, MB,
                                  bs=bs, H=H, D=D, L=L)
    heads = kf.shape[:3] + (H, D)
    ks = jnp.max(jnp.abs(kf.reshape(heads)), axis=(2, 4))    # [L, NB, H]
    vs = jnp.max(jnp.abs(vf.reshape(heads)), axis=(2, 4))
    kq = Q.kv_quantize(kf.reshape(heads), ks[:, :, None, :]).reshape(kf.shape)
    vq = Q.kv_quantize(vf.reshape(heads), vs[:, :, None, :]).reshape(vf.shape)
    for layer in range(L):
        x_q = A.decode_attention(q, kq, vq, bt, cl, layer, impl="xla",
                                 k_scale=ks, v_scale=vs)
        p_q = A.decode_attention(q, kq, vq, bt, cl, layer, impl="pallas",
                                 k_scale=ks, v_scale=vs)
        np.testing.assert_allclose(np.asarray(p_q), np.asarray(x_q),
                                   rtol=1e-5, atol=1e-5)
        ref = dense_reference(q, kf, vf, bt, cl, layer)
        assert np.max(np.abs(np.asarray(p_q) - ref)) < 0.1


def test_quantized_cache_layout_and_bytes():
    f32 = PagedKVCache(2, 2, 16, 6, 4)
    i8 = PagedKVCache(2, 2, 16, 6, 4, dtype="int8")
    assert not f32.quantized and i8.quantized
    assert len(f32.state()) == 2 and len(i8.state()) == 4
    assert i8.k.dtype == jnp.int8 and i8.k_scale.shape == (2, 6, 2)
    # one layout for both dtypes: [L, NB, bs, H*Dh]
    assert i8.k.shape == f32.k.shape == (2, 6, 4, 32)
    # codes are 1/4 the f32 bytes; scales add a thin f32 sliver
    assert i8.nbytes < f32.nbytes * 0.3
    snap_f, snap_q = f32.snapshot(), i8.snapshot()
    assert "dtype" not in snap_f and "scale_bytes" not in snap_f
    assert snap_q["dtype"] == "int8"
    assert snap_q["scale_bytes"] == i8.k_scale.size * 4 * 2
    assert snap_q["bytes"] == i8.nbytes


# ---------------------------------------------------------------------------
# the quantized engine: first-token exactness, prefix adoption,
# preemption re-prefill, flags
# ---------------------------------------------------------------------------

# shared references, computed once (the tier-1 wall budget is tight on
# 1 core — every engine build is a compile)
_PA = np.arange(1, 9, dtype=np.int32)                # 2 full blocks
_PB = np.concatenate([_PA, [9, 10]]).astype(np.int32)
_MEMO = {}


def _f32_tokens():
    if "f32" not in _MEMO:
        eng = _engine("tq_ref")
        try:
            _MEMO["f32"] = eng.generate(_PA, max_new_tokens=4)["tokens"]
        finally:
            eng.close()
    return _MEMO["f32"]


def _int8_cold():
    if "cold" not in _MEMO:
        eng = _engine("tq_cold", cache_dtype="int8")
        try:
            assert eng.cache.quantized
            _MEMO["cold"] = {
                "tokA": eng.generate(_PA, max_new_tokens=4)["tokens"],
                "tokB": eng.generate(_PB, max_new_tokens=4)["tokens"],
                "leaked": eng.cache.allocator.leaked(),
                "block_bytes": eng._block_bytes,
                "kv_info": dict(Q._KV_INFO["tq_cold"]),
            }
        finally:
            eng.close()
    return _MEMO["cold"]


def test_int8_engine_first_token_exact_and_noted():
    """The first generated token samples inside prefill on fresh f32
    K/V — exact by construction regardless of the cache dtype."""
    cold = _int8_cold()
    assert cold["tokA"][0] == _f32_tokens()[0]
    assert cold["leaked"] == 0
    assert cold["kv_info"]["dtype"] == "int8"
    assert cold["kv_info"]["bytes_per_block"] == cold["block_bytes"]


def test_int8_prefix_adoption_carries_block_scales():
    """Leg (b) under the prefix cache: adopted quantized blocks must
    travel WITH their scale rows — a prefix-hit stream generates the
    same tokens as a cold int8 engine (identical quantized math)."""
    pA, pB = _PA, _PB
    wantA, wantB = _int8_cold()["tokA"], _int8_cold()["tokB"]
    eng = _engine("tq_pfx", cache_dtype="int8", prefix_cache=True)
    try:
        assert eng.generate(pA, max_new_tokens=4)["tokens"] == wantA
        # pB adopts pA's two quantized blocks (scales included): the
        # suffix prefill and every decode step read them dequantized
        assert eng.generate(pB, max_new_tokens=4)["tokens"] == wantB
        assert eng._pstats.prefix_hits.value >= 1
        assert eng._pstats.saved_prefill_tokens.value == 8
        assert eng.cache.allocator.leaked(eng.prefix.parked_blocks) == 0
    finally:
        eng.close()


def test_int8_overcommit_preempt_resume_is_loss_free():
    """Preemption + re-prefill on the quantized plane: a preempted
    stream resumes token-exact against an UNINTERRUPTED int8 engine
    (re-prefill requantizes the same tokens into fresh blocks — same
    codes, same scales, same math)."""
    prompts = [np.arange(1 + 7 * i, 7 + 7 * i, dtype=np.int32)
               for i in range(3)]
    ref = _engine("tqoc_ref", prefill_buckets=(8,), cache_dtype="int8")
    try:
        want = [ref.generate(p, max_new_tokens=10)["tokens"]
                for p in prompts]
    finally:
        ref.close()
    eng = _engine("tqoc", prefill_buckets=(8,), cache_dtype="int8",
                  num_blocks=9, overcommit=True)
    try:
        handles = [eng.submit(p, SamplingParams(max_new_tokens=10))
                   for p in prompts]
        got = [h.result(timeout=120) for h in handles]
        assert [g["tokens"] for g in got] == want
        assert eng._pstats.preempts.value >= 1
        assert eng._pstats.preempt_resumes.value >= 1
        assert eng.cache.allocator.leaked() == 0
    finally:
        eng.close()


def test_cache_dtype_is_latched_at_engine_build():
    eng = _engine("tq_flag", cache_dtype="int8")
    try:
        assert eng.cache.quantized and eng.cache.dtype == "int8"
        assert len(eng.cache.state()) == 4      # codes and scale pools
        assert eng.decodez()["cache"]["dtype"] == "int8"
    finally:
        eng.close()


def test_flags_off_surface_is_byte_identical():
    """Both flags off: the default engine's cache is the PR-19 f32
    layout bit for bit — 2-array state, no dtype/scale_bytes snapshot
    keys, the f32 nbytes formula — and the default pass pipeline has
    no quantize_int8 entry."""
    eng = _engine("tq_off")
    try:
        assert not eng.cache.quantized
        assert len(eng.cache.state()) == 2
        c = eng.cache
        assert c.nbytes == c.k.size * 4 * 2
        snap = c.snapshot()
        assert "dtype" not in snap and "scale_bytes" not in snap
    finally:
        eng.close()
    assert "quantize_int8" not in AnalysisConfig()._passes
    assert _flags.get_flags("int8_inference") is False


def test_quantz_page_payload_shapes():
    z = Q.quantz()
    assert set(z) == {"calibrated_layers", "counters", "kv_caches"}
    txt = Q.quantz_text()
    for section in ("int8 calibration", "quant.* counters",
                    "quantized KV caches"):
        assert section in txt
