"""Flash/ring attention parity tests (kernel correctness vs XLA math +
sequence-parallel ring vs full attention)."""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.kernels import attention as A

rng = np.random.RandomState(11)


def qkv(B=2, H=4, T=64, D=32, dtype="float32"):
    q = rng.randn(B, H, T, D).astype(dtype)
    k = rng.randn(B, H, T, D).astype(dtype)
    v = rng.randn(B, H, T, D).astype(dtype)
    mask = (rng.rand(B, T) > 0.2).astype("float32")
    mask[:, 0] = 1.0  # at least one valid key
    return q, k, v, mask


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_flash_matches_xla(causal):
    q, k, v, mask = qkv()
    ref = A.mha_xla(q, k, v, mask, causal=causal)
    got = A.mha_pallas(q, k, v, mask, causal=causal, block_q=32, block_k=32,
                       interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_pallas_nonmultiple_lengths():
    q, k, v, mask = qkv(T=50)
    ref = A.mha_xla(q, k, v, mask)
    got = A.mha_pallas(q, k, v, mask, block_q=32, block_k=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_grad_matches_xla_grad():
    q, k, v, mask = qkv(T=32)

    def loss_flash(q, k, v):
        return jnp.sum(A.flash_attention(q, k, v, mask, False, None) ** 2)

    def loss_xla(q, k, v):
        return jnp.sum(A.mha_xla(q, k, v, mask) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    q, k, v, mask = qkv(B=2, H=2, T=64, D=16)
    ref = A.mha_xla(q, k, v, mask, causal=causal)
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    spec = P(None, None, "sp", None)

    def ring(q, k, v, m):
        return A.ring_attention(q, k, v, m, "sp", causal=causal)

    got = jax.jit(jax.shard_map(
        ring, mesh=mesh, in_specs=(spec, spec, spec, P(None, "sp")),
        out_specs=spec))(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_attention_differentiable():
    q, k, v, mask = qkv(B=1, H=2, T=32, D=8)
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    spec = P(None, None, "sp", None)

    def loss(q, k, v):
        out = jax.shard_map(
            lambda q, k, v, m: A.ring_attention(q, k, v, m, "sp"),
            mesh=mesh, in_specs=(spec, spec, spec, P(None, "sp")),
            out_specs=spec)(q, k, v, mask)
        return jnp.sum(out ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(A.mha_xla(q, k, v, mask) ** 2)

    g1 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_ring_attention_causal_grads_match_xla():
    """Causal ring gradients (the lax.cond skip path + diagonal flash
    pair + dk/dv ring-return) == full-attention XLA autodiff."""
    q, k, v, mask = qkv(B=1, H=2, T=64, D=16)
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    spec = P(None, None, "sp", None)

    def loss(q, k, v):
        out = jax.shard_map(
            lambda q, k, v, m: A.ring_attention(q, k, v, m, "sp",
                                                causal=True),
            mesh=mesh, in_specs=(spec, spec, spec, P(None, "sp")),
            out_specs=spec)(q, k, v, mask)
        return jnp.sum(out ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(A.mha_xla(q, k, v, mask, causal=True) ** 2)

    g1 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_ring_attention_mask_none_under_shard_map():
    """kv_mask=None inside shard_map: the fresh ones mask must be marked
    varying over the ring axis (pvary) before entering ppermute carries
    — regression for the vma-check crash, fwd AND grads."""
    q, k, v, _ = qkv(B=1, H=2, T=64, D=16)
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    spec = P(None, None, "sp", None)

    def ring(q, k, v):
        return A.ring_attention(q, k, v, None, "sp", causal=True)

    sharded = jax.shard_map(ring, mesh=mesh, in_specs=(spec,) * 3,
                            out_specs=spec)
    out = sharded(q, k, v)
    ref = A.mha_xla(q, k, v, None, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    g1 = jax.grad(lambda q: jnp.sum(sharded(q, k, v) ** 2))(q)
    g2 = jax.grad(lambda q: jnp.sum(
        A.mha_xla(q, k, v, None, causal=True) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                               rtol=1e-4, atol=1e-4)


def test_ring_attention_dropout_deterministic_o_block_memory():
    """Ring dropout: counter-hash (no threefry), deterministic per seed,
    distinct bits per (q-shard, kv-shard) pair, and the fwd+bwd stay
    consistent (gradient of the dropped loss is a descent direction)."""
    q, k, v, mask = qkv(B=1, H=2, T=64, D=16)
    mesh = Mesh(np.array(jax.devices()), ("sp",))
    spec = P(None, None, "sp", None)

    def run(seed):
        return jax.shard_map(
            lambda q, k, v, m: A.ring_attention(
                q, k, v, m, "sp", dropout_rate=0.3,
                dropout_seed=jnp.asarray(seed, jnp.int32)),
            mesh=mesh, in_specs=(spec, spec, spec, P(None, "sp")),
            out_specs=spec)(q, k, v, mask)

    a, b, c = run(7), run(7), run(8)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.max(np.abs(np.asarray(a) - np.asarray(c))) > 1e-4
    no_drop = jax.shard_map(
        lambda q, k, v, m: A.ring_attention(q, k, v, m, "sp"),
        mesh=mesh, in_specs=(spec, spec, spec, P(None, "sp")),
        out_specs=spec)(q, k, v, mask)
    assert np.isfinite(np.asarray(no_drop)).all()
    # dropped output must differ from undropped (masks actually engage)
    assert np.max(np.abs(np.asarray(a) - np.asarray(no_drop))) > 1e-4


def test_fused_attention_op_in_program():
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.executor import Executor, Scope, scope_guard
    from paddle_tpu.core.program import Program, program_guard
    from paddle_tpu.layer_helper import LayerHelper

    B, H, T, D = 2, 2, 16, 8
    q, k, v, mask = qkv(B, H, T, D)
    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        qv = fluid.layers.data("q", [H, T, D])
        kv = fluid.layers.data("k", [H, T, D])
        vv = fluid.layers.data("v", [H, T, D])
        mv = fluid.layers.data("m", [T])
        helper = LayerHelper("fa")
        out = helper.create_variable_for_type_inference("float32", shape=(-1, H, T, D))
        helper.append_op("fused_attention",
                         {"Q": [qv], "K": [kv], "V": [vv], "KvMask": [mv]},
                         {"Out": [out]}, {"impl": "xla", "causal": True})
        loss = fluid.layers.mean(fluid.layers.square(out))
        grads = fluid.append_backward(loss, parameter_list=None)
    exe = Executor()
    with scope_guard(Scope()):
        (o,) = exe.run(prog, feed={"q": q, "k": k, "v": v, "m": mask},
                       fetch_list=[out])
    ref = A.mha_xla(q, k, v, mask, causal=True)
    np.testing.assert_allclose(o, np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_transformer_attention_impl_parity():
    """base op-chain, fused-xla, and pallas paths agree on the loss
    (guards the (m-1)*1e9 bias formula and the fused op wiring)."""
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.executor import Executor, Scope, scope_guard
    from paddle_tpu.core.program import Program, program_guard
    from paddle_tpu.models import transformer

    rng2 = np.random.RandomState(0)
    B, T = 4, 16
    m = np.zeros((B, T), "float32")
    for b in range(B):
        m[b, : rng2.randint(3, T + 1)] = 1
    feed = {"src_ids": rng2.randint(0, 50, (B, T)).astype("int64"),
            "tgt_ids": rng2.randint(0, 50, (B, T)).astype("int64"),
            "lbl_ids": rng2.randint(0, 50, (B, T)).astype("int64"),
            "src_mask": m, "tgt_mask": m}

    def run(impl):
        prog, startup = Program(), Program()
        prog.random_seed = 3
        startup.random_seed = 3
        with program_guard(prog, startup), unique_name.guard():
            _, loss, _ = transformer.build(
                src_vocab=50, tgt_vocab=50, max_len=16, d_model=32, n_head=4,
                d_ffn=64, n_layer=2, dropout=0.0, with_optimizer=False,
                attention_impl=impl)
        exe = Executor()
        with scope_guard(Scope()):
            exe.run(startup)
            (l,) = exe.run(prog, feed=feed, fetch_list=[loss])
        return float(l)

    base, fused, pallas = run("base"), run("xla"), run("pallas")
    assert abs(base - fused) < 2e-4, (base, fused)
    assert abs(base - pallas) < 1e-3, (base, pallas)


# ---------------------------------------------------------------------------
# Pallas backward kernels + in-kernel dropout
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [False, True])
def test_pallas_bwd_kernels_match_xla(causal):
    """dq/dk/dv from the tiled Pallas backward == XLA autodiff, with key
    padding masks and a genuinely multi-block grid (T=300 > 2x128: three
    q-blocks x three k-blocks exercises scratch resets and cross-block
    accumulation, plus ragged padding)."""
    q, k, v, mask = qkv(T=300, D=16)

    def loss_flash(q, k, v):
        return jnp.sum(A.flash_attention(q, k, v, mask, causal, None) ** 2)

    def loss_xla(q, k, v):
        return jnp.sum(A.mha_xla(q, k, v, mask, causal) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_pallas_bwd_bf16_grad_precision():
    """bf16 gradients from the Pallas backward must stay within intrinsic
    bf16 noise of the XLA chain (rel maxdiff ~0.01).  Regression pin for
    the reverted -delta-lane packing, which funneled the f32 delta
    through bf16 and inflated dq/dk error 5x (0.037 rel)."""
    rng = np.random.RandomState(0)
    B, H, T, D = 1, 2, 256, 64
    mk = lambda: jnp.asarray(rng.randn(B, H, T, D) * 0.3, jnp.bfloat16)
    q, k, v = mk(), mk(), mk()

    def loss_flash(q, k, v):
        return (A.flash_attention(q, k, v, None, True, None)
                .astype(jnp.float32) ** 2).sum()

    def loss_xla(q, k, v):
        return (A.mha_xla(q, k, v, None, True)
                .astype(jnp.float32) ** 2).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        rel = np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-6)
        assert rel < 0.02, f"bf16 grad rel maxdiff {rel:.4f} >= 0.02"


def test_pallas_bwd_cross_length_causal():
    """Tq < Tk causal (chunked-prefill shape): k-blocks entirely above the
    causal frontier must produce ZERO dk/dv, not a stale copy of the
    previous k-block's accumulator (regression: _first_qb clamping)."""
    B, H, D = 1, 2, 16
    q = rng.randn(B, H, 128, D).astype("float32")
    k = rng.randn(B, H, 256, D).astype("float32")
    v = rng.randn(B, H, 256, D).astype("float32")

    def loss_flash(q, k, v):
        return jnp.sum(A.flash_attention(q, k, v, None, True, None) ** 2)

    def loss_xla(q, k, v):
        return jnp.sum(A.mha_xla(q, k, v, None, True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)
    # keys past the causal frontier get exactly zero gradient
    np.testing.assert_array_equal(np.asarray(g1[1][:, :, 128:]), 0.0)
    np.testing.assert_array_equal(np.asarray(g1[2][:, :, 128:]), 0.0)


def test_flash_dropout_deterministic_and_scaled():
    q, k, v, mask = qkv(T=64)
    seed = jnp.asarray([42], jnp.int32)
    a1 = A.flash_attention(q, k, v, mask, False, None, 0.3, seed)
    a2 = A.flash_attention(q, k, v, mask, False, None, 0.3, seed)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    b = A.flash_attention(q, k, v, mask, False, None, 0.3,
                          jnp.asarray([43], jnp.int32))
    assert np.abs(np.asarray(a1) - np.asarray(b)).max() > 1e-4
    # dropout preserves the expectation (inverted scaling): means close
    base = A.flash_attention(q, k, v, mask, False, None)
    outs = [A.flash_attention(q, k, v, mask, False, None, 0.3,
                              jnp.asarray([s], jnp.int32))
            for s in range(16)]
    avg = np.mean([np.asarray(o) for o in outs], axis=0)
    corr = np.corrcoef(avg.ravel(), np.asarray(base).ravel())[0, 1]
    assert corr > 0.95, corr


def test_flash_dropout_grad_is_directional_derivative():
    """With a fixed seed the dropped attention is a deterministic function;
    its autodiff gradient must match a finite-difference directional
    derivative (validates the regenerated masks agree across fwd/dq/dkv)."""
    q, k, v, mask = qkv(B=1, H=2, T=32, D=16)
    seed = jnp.asarray([7], jnp.int32)
    rate = 0.4

    def f(q, k, v):
        return jnp.sum(A.flash_attention(q, k, v, mask, False, None,
                                         rate, seed) ** 2)

    g = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    rs = np.random.RandomState(3)
    for i, x in enumerate((q, k, v)):
        d = rs.randn(*x.shape).astype("float32")
        eps = 1e-2
        args_p = [q, k, v]
        args_m = [q, k, v]
        args_p[i] = x + eps * d
        args_m[i] = x - eps * d
        num = (float(f(*args_p)) - float(f(*args_m))) / (2 * eps)
        ana = float(jnp.vdot(g[i], d))
        np.testing.assert_allclose(num, ana, rtol=2e-2, atol=2e-2)


def test_dropout_engages_in_lowered_hlo():
    """A training program with attention dropout_rate > 0 must carry the
    regenerable-dropout hash in its lowered computation (the murmur
    finalizer constant 0x7FEB352D), and lose it at dropout=0 — verifying
    dropout is live in the compiled step, not silently elided."""
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.executor import Executor, Scope, scope_guard
    from paddle_tpu.core.lowering import analyze_block, build_block_fn
    from paddle_tpu.core.program import Program, program_guard
    from paddle_tpu.models import transformer

    def hlo_for(dropout):
        prog, startup = Program(), Program()
        prog.random_seed = 3
        with program_guard(prog, startup), unique_name.guard():
            feeds, loss, _ = transformer.build(
                src_vocab=50, tgt_vocab=50, max_len=8, d_model=16,
                n_head=2, d_ffn=32, n_layer=1, dropout=dropout,
                attention_impl="xla")
        B, T = 2, 8
        r = np.random.RandomState(0)
        feed = {"src_ids": r.randint(0, 50, (B, T)).astype("int64"),
                "tgt_ids": r.randint(0, 50, (B, T)).astype("int64"),
                "lbl_ids": r.randint(0, 50, (B, T)).astype("int64"),
                "src_mask": np.ones((B, T), "float32"),
                "tgt_mask": np.ones((B, T), "float32")}
        scope, exe = Scope(), Executor()
        with scope_guard(scope):
            exe.run(startup)
            ordered = sorted(feed)
            plan = analyze_block(prog, 0, ordered, [loss.name])
            fn = build_block_fn(prog, plan)
            args = ([jnp.asarray(feed[n]) for n in ordered],
                    [jnp.asarray(np.asarray(scope.find_var(n)))
                     for n in plan.donated_reads],
                    [jnp.asarray(np.asarray(scope.find_var(n)))
                     for n in plan.const_reads],
                    jax.random.PRNGKey(0))
            return jax.jit(fn).lower(*args).as_text()

    hash_const = str(0x7FEB352D)
    assert hash_const in hlo_for(0.1)
    assert hash_const not in hlo_for(0.0)


# ---------------------------------------------------------------------------
# short-sequence kernel (kernels/short_attention.py): one forward, ONE
# backward, whole key sequence resident; the same function as mha_xla,
# dropout included, up to the order of sums
# ---------------------------------------------------------------------------

from paddle_tpu.kernels import short_attention as S  # noqa: E402
from paddle_tpu.ops import attention_ops as O  # noqa: E402

SHORT_SEED = jnp.asarray([20240611], jnp.int32)
# (Tq, Tk, D, dtype, causal, masked, rate, head_group, q_chunk)
SHORT_CASES = {
    "plain": (64, 64, 32, "float32", False, False, 0.0, None, None),
    "masked": (64, 64, 32, "float32", False, True, 0.0, None, None),
    "causal": (64, 64, 32, "float32", True, False, 0.0, None, None),
    "causal_masked": (64, 64, 32, "float32", True, True, 0.0, None, None),
    "dropout": (64, 64, 32, "float32", False, False, 0.3, None, None),
    "dropout_causal_masked": (64, 64, 32, "float32", True, True, 0.3, None,
                              None),
    "cross": (48, 80, 32, "float32", False, True, 0.0, None, None),
    "cross_dropout": (48, 80, 32, "float32", False, True, 0.2, None, None),
    "cross_longer_queries": (200, 72, 16, "float32", False, True, 0.2, None,
                             None),
    "ragged": (50, 50, 32, "float32", True, True, 0.25, None, None),
    "ragged_over_a_tile": (300, 300, 16, "float32", True, True, 0.2, None,
                           128),
    # a lane group is two heads of 64, one of 128: a step takes whole groups
    "two_heads_a_step": (64, 64, 64, "float32", True, True, 0.3, 2, None),
    "one_head_a_step": (64, 64, 128, "float32", False, True, 0.3, 1, None),
    "causal_tiles_end_at_the_diagonal": (384, 384, 16, "float32", True,
                                         False, 0.2, None, 128),
    "plain_tiles": (256, 256, 64, "float32", False, True, 0.2, 2, 128),
    "causal_tiles_two_groups": (256, 256, 64, "float32", True, True, 0.2, 4,
                                128),
    "bf16": (256, 256, 64, "bfloat16", False, True, 0.0, None, None),
    "bf16_dropout_causal": (256, 256, 64, "bfloat16", True, True, 0.1, None,
                            None),
}


def _short_inputs(case):
    Tq, Tk, D, dtype, causal, masked, rate, hg, cq = SHORT_CASES[case]
    r = np.random.RandomState(sorted(SHORT_CASES).index(case))
    B, H = 2, 4
    mk = lambda T: jnp.asarray(r.randn(B, H, T, D) * 0.5, dtype)  # noqa: E731
    q, k, v, do = mk(Tq), mk(Tk), mk(Tk), mk(Tq)
    mask = None
    if masked:
        m = (r.rand(B, Tk) > 0.2).astype("float32")
        m[:, 0] = 1.0
        mask = jnp.asarray(m)
    return q, k, v, do, mask


def _short_both(case):
    """(o, dq, dk, dv) of the kernel and of mha_xla and its vjp."""
    Tq, Tk, D, dtype, causal, masked, rate, hg, cq = SHORT_CASES[case]
    q, k, v, do, mask = _short_inputs(case)
    kw = dict(causal=causal, sm_scale=S._scale(q, None), rate=rate,
              interpret=True, head_group=hg, q_chunk=cq)
    seeds = S._seeds(SHORT_SEED, None)
    H = q.shape[1]
    q2, k2, v2, do2 = (S._merged(x) for x in (q, k, v, do))  # [B, T, H*D]
    o2, lse = S._forward(seeds, q2, k2, v2, mask, heads=H, **kw)
    got = (o2,) + tuple(S._backward(seeds, q2, k2, v2, mask, o2, lse, do2,
                                    **kw))
    got = tuple(S._split(x, H) for x in got)
    ref, vjp = jax.vjp(lambda q, k, v: A.mha_xla(
        q, k, v, mask, causal, None, dropout_rate=rate,
        dropout_seed=SHORT_SEED), q, k, v)
    return got, (ref,) + tuple(vjp(do))


@pytest.mark.parametrize("case", sorted(SHORT_CASES))
def test_short_attention_matches_xla_forward_and_gradients(case):
    """o, dq, dk, dv against mha_xla and its autodiff — with dropout ON the
    kernel drops mha_xla's elements (same seed, same global b, h, q, k), so
    the comparison is elementwise, not statistical."""
    got, want = _short_both(case)
    bf16 = SHORT_CASES[case][3] == "bfloat16"
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape, name
        if bf16:
            rel = np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-6)
            assert rel < 0.02, (name, rel)
        else:
            np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5,
                                       err_msg=name)


def test_short_attention_drops_the_elements_mha_xla_drops():
    """With v the identity the output IS the dropped probability matrix:
    the zeros of the two paths are the same elements."""
    B, H, T = 2, 2, 64
    r = np.random.RandomState(5)
    q = jnp.asarray(r.randn(B, H, T, T) * 0.3, "float32")
    k = jnp.asarray(r.randn(B, H, T, T) * 0.3, "float32")
    v = jnp.broadcast_to(jnp.eye(T, dtype="float32"), (B, H, T, T))
    a = S.short_attention(q, k, v, None, SHORT_SEED, None, False, None, 0.4)
    b = A.mha_xla(q, k, v, None, False, None, dropout_rate=0.4,
                  dropout_seed=SHORT_SEED)
    dropped = np.asarray(b) == 0.0
    assert 0.3 < dropped.mean() < 0.5
    np.testing.assert_array_equal(np.asarray(a) == 0.0, dropped)
    c = S.short_attention(q, k, v, None, SHORT_SEED + 1, None, False, None,
                          0.4)
    assert ((np.asarray(c) == 0.0) != dropped).mean() > 0.3


def test_short_attention_custom_vjp_matches_xla_grad():
    q, k, v, _, mask = _short_inputs("dropout_causal_masked")

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v) ** 2)

    g1 = jax.grad(loss(lambda q, k, v: S.short_attention(
        q, k, v, mask, SHORT_SEED, None, True, None, 0.3)), (0, 1, 2))(q, k, v)
    g2 = jax.grad(loss(lambda q, k, v: A.mha_xla(
        q, k, v, mask, True, None, dropout_rate=0.3,
        dropout_seed=SHORT_SEED)), (0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_short_attention_row_without_a_visible_key_is_zero():
    """The one place the kernel departs from mha_xla: the flash kernel's
    convention for a query that sees no key."""
    q, k, v, do, _ = _short_inputs("plain")
    mask = jnp.ones((2, 64), "float32").at[1].set(0.0)
    f = lambda q, k, v: S.short_attention(  # noqa: E731
        q, k, v, mask, None, None, False, None, 0.0)
    o, vjp = jax.vjp(f, q, k, v)
    assert np.all(np.asarray(o[1]) == 0.0) and np.isfinite(np.asarray(o)).all()
    for g in vjp(do):
        assert np.all(np.asarray(g[1]) == 0.0)
        assert np.isfinite(np.asarray(g)).all()


def test_short_attention_traces_one_forward_a_variant():
    """Eighteen attentions of two variants trace two forwards: the inner
    jit hands every later call the first one's jaxpr."""
    q, k, v, _, mask = _short_inputs("masked")

    def net(q, k, v):
        x = q
        for i in range(6):
            x = S.short_attention(x, k, v, mask, SHORT_SEED + i, None,
                                  i % 2 == 1, None, 0.1)
        return jnp.sum(x ** 2)

    text = jax.jit(jax.grad(net)).lower(q, k, v).as_text()
    assert len(re.findall(r"func\.func private @_forward", text)) == 2
    assert len(re.findall(r"func\.func private @_backward", text)) == 2


@pytest.mark.parametrize("backend, Tq, Tk, D, dtype, want", [
    ("cpu", 256, 256, 64, "bfloat16", "xla"),
    ("gpu", 256, 256, 64, "bfloat16", "xla"),
    ("cpu", 8192, 8192, 128, "bfloat16", "xla"),
    ("tpu", 256, 256, 64, "bfloat16", "short"),
    ("tpu", 256, 256, 64, "float32", "short"),
    ("tpu", 64, 256, 64, "bfloat16", "short"),
    ("tpu", 512, 512, 64, "bfloat16", "short"),
    ("tpu", 1024, 1024, 64, "bfloat16", "short"),
    ("tpu", 256, 256, 128, "bfloat16", "short"),
    ("tpu", 1025, 1025, 64, "bfloat16", "xla"),
    ("tpu", 256, 1536, 64, "bfloat16", "xla"),
    ("tpu", 2047, 2047, 128, "bfloat16", "xla"),
    ("tpu", 256, 256, 64, "float16", "xla"),
    ("tpu", 256, 256, 96, "bfloat16", "xla"),  # 96 lanes fill no group
    ("tpu", 256, 256, 32, "bfloat16", "short"),
    # the flash kernel's thresholds, as they were
    ("tpu", 2048, 2048, 128, "bfloat16", "pallas"),
    ("tpu", 2048, 2048, 64, "bfloat16", "xla"),
    ("tpu", 4095, 4095, 64, "bfloat16", "xla"),
    ("tpu", 4096, 4096, 64, "bfloat16", "pallas"),
    ("tpu", 256, 4096, 64, "bfloat16", "pallas"),
    ("tpu", 8192, 8192, 256, "bfloat16", "pallas"),
])
def test_auto_chooses_by_backend_and_shape(backend, Tq, Tk, D, dtype, want):
    got = O._auto_impl(backend, (96, 8, Tq, D), (96, 8, Tk, D), dtype)
    assert got == want


def test_auto_keeps_xla_under_a_mesh_it_has_no_spec_for():
    devs = np.array(jax.devices())
    shapes = (96, 8, 256, 64), (96, 8, 256, 64), "bfloat16"
    dp4 = Mesh(devs[:4], ("dp",))
    assert O._auto_impl("tpu", *shapes, mesh=dp4) == "short"
    assert O._auto_impl("tpu", *shapes, mesh=Mesh(
        devs[:4].reshape(4, 1), ("dp", "mp"))) == "short"
    assert O._auto_impl("tpu", *shapes, mesh=Mesh(
        devs[:4].reshape(2, 2), ("dp", "mp"))) == "xla"
    assert O._auto_impl("tpu", *shapes, mesh=Mesh(devs[:4], ("sp",))) == "xla"
    assert O._auto_impl("tpu", (6, 8, 256, 64), (6, 8, 256, 64), "bfloat16",
                        mesh=dp4) == "xla"  # 6 rows over 4 shards
    # no mesh, yet compiled across devices: nothing to wrap the kernel over
    assert O._auto_impl("tpu", *shapes, spans_devices=True) == "xla"
    assert O._auto_impl("tpu", *shapes, mesh=dp4, spans_devices=True) == "short"


def test_short_plan_reckons_heads_and_rows_from_the_shapes():
    assert S.plan(8, 256, 256, 64, 2, backward=True) == (8, 256)
    assert S.plan(8, 256, 256, 64, 2) == (8, 256)
    assert S.plan(8, 512, 512, 64, 2, backward=True) == (4, 256)
    assert S.plan(8, 1024, 1024, 64, 4, backward=True) == (2, 256)
    assert S.plan(8, 64, 1024, 64, 2) == (8, 128)
    assert S.plan(8, 200, 200, 64, 2) == (8, 256)  # padded to the lane width
    assert S.plan(8, 16384, 16384, 128, 2, backward=True) is None


@pytest.mark.parametrize("masked", [True, False])
def test_short_attention_under_a_dp_mesh_is_the_unsharded_result(masked):
    """Per shard under shard_map: the four-device result (and gradients) are
    the one-device mha_xla's, dropout included — a shard hashes its rows by
    their place in the GLOBAL batch — and the compiled text holds no
    collective: GSPMD gathered nothing."""
    from jax.sharding import NamedSharding
    r = np.random.RandomState(3)
    B, H, T, D = 8, 2, 32, 16
    mk = lambda: jnp.asarray(r.randn(B, H, T, D) * 0.5, "float32")  # noqa: E731
    q, k, v = mk(), mk(), mk()
    mask = None
    if masked:
        mask = jnp.asarray((r.rand(B, T) > 0.2).astype("float32")
                           ).at[:, 0].set(1.0)
    mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
    rows = NamedSharding(mesh, P("dp"))
    qs, ks, vs = (jax.device_put(x, rows) for x in (q, k, v))

    def short(q, k, v):
        return O._short(mesh, q, k, v, mask, SHORT_SEED, True, None, 0.25)

    def xla(q, k, v):
        return A.mha_xla(q, k, v, mask, True, None, dropout_rate=0.25,
                         dropout_seed=SHORT_SEED)

    compiled = jax.jit(short).lower(qs, ks, vs).compile()
    np.testing.assert_allclose(np.asarray(compiled(qs, ks, vs)),
                               np.asarray(xla(q, k, v)), rtol=2e-5, atol=2e-5)
    grad = lambda f: jax.grad(  # noqa: E731
        lambda q, k, v: jnp.sum(f(q, k, v) ** 2), (0, 1, 2))
    gcomp = jax.jit(grad(short)).lower(qs, ks, vs).compile()
    for a, b in zip(gcomp(qs, ks, vs), grad(xla)(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)
    for text in (compiled.as_text(), gcomp.as_text()):
        for collective in ("all-gather", "all-reduce", "all-to-all",
                           "collective-permute"):
            assert collective not in text, collective


def _fused_attention_program(monkeypatch, impl_for_auto):
    """A program of one fused_attention at [2, 8, 256, 64] with dropout and
    a mean-square loss, with ``auto`` resolved as on a TPU (the kernel then
    runs in interpret mode here)."""
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.program import Program, program_guard
    from paddle_tpu.layer_helper import LayerHelper

    monkeypatch.setattr(O, "_auto_impl", lambda *a, **kw: impl_for_auto)
    B, H, T, D = 2, 8, 256, 64
    prog, startup = Program(), Program()
    with program_guard(prog, startup), unique_name.guard():
        qv, kv, vv = (fluid.layers.data(n, [H, T, D]) for n in "qkv")
        for var in (qv, kv, vv):
            var.stop_gradient = False
        mv = fluid.layers.data("m", [T])
        sv = fluid.layers.data("s", [1], dtype="int32",
                               append_batch_size=False)
        helper = LayerHelper("fa")
        out = helper.create_variable_for_type_inference(
            "float32", shape=(-1, H, T, D))
        helper.append_op(
            "fused_attention",
            {"Q": [qv], "K": [kv], "V": [vv], "KvMask": [mv], "Seed": [sv]},
            {"Out": [out]}, {"impl": "auto", "causal": True,
                             "dropout_rate": 0.1})
        loss = fluid.layers.mean(fluid.layers.square(out))
        fluid.append_backward(loss, parameter_list=None)
    r = np.random.RandomState(0)
    feed = {n: (r.randn(B, H, T, D) * 0.5).astype("float32") for n in "qkv"}
    feed["m"] = np.ones((B, T), "float32")
    feed["s"] = np.asarray([99], "int32")
    grads = [prog.global_block.var(f"{n}@GRAD") for n in "qkv"]
    return prog, feed, [out, loss] + grads


def test_fused_attention_auto_short_in_a_program_matches_xla(monkeypatch):
    """The op's auto path through Executor, forward and fused_attention_grad,
    against the same program lowered to mha_xla; the registry counts what
    auto chose."""
    from paddle_tpu.core.executor import Executor, Scope, scope_guard
    from paddle_tpu.observability import stats

    results = {}
    for impl in ("short", "xla"):
        counter = stats.scope("attn").counter(f"fused_auto_{impl}")
        before = counter.value
        prog, feed, fetch = _fused_attention_program(monkeypatch, impl)
        with scope_guard(Scope()):
            results[impl] = Executor().run(prog, feed=feed, fetch_list=fetch)
        assert counter.value > before, impl
    for a, b in zip(results["short"], results["xla"]):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("spread", [False, True])
def test_a_plain_executor_tells_auto_when_its_arrays_span_devices(
        monkeypatch, spread):
    """A plain Executor has no mesh; over a scope that a ParallelExecutor
    placed (here: a feed laid over four devices) jit compiles one partitioned
    program all the same, which no Mosaic kernel survives — the executor
    latches that and the lowering hands it to ``auto``."""
    from jax.sharding import NamedSharding
    from paddle_tpu.core.executor import Executor, Scope, scope_guard

    prog, feed, fetch = _fused_attention_program(monkeypatch, "xla")
    seen = []
    monkeypatch.setattr(
        O, "_auto_impl",
        lambda backend, q, k, dtype, mesh=None, spans_devices=False:
        seen.append((mesh, spans_devices)) or "xla")
    if spread:
        mesh = Mesh(np.array(jax.devices()[:4]), ("dp",))
        feed["m"] = jax.device_put(feed["m"], NamedSharding(mesh, P()))
    with scope_guard(Scope()):
        exe = Executor()
        exe.run(prog, feed=feed, fetch_list=fetch)
    assert seen and all(s == (None, spread) for s in seen)
    assert exe._spans_devices == spread


def test_the_train_cells_path_on_a_mesh_then_a_plain_executor(monkeypatch):
    """``tfbase_train_dp4``'s own sequence: ParallelExecutor dp=4 trains
    (``auto`` takes the kernel per shard), then a plain Executor evaluates
    the dropout-free program over the scope the mesh placed — ``auto``
    resolved as on a TPU throughout.  The evaluation keeps ``mha_xla``."""
    import paddle_tpu as fluid
    from paddle_tpu.core import unique_name
    from paddle_tpu.core.executor import Executor, Scope
    from paddle_tpu.core.program import Program, program_guard
    from paddle_tpu.models import transformer
    from paddle_tpu.observability import stats
    from paddle_tpu.parallel import BuildStrategy, ParallelExecutor

    real = O._auto_impl
    monkeypatch.setattr(O, "_auto_impl",
                        lambda backend, *a, **kw: real("tpu", *a, **kw))

    def build(**kw):
        prog, startup = Program(), Program()
        prog.random_seed = startup.random_seed = 7
        with program_guard(prog, startup), unique_name.guard():
            out = transformer.build(
                src_vocab=32, tgt_vocab=32, max_len=8, d_model=128, n_head=2,
                d_ffn=32, n_layer=1, attention_impl="auto", **kw)
        return prog, startup, out

    prog, startup, (_, loss, _) = build(dropout=0.1, warmup_steps=10)
    evalp, _, (_, eval_loss, _) = build(dropout=0.0, with_optimizer=False)
    r = np.random.RandomState(0)
    ids = lambda: r.randint(0, 32, (4, 8)).astype("int64")  # noqa: E731
    ones = np.ones((4, 8), "float32")
    feed = {"src_ids": ids(), "tgt_ids": ids(), "lbl_ids": ids(),
            "src_mask": ones, "tgt_mask": ones}
    chose = {i: stats.scope("attn").counter(f"fused_auto_{i}")
             for i in ("short", "xla")}
    count = lambda: {i: c.value for i, c in chose.items()}  # noqa: E731

    scope, exe = Scope(), Executor()
    exe.run(startup, scope=scope)
    pe = ParallelExecutor(
        loss_name=loss.name, main_program=prog, scope=scope,
        places=jax.devices()[:4],
        build_strategy=BuildStrategy(mesh_shape={"dp": 4}))
    c0 = count()
    (trained,) = pe.run(feed=feed, fetch_list=[loss.name])
    c1 = count()
    assert np.isfinite(trained).all()
    assert c1["short"] > c0["short"] and c1["xla"] == c0["xla"]
    assert not exe._spans_devices
    (got,) = exe.run(evalp, feed=feed, fetch_list=[eval_loss], scope=scope)
    c2 = count()
    assert np.isfinite(got).all()
    assert exe._spans_devices
    assert c2["xla"] > c1["xla"] and c2["short"] == c1["short"]
    pe.close()
    exe.close()


@pytest.mark.parametrize("impl, holds_scores", [("short", False),
                                                ("xla", True)])
def test_lowered_fused_attention_keeps_scores_out_of_hbm(monkeypatch, impl,
                                                         holds_scores):
    """At [., 8, 256, 64] the lowered text of forward + backward holds no
    f32[B, H, Tq, Tk] with the kernel (interpret mode lowers its body to
    per-head [256, 256] tiles), and does with mha_xla."""
    from paddle_tpu.core.executor import Executor, Scope, scope_guard
    from paddle_tpu.core.lowering import analyze_block, build_block_fn
    prog, feed, fetch = _fused_attention_program(monkeypatch, impl)
    names = sorted(feed)
    with scope_guard(Scope()):
        plan = analyze_block(prog, 0, names, [f.name for f in fetch])
        fn = build_block_fn(prog, plan)
        text = jax.jit(fn).lower([jnp.asarray(feed[n]) for n in names], [],
                                 [], jax.random.PRNGKey(0)).as_text()
    assert ("tensor<2x8x256x256xf32>" in text) == holds_scores
    assert ("tensor<256x256xf32>" in text) != holds_scores
