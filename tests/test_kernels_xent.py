"""``kernels/xent.py proj_xent_fwd``: the output projection that carries each
row's running maximum and rescaled sum of exponentials across its vocabulary
tiles, against ``jnp.matmul`` + ``logsumexp`` (interpret mode: what the TPU's
compiler makes of it is ``tests/test_xent_v5e_compile.py``'s and
``chip_smoke.phase_proj_xent``'s), and the choice between the two that the
op ``fc_softmax_with_cross_entropy`` makes from what it can observe."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from paddle_tpu.kernels import xent
from paddle_tpu.ops import nn_ops

D = 128


def _operands(rows, n, v, seed=0):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(kx, (n, D), rows)
    # a column scale that spreads the logits over a few units, and a bias
    # column far above the rest so that a later tile moves the maximum
    w = jax.random.normal(kw, (D, v), jnp.float32) * 0.2
    w = w.at[:, v - 3].mul(8.0).astype(jnp.bfloat16)
    return x, w


def _reference(x, w):
    """The kernel's mathematics spelled out: rows rounded to bf16 once (what
    XLA's default-precision product does on a TPU and not on the CPU), every
    product exact in float32, the log-sum-exp of what was written."""
    logits = jnp.matmul(x.astype(jnp.bfloat16).astype(jnp.float32),
                        w.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST).astype(
        jnp.result_type(x, w))
    return logits, jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1,
                                    keepdims=True)


@pytest.mark.parametrize("rows", [jnp.float32, jnp.bfloat16],
                         ids=["f32_rows", "bf16_rows"])
@pytest.mark.parametrize("n, v, seq, tm, tn", [
    (512, 1000, 128, 256, 256),     # a ragged last tile: 232 of 256 classes
    (256, 512, 128, 128, 256),      # whole tiles, a sequence a row block
    (256, 200, 256, 256, 256),      # one vocabulary tile, ragged
    (512, 256, 128, 256, 256),      # one vocabulary tile, whole
    (384, 300, 128, 128, 128),      # three row blocks, a tile of 44 classes
], ids=["ragged", "whole", "one_ragged", "one_whole", "narrow"])
def test_the_kernel_is_the_product_and_its_log_sum_exp(rows, n, v, seq, tm,
                                                       tn):
    x, w = _operands(rows, n, v)
    logits, lse = xent.proj_xent_fwd(x, w, seq=seq, tm=tm, tn=tn)
    want, want_lse = _reference(x, w)
    assert logits.dtype == want.dtype == jnp.matmul(x, w).dtype
    # a view, sequence by sequence, of what was written [n / seq, v, seq]
    assert logits.shape == (n // seq, seq, v)
    assert lse.shape == (n // seq, seq, 1) and lse.dtype == jnp.float32
    logits, lse = logits.reshape(n, v), lse.reshape(n, 1)
    got = np.asarray(logits, np.float64)
    # float32: the accumulation order differs; bf16: an ulp of the rounding
    tol = 2e-5 if rows == jnp.float32 else 2 ** -7
    np.testing.assert_allclose(got, np.asarray(want, np.float64),
                               rtol=tol, atol=tol)
    # the statistics are of the logits as WRITTEN, in float32: held to the
    # log-sum-exp of the kernel's own output, which no rounding of the
    # product reaches
    own = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(own), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=tol, atol=tol)
    assert np.isfinite(got).all() and np.isfinite(np.asarray(lse)).all()
    # the planted class carries the maximum somewhere past the first tile
    assert (got.argmax(-1) >= min(tn, v) - 3).any() or v <= tn


def test_a_later_tile_that_raises_the_maximum_rescales_the_sum():
    """Rows whose largest logit stands in the LAST tile, far above the rest:
    a sum that was not rescaled to the new maximum would overflow or vanish."""
    x = jnp.ones((128, D), jnp.float32)
    w = jnp.zeros((D, 512), jnp.float32).at[:, 500].set(0.5).at[:, 3].set(
        -0.5).astype(jnp.bfloat16)          # logits 0 but 64 and -64
    logits, lse = xent.proj_xent_fwd(x, w, seq=128, tm=128, tn=128)
    assert float(logits[0, 0, 500]) == 64.0
    assert float(logits[0, 0, 3]) == -64.0
    np.testing.assert_allclose(np.asarray(lse), 64.0, rtol=1e-6)


def test_the_xla_form_is_matmul_and_logsumexp():
    x, w = _operands(jnp.float32, 24, 77)
    logits, lse = xent.proj_xent_xla(x, w)
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(jnp.matmul(x, w)))
    np.testing.assert_array_equal(
        np.asarray(lse), np.asarray(jax.nn.logsumexp(
            jnp.matmul(x, w), axis=-1, keepdims=True)))


@pytest.mark.parametrize("n, d, seq, dtype", [
    (320, D, 128, jnp.bfloat16),    # rows short of a block
    (256, 96, 128, jnp.bfloat16),   # a contraction short of a lane tile
    (256, D, 64, jnp.bfloat16),     # sequences short of a lane tile
    (256, D, 128, jnp.float32),     # no product for a float32 weight
], ids=["rows", "lanes", "sequences", "f32_weight"])
def test_operands_that_do_not_fit_are_refused(n, d, seq, dtype):
    x = jnp.zeros((n, d), jnp.float32)
    with pytest.raises(ValueError, match="does not fit"):
        xent.proj_xent_fwd(x, jnp.zeros((d, 256), dtype), seq=seq, tm=256,
                           tn=128)


def test_fits_is_whole_blocks_of_whole_sequences_and_whole_lane_tiles():
    assert xent.fits(24576, 512, 256) and xent.fits(4096, 512, 256)
    assert xent.fits(xent.ROW_BLOCK, 128, xent.ROW_BLOCK)
    assert not xent.fits(24576 + 256, 512, 256)     # rows short of a block
    assert not xent.fits(24576, 500, 256)           # the contraction
    assert not xent.fits(24576, 512, 192)           # a sequence's lanes
    assert not xent.fits(24576, 512, 384)           # sequences a block
    assert not xent.fits(24576, 512, 0)
    assert not xent.fits(256, 512, 128) and xent.fits(256, 512, 128, tm=128)
    # the call asks for the VMEM its tile needs, a fifth of the chip's at
    # the tile it runs at: the rest stays XLA's
    assert xent._vmem_bytes(xent.ROW_BLOCK, xent.VOCAB_TILE, 512, 2) \
        < 32 * 2 ** 20


def test_the_op_takes_the_kernel_only_where_it_can_observe_that_it_fits():
    devs = np.array(jax.devices()[:4])
    dp4 = Mesh(devs, ("dp",))
    cell = (24576, 512, 256, jnp.float32, jnp.bfloat16)
    choose = nn_ops._proj_xent_impl
    assert choose("tpu", *cell) == "kernel"
    assert choose("tpu", 4096, 512, 256, jnp.bfloat16, jnp.bfloat16) == "kernel"
    assert choose("cpu", *cell) == choose("gpu", *cell) == "xla"
    # rows short of a block, a contraction or sequences short of a lane
    # tile, operands the kernel has no product for
    assert choose("tpu", 24576 + 256, *cell[1:]) == "xla"
    assert choose("tpu", 24576, 500, *cell[2:]) == "xla"
    assert choose("tpu", 24576, 512, 64, *cell[3:]) == "xla"
    assert choose("tpu", 24576, 512, 256, jnp.float32, jnp.float32) == "xla"
    assert choose("tpu", 24576, 512, 256, jnp.float64, jnp.bfloat16) == "xla"
    # a mesh: every SHARD's rows fill whole blocks, and dp is its only axis
    assert choose("tpu", 4 * 24576, *cell[1:], mesh=dp4) == "kernel"
    assert choose("tpu", 2 * xent.ROW_BLOCK, *cell[1:], mesh=dp4) == "xla"
    assert choose("tpu", 4 * 24576, *cell[1:], mesh=Mesh(
        devs.reshape(2, 2), ("dp", "mp"))) == "xla"
    assert choose("tpu", 4 * 24576, *cell[1:], mesh=Mesh(
        devs.reshape(4, 1), ("dp", "mp"))) == "kernel"
    assert choose("tpu", *cell, mesh=Mesh(devs, ("sp",))) == "xla"
    # no mesh, yet compiled across devices: nothing to wrap the kernel over
    assert choose("tpu", *cell, spans_devices=True) == "xla"
    assert choose("tpu", 4 * 24576, *cell[1:], mesh=dp4,
                  spans_devices=True) == "kernel"
