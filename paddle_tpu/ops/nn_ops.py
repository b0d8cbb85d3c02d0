"""Neural-net op lowerings: conv, pool, normalization, losses, recurrent
cells.

Reference coverage: ``conv_op.cc``/``conv_cudnn_op.cu.cc``, ``pool_op.cc``,
``batch_norm_op.cc``, ``layer_norm_op.cc``, ``cross_entropy_op.cc``,
``softmax_with_cross_entropy_op.cc``, ``accuracy_op.cc``, ``lstm_op.cc`` +
``math/lstm_compute``, ``gru_op.cc``, ``conv2d_transpose``, ``norm_op.cc``,
``huber_loss``/``square_error_cost``-style losses.

TPU mapping: convs/matmuls go through lax.conv_general_dilated / jnp.matmul
(MXU); recurrences are ``lax.scan`` over padded [B,T,...] tensors with a
length mask — the static-shape replacement for the reference's LoDTensor
batch⇄sequence machinery (``math/sequence2batch.h``).  Gradients come from
the vjp default rule (scan differentiates to reverse-scan, the functional
equivalent of the reference's recurrent grad machinery).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.registry import _scope, get as get_opdef, register, \
    register_grad, vjp_grad
from ..kernels import xent as _xent_kernel
from ..observability import stats as _obs_stats
from .attention_ops import _dp_only
from .math_ops import flatten_to_2d


def _pair(v):
    return tuple(v) if isinstance(v, (list, tuple)) else (v, v)


def _stat_dtype(x):
    """Statistics dtype: at least f32 (bf16 inputs promote), keep f64."""
    return jnp.promote_types(x.dtype, jnp.float32)


# ---------------------------------------------------------------------------
# conv / pool
# ---------------------------------------------------------------------------

@register("conv2d", no_grad_slots=())
def _conv2d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dil = _pair(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1)
    layout = attrs.get("data_layout", "NCHW")
    # Filter params are always OIHW (the reference's storage layout) so
    # checkpoints stay layout-independent; for NHWC activations the spec
    # string retargets the conv and XLA folds the constant-strided filter
    # view into its im2col read.
    if (layout == "NHWC" and x.shape[-1] <= 4 and strides == (2, 2)
            and pads == (3, 3) and w.shape[2:] == (7, 7)
            and dil == (1, 1) and groups == 1
            and x.shape[1] % 2 == 0 and x.shape[2] % 2 == 0):
        # Space-to-depth stem (the MLPerf ResNet trick, exact): a 7x7/s2/p3
        # conv on <=4 input channels runs at ~2% MXU utilization (3 lanes of
        # 128).  Fold 2x2 pixel blocks into channels (12 lanes), zero-pad
        # the kernel to 8x8 and rearrange to 4x4 in block space — identical
        # math (the zero taps contribute nothing and their grads are
        # discarded by pad's vjp), 4x the lane occupancy.
        b, h, wd, c = x.shape
        o = w.shape[0]
        xs = x.reshape(b, h // 2, 2, wd // 2, 2, c)
        xs = xs.transpose(0, 1, 3, 2, 4, 5).reshape(b, h // 2, wd // 2, 4 * c)
        wp = jnp.pad(w.transpose(2, 3, 1, 0), ((1, 0), (1, 0), (0, 0), (0, 0)))
        ws = wp.reshape(4, 2, 4, 2, c, o).transpose(0, 2, 1, 3, 4, 5)
        ws = ws.reshape(4, 4, 4 * c, o)
        out = lax.conv_general_dilated(
            xs, ws, window_strides=(1, 1), padding=[(2, 1), (2, 1)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return {"Output": [out]}
    # NOTE(perf A/B, r4): lowering 1x1 convs as reshape->dot (so XLA could
    # fuse the BN stats reductions into the dot epilogue, which its conv
    # emitter cannot take) was tried and REVERTED: whole-model resnet50
    # measured 2,547 img/s (bf16 dot) / 1,395 (f32-accum dot) vs 2,626
    # with lax.conv — the reshape barriers break more producer/consumer
    # fusion than the epilogue recovers.  See PERF.md par.2 round-4 note.
    dn = ("NHWC", "OIHW", "NHWC") if layout == "NHWC" else ("NCHW", "OIHW", "NCHW")
    out = lax.conv_general_dilated(
        x, w,
        window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dil,
        feature_group_count=groups,
        dimension_numbers=dn,
    )
    return {"Output": [out]}


register("depthwise_conv2d")(
    lambda ctx, ins, attrs: _conv2d(
        ctx, ins, {**attrs, "groups": ins["Input"][0].shape[1]}
    )
)


@register("conv2d_transpose")
def _conv2d_transpose(ctx, ins, attrs):
    # conv2d_transpose is defined as the input-gradient of a forward conv2d
    # (reference conv_transpose_op semantics: out = (in-1)*s - 2p + d*(k-1)+1,
    # weight layout [C_in, C_out/g, kh, kw] ≡ OIHW of the y→x conv).
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dil = _pair(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1)
    n, _, h, wd = x.shape
    _, cout_pg, kh, kw = w.shape
    cout = cout_pg * groups
    hout = (h - 1) * strides[0] - 2 * pads[0] + dil[0] * (kh - 1) + 1
    wout = (wd - 1) * strides[1] - 2 * pads[1] + dil[1] * (kw - 1) + 1

    def fwd(y):
        return lax.conv_general_dilated(
            y, w,
            window_strides=strides,
            padding=[(pads[0], pads[0]), (pads[1], pads[1])],
            rhs_dilation=dil,
            feature_group_count=groups,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
        )

    _, vjp_fn = jax.vjp(fwd, jnp.zeros((n, cout, hout, wout), x.dtype))
    (out,) = vjp_fn(x)
    return {"Output": [out]}


@register("pool2d")
def _pool2d(ctx, ins, attrs):
    x = ins["X"][0]
    ptype = attrs.get("pooling_type", "max")
    layout = attrs.get("data_layout", "NCHW")
    sp = (1, 2) if layout == "NHWC" else (2, 3)
    if attrs.get("global_pooling", False):
        ks = tuple(x.shape[d] for d in sp)
        strides, pads = ks, (0, 0)
    else:
        ks = _pair(attrs["ksize"])
        strides = _pair(attrs.get("strides", [1, 1]))
        pads = _pair(attrs.get("paddings", [0, 0]))
    window = [1, 1, 1, 1]
    strides_full = [1, 1, 1, 1]
    padding = [(0, 0)] * 4
    for i, d in enumerate(sp):
        window[d] = ks[i]
        strides_full[d] = strides[i]
        padding[d] = (pads[i], pads[i])
    window, strides_full = tuple(window), tuple(strides_full)
    padding = tuple(padding)
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        out = lax.reduce_window(x, init, lax.max, window, strides_full, padding)
    else:
        summed = lax.reduce_window(x.astype(jnp.float32), 0.0, lax.add, window, strides_full, padding)
        if attrs.get("exclusive", True) and (pads[0] or pads[1]):
            ones = jnp.ones(tuple(x.shape[d] for d in sp), jnp.float32)
            ones = ones[None, :, :, None] if layout == "NHWC" else ones[None, None]
            counts = lax.reduce_window(ones, 0.0, lax.add, window, strides_full, padding)
            out = summed / counts
        else:
            out = summed / float(np.prod(ks))
        out = out.astype(x.dtype)
    return {"Out": [out]}


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

@register("batch_norm", no_grad_slots=("Mean", "Variance"))
def _batch_norm(ctx, ins, attrs):
    """batch_norm_op.cc semantics: training mode uses batch statistics and
    exponentially updates the running Mean/Variance (persistable state — the
    executor writes MeanOut/VarianceOut back to the same scope vars);
    is_test uses the running stats."""
    x = ins["X"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean, var = ins["Mean"][0], ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False) or not ctx.training
    layout = attrs.get("data_layout", "NCHW")
    axes = tuple(i for i in range(x.ndim) if i != (1 if layout == "NCHW" else x.ndim - 1))
    bshape = [1] * x.ndim
    bshape[1 if layout == "NCHW" else x.ndim - 1] = -1

    sdt = _stat_dtype(x)
    if is_test:
        use_mean, use_var = mean, var
        mean_out, var_out = mean, var
        saved_mean = mean
        saved_inv_std = lax.rsqrt(var + eps)
    else:
        # One-pass statistics (E[x], E[x^2]) so XLA reads the activation a
        # single time for both moments — on TPU the two-pass mean/var form
        # costs an extra full HBM sweep of the conv output, which dominates
        # BN time for bandwidth-bound image models.
        xf = x.astype(sdt)
        use_mean = jnp.mean(xf, axis=axes)
        use_var = jnp.maximum(
            jnp.mean(xf * xf, axis=axes) - use_mean * use_mean, 0.0)
        mean_out = mean * momentum + use_mean * (1.0 - momentum)
        var_out = var * momentum + use_var * (1.0 - momentum)
        saved_mean = use_mean
        saved_inv_std = lax.rsqrt(use_var + eps)

    # Folded affine: y = x*(inv*scale) + (bias - mean*inv*scale).  The
    # per-channel factors are computed in fp32 then cast to x.dtype, so the
    # per-element work stays in the activation dtype (bf16 on the MXU path)
    # instead of materializing an fp32 copy of the activation.
    inv = lax.rsqrt(use_var.astype(sdt) + eps)
    inv_s = inv * scale.astype(sdt)
    shift = bias.astype(sdt) - use_mean.astype(sdt) * inv_s
    y = x * inv_s.reshape(bshape).astype(x.dtype) \
        + shift.reshape(bshape).astype(x.dtype)
    return {
        "Y": [y],
        "MeanOut": [mean_out],
        "VarianceOut": [var_out],
        "SavedMean": [saved_mean],
        "SavedVariance": [saved_inv_std],
    }


@register("layer_norm")
def _layer_norm(ctx, ins, attrs):
    """layer_norm_op.cc: normalize over dims [begin_norm_axis:], affine with
    flattened Scale/Bias.  Stats in fp32 for bf16 inputs (TPU numeric
    policy)."""
    x = ins["X"][0]
    eps = attrs.get("epsilon", 1e-5)
    bna = attrs.get("begin_norm_axis", 1)
    axes = tuple(range(bna, x.ndim))
    xf = x.astype(_stat_dtype(x))
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    bshape = [1] * bna + list(x.shape[bna:])
    if "Scale" in ins and ins["Scale"]:
        y = y * ins["Scale"][0].reshape(bshape)
    if "Bias" in ins and ins["Bias"]:
        y = y + ins["Bias"][0].reshape(bshape)
    return {
        "Y": [y.astype(x.dtype)],
        "Mean": [mean.reshape(x.shape[:bna])],
        "Variance": [var.reshape(x.shape[:bna])],
    }


@register("norm")
def _norm(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", 1)
    eps = attrs.get("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    return {"Out": [x / norm], "Norm": [norm]}


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def _squeeze_label(label):
    if label.ndim >= 2 and label.shape[-1] == 1:
        return label.squeeze(-1)
    return label


@register("cross_entropy", no_grad_slots=("Label",))
def _cross_entropy(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    eps = 1e-8
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        li = _squeeze_label(label)
        p = jnp.take_along_axis(x, li[..., None].astype(jnp.int32), axis=-1)
        loss = -jnp.log(p + eps)
    return {"Y": [loss]}


def _xent_stats(logits):
    """``logits`` in the statistics' dtype and their log-sum-exp over the
    classes: the one expression the forward and the grad rule share, so XLA's
    CSE folds the grad rule's copy into the forward's."""
    xf = logits.astype(_stat_dtype(logits))
    return xf, jax.nn.logsumexp(xf, axis=-1, keepdims=True)


def _xent_hard_label(label, attrs, dtype):
    """Class index ``[..., 1]`` and the ``ignore_index`` mask (None: unset)."""
    li = _squeeze_label(label).astype(jnp.int32)[..., None]
    if attrs.get("ignore_index", -100) == -100:
        return li, None
    return li, (li != attrs["ignore_index"]).astype(dtype)


@register("softmax_with_cross_entropy", no_grad_slots=("Label",))
def _softmax_xent(ctx, ins, attrs):
    """Nothing of the classes' width is written beside ``Softmax`` (which XLA
    drops where nothing reads it): the label's logit is taken from the logits
    as they arrive, not from a ``log_softmax`` tensor in the statistics'
    dtype."""
    logits, label = ins["Logits"][0], ins["Label"][0]
    xf, lse = _xent_stats(logits)
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * (xf - lse), axis=-1, keepdims=True)
    else:
        li, mask = _xent_hard_label(label, attrs, lse.dtype)
        loss = lse - jnp.take_along_axis(logits, li, axis=-1).astype(lse.dtype)
        if mask is not None:
            loss = loss * mask
    return {"Softmax": [jnp.exp(xf - lse).astype(logits.dtype)],
            "Loss": [loss.astype(logits.dtype)]}


@register_grad("softmax_with_cross_entropy", retraces=True)
def _softmax_xent_grad(ctx, ins, attrs):
    """Closed form, from ``Logits``, ``Label`` and ``Loss@GRAD`` alone:
    ``(softmax - onehot(label)) * g``, the exponential recomputed inside
    whatever consumes the gradient.  ``ins["Softmax"]`` is not read: that
    would pin a classes-wide write in the forward that nothing else needs.
    A program that differentiates through ``Softmax`` takes ``vjp_grad``."""
    counters = _obs_stats.scope("loss")
    if ins.get("Softmax@GRAD"):
        counters.counter("xent_vjp_fallback_grads").inc()
        return vjp_grad(get_opdef("softmax_with_cross_entropy"), ctx, ins,
                        attrs)
    counters.counter("xent_closed_form_grads").inc()
    logits, label = ins["Logits"][0], ins["Label"][0]
    fwd_scope, bwd_scope = ctx.grad_scopes
    with _scope(fwd_scope):  # named as the forward's, whichever CSE keeps
        xf, lse = _xent_stats(logits)
    with _scope(bwd_scope):
        g = ins["Loss@GRAD"][0].astype(lse.dtype)
        softmax = jnp.exp(xf - lse)
        if attrs.get("soft_label", False):
            d = softmax * jnp.sum(label, axis=-1, keepdims=True) - label
        else:
            li, mask = _xent_hard_label(label, attrs, lse.dtype)
            classes = lax.broadcasted_iota(jnp.int32, logits.shape,
                                           logits.ndim - 1)
            d = softmax - (classes == li).astype(lse.dtype)
            if mask is not None:
                g = g * mask
        return {"Logits@GRAD": [(d * g).astype(logits.dtype)]}


def _proj_xent_impl(backend, n_rows, d, seq, x_dtype, w_dtype, mesh=None,
                    spans_devices=False):
    """What ``fc_softmax_with_cross_entropy`` lowers to, from what the op can
    observe: ``kernel`` (``kernels/xent.py proj_xent_fwd``: the projection
    carries the log-sum-exp, no pass over the logits follows it) on a TPU
    where every shard's rows fill whole row blocks of whole sequences, the
    sequences (the last of the leading dimensions, ``seq``) and the
    contraction whole lane tiles, the weight is bf16 and the mesh is one the
    call can be wrapped over (``attention_ops._dp_only``'s rule, and for its
    reason); ``xla`` — ``jnp.matmul`` and ``logsumexp`` — everywhere else.
    The measurements are in the kernel's module."""
    if backend != "tpu":
        return "xla"
    shards = mesh.shape["dp"] if mesh is not None and "dp" in mesh.shape else 1
    if (_dp_only(mesh, n_rows, spans_devices)
            and _xent_kernel.fits(n_rows // shards, d, seq)
            and jnp.dtype(w_dtype) == jnp.bfloat16
            and jnp.dtype(x_dtype) in (jnp.bfloat16, jnp.float32)):
        return "kernel"
    return "xla"


def _proj_xent_kernel(mesh, x2, w, seq):
    """The kernel, per shard under a mesh: the rows are what ``dp`` shards
    and ``w`` is whole on every chip (GSPMD cannot partition a Mosaic call,
    ``attention_ops._short``)."""
    # the rows rounded to bf16 HERE, where the kernel would first: XLA then
    # writes a bf16 copy for the call and goes on recomputing the float32
    # rows inside the weight-gradient product from what it keeps in VMEM; a
    # float32 operand it had to write to HBM, and read there from that product
    call = functools.partial(_xent_kernel.proj_xent_fwd, seq=seq,
                             logits_dtype=jnp.result_type(x2, w))
    x2 = x2.astype(jnp.bfloat16)
    if mesh is None:
        return call(x2, w)
    by_row = P("dp")
    return jax.shard_map(call, mesh=mesh, check_vma=False,
                         in_specs=(by_row, P()),
                         out_specs=(by_row, by_row))(x2, w)


@register("fc_softmax_with_cross_entropy", no_grad_slots=("Label",))
def _fc_softmax_xent(ctx, ins, attrs):
    """``softmax_with_cross_entropy(mul(X, W), Label)`` over hard labels as
    one op, so that the projection can hand the loss its log-sum-exp:
    ``Loss``, and ``LSE`` and ``Logits`` for the grad rule, which reads both
    and recomputes neither."""
    x, w, label = ins["X"][0], ins["W"][0], ins["Label"][0]
    xnc = attrs.get("x_num_col_dims", 1)
    x2 = flatten_to_2d(x, xnc)
    lead, seq = x.shape[:xnc], x.shape[xnc - 1]
    impl = _proj_xent_impl(jax.default_backend(), x2.shape[0], x2.shape[1],
                           seq, x.dtype, w.dtype, ctx.mesh, ctx.spans_devices)
    # which lowering the op chose, counted when it is lowered
    _obs_stats.scope("loss").counter(
        "proj_xent_kernel" if impl == "kernel" else "proj_xent_fallbacks").inc()
    if impl == "kernel":
        logits, lse = _proj_xent_kernel(ctx.mesh, x2, w, seq)
        logits, lse = logits.reshape(lead + w.shape[1:]), lse.reshape(
            lead + (1,))
    else:
        # the two ops' own expressions, the statistics in the leading
        # dimensions' shape as the grad rule's half is: under a log-sum-exp
        # taken over [N, V] and reshaped, XLA:TPU wrote ``softmax - onehot``
        # out in bf16, once a gradient product
        logits = jnp.matmul(x2, w).reshape(lead + w.shape[1:])
        _, lse = _xent_stats(logits)
    li, mask = _xent_hard_label(label, attrs, lse.dtype)
    loss = lse - jnp.take_along_axis(logits, li, axis=-1).astype(lse.dtype)
    if mask is not None:
        loss = loss * mask
    return {"Loss": [loss.astype(logits.dtype)], "LSE": [lse],
            "Logits": [logits]}


@register_grad("fc_softmax_with_cross_entropy")
def _fc_softmax_xent_grad(ctx, ins, attrs):
    """``softmax_with_cross_entropy``'s closed form, ``(softmax - onehot) *
    g``, with the softmax from the forward's ``LSE`` — a log-sum-exp retraced
    here would have no forward copy to fold into and bring the pass over the
    logits back — then ``mul``'s two gradient products, in plain
    ``jax.numpy`` so that XLA keeps ``softmax - onehot`` inside them as
    their producer."""
    x, w, label = ins["X"][0], ins["W"][0], ins["Label"][0]
    logits, lse = ins["Logits"][0], ins["LSE"][0]
    xnc = attrs.get("x_num_col_dims", 1)
    g = ins["Loss@GRAD"][0].astype(lse.dtype)
    li, mask = _xent_hard_label(label, attrs, lse.dtype)
    if mask is not None:
        g = g * mask
    classes = lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    d = jnp.exp(logits.astype(lse.dtype) - lse) - (classes == li).astype(
        lse.dtype)
    d = flatten_to_2d((d * g).astype(logits.dtype), xnc)
    dx = jnp.matmul(d, w.T).astype(x.dtype)
    dw = jnp.matmul(flatten_to_2d(x, xnc).T, d).astype(w.dtype)
    return {"X@GRAD": [dx.reshape(x.shape)], "W@GRAD": [dw]}


@register("square_error_cost")
def _square_error_cost(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    return {"Out": [jnp.square(x - y)]}


@register("huber_loss")
def _huber_loss(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    d = attrs.get("delta", 1.0)
    r = y - x
    a = jnp.abs(r)
    loss = jnp.where(a <= d, 0.5 * r * r, d * (a - 0.5 * d))
    return {"Out": [loss], "Residual": [r]}


@register("sigmoid_cross_entropy_with_logits", no_grad_slots=("Label",))
def _sce_logits(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    return {"Out": [loss]}


@register("smooth_l1_loss")
def _smooth_l1(ctx, ins, attrs):
    """smooth_l1_loss_op.h: diff = (x-y)*inside_weight; per-element error
    scaled by outside_weight; row-summed loss."""
    x, y = ins["X"][0], ins["Y"][0]
    sigma2 = attrs.get("sigma", 1.0) ** 2
    d = x - y
    if "InsideWeight" in ins and ins["InsideWeight"]:
        d = d * ins["InsideWeight"][0]
    a = jnp.abs(d)
    loss = jnp.where(a < 1.0 / sigma2, 0.5 * d * d * sigma2, a - 0.5 / sigma2)
    if "OutsideWeight" in ins and ins["OutsideWeight"]:
        loss = loss * ins["OutsideWeight"][0]
    return {"Out": [jnp.sum(loss, axis=tuple(range(1, x.ndim)), keepdims=False)[..., None]],
            "Diff": [d]}


# ---------------------------------------------------------------------------
# metrics (accuracy_op.cc; used by fluid.layers.accuracy)
# ---------------------------------------------------------------------------

@register("accuracy", no_grad_slots=("Out", "Indices", "Label"))
def _accuracy(ctx, ins, attrs):
    idx = ins["Indices"][0]
    label = _squeeze_label(ins["Label"][0])
    correct = jnp.any(idx == label[..., None], axis=-1)
    num_correct = jnp.sum(correct.astype(jnp.int32))
    total = jnp.asarray(idx.shape[0], jnp.int32)
    acc = num_correct.astype(jnp.float32) / total.astype(jnp.float32)
    return {"Accuracy": [acc], "Correct": [num_correct], "Total": [total]}


# ---------------------------------------------------------------------------
# recurrent cells — scan over padded [B,T,*] + length mask.
# Gate order: i, f, c(candidate), o — documented contract for Weight layout.
# ---------------------------------------------------------------------------

def _length_mask(seq_len, B, T, dtype):
    if seq_len is None:
        return jnp.ones((B, T), dtype)
    t = jnp.arange(T)[None, :]
    return (t < seq_len[:, None]).astype(dtype)


def _rnn_pallas_eligible(ctx, B, T, H, dtype, attrs, supported_fn):
    """Shared Pallas-cell dispatch policy (lstm + gru): explicit attr
    wins; otherwise TPU backend + top-level block (control-flow
    sub-blocks differentiate via jax.vjp, which cannot see through a
    pallas_call) + MXU/VMEM-compatible shapes."""
    force = attrs.get("use_pallas_kernel", None)
    if force is not None:
        return bool(force)
    top_level = ctx.block is None or getattr(ctx.block, "idx", 0) == 0
    return (jax.default_backend() == "tpu" and top_level
            and supported_fn(B, T, H, dtype))


def _lstm_pallas_eligible(ctx, B, T, H, dtype, attrs):
    from ..kernels import rnn as _rnn
    return _rnn_pallas_eligible(ctx, B, T, H, dtype, attrs,
                                _rnn.lstm_supported)


@register("lstm", no_grad_slots=("SeqLen",))
def _lstm(ctx, ins, attrs):
    """Fused LSTM over a padded batch (lstm_op.cc + math/lstm_compute
    re-designed for XLA: lax.scan with [B,4H] gate matmuls per step — the
    recurrent matmul rides the MXU, elementwise gates fuse on the VPU).

    Inputs: Input [B,T,4H] (x·Wx + b precomputed by the layer), Weight
    [H,4H] recurrent weights, optional H0/C0 [B,H], optional SeqLen [B].
    Outputs: Hidden [B,T,H], Cell [B,T,H], LastH, LastC [B,H].
    """
    xproj = ins["Input"][0]
    w = ins["Weight"][0]
    B, T, H4 = xproj.shape
    H = H4 // 4
    h0 = ins["H0"][0] if ins.get("H0") else jnp.zeros((B, H), xproj.dtype)
    c0 = ins["C0"][0] if ins.get("C0") else jnp.zeros((B, H), xproj.dtype)
    seq_len = ins["SeqLen"][0] if ins.get("SeqLen") else None
    mask = _length_mask(seq_len, B, T, xproj.dtype)
    reverse = attrs.get("is_reverse", False)

    # Fused Pallas cell (jit_kernel_rnn.cc analogue): whole scan in one
    # kernel, recurrent weights VMEM-resident.  TPU + MXU-aligned shapes
    # + top-level block only (control-flow sub-blocks differentiate via
    # jax.vjp, which cannot see through a pallas_call — they keep the XLA
    # scan); attr use_pallas_kernel forces it (interpret) for kernel tests.
    use_pallas = _lstm_pallas_eligible(ctx, B, T, H, xproj.dtype, attrs)
    from ..kernels import rnn as _rnn
    if use_pallas:
        # is_reverse is the kernel's own index maps (no flipped copies):
        # the scan's final state sits at time 0 then, else at T-1
        hs_bt, cs_bt = _rnn.lstm_fused(
            xproj, w, h0.astype(xproj.dtype), c0.astype(xproj.dtype),
            mask.astype(jnp.float32), reverse=reverse)
        last = 0 if reverse else -1
        return {"Hidden": [hs_bt], "Cell": [cs_bt],
                "LastH": [hs_bt[:, last]], "LastC": [cs_bt[:, last]]}

    xs = jnp.swapaxes(xproj, 0, 1)  # [T,B,4H]
    ms = jnp.swapaxes(mask, 0, 1)[..., None]  # [T,B,1]
    if reverse:
        xs, ms = jnp.flip(xs, 0), jnp.flip(ms, 0)

    def step(carry, inp):
        h, c = carry
        x_t, m_t = inp
        gates = x_t + jnp.matmul(h, w)
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        g = jnp.tanh(g)
        c_new = f * c + i * g
        h_new = o * jnp.tanh(c_new)
        c_new = m_t * c_new + (1 - m_t) * c
        h_new = m_t * h_new + (1 - m_t) * h
        return (h_new, c_new), (h_new, c_new)

    (h_last, c_last), (hs, cs) = lax.scan(step, (h0, c0), (xs, ms))
    if reverse:
        hs, cs = jnp.flip(hs, 0), jnp.flip(cs, 0)
    return {
        "Hidden": [jnp.swapaxes(hs, 0, 1)],
        "Cell": [jnp.swapaxes(cs, 0, 1)],
        "LastH": [h_last],
        "LastC": [c_last],
    }


@register_grad("lstm")
def _lstm_grad(ctx, ins, attrs):
    """Explicit lstm backward: the Pallas path calls the fused backward
    kernel (gates recomputed in-kernel); other shapes fall back to
    jax.vjp of the XLA scan lowering.  An explicit grad op (not a
    custom_vjp) because that is the framework's own mechanism."""
    from ..core import registry as _registry
    from ..kernels import rnn as _rnn

    xproj = ins["Input"][0]
    B, T, H4 = xproj.shape
    H = H4 // 4
    if not _lstm_pallas_eligible(ctx, B, T, H, xproj.dtype, attrs):
        fwd_attrs = {**attrs, "use_pallas_kernel": False}
        return _registry.vjp_grad(_registry.get("lstm"), ctx, ins, fwd_attrs)

    w = ins["Weight"][0]
    h0 = ins["H0"][0] if ins.get("H0") else jnp.zeros((B, H), xproj.dtype)
    c0 = ins["C0"][0] if ins.get("C0") else jnp.zeros((B, H), xproj.dtype)
    seq_len = ins["SeqLen"][0] if ins.get("SeqLen") else None
    mask = _length_mask(seq_len, B, T, jnp.float32)
    reverse = attrs.get("is_reverse", False)
    hs, cs = ins["Hidden"][0], ins["Cell"][0]

    def grad_or_zeros(slot, shape):
        g = ins.get(slot)
        if g and g[0] is not None:
            return g[0].astype(jnp.float32)
        return jnp.zeros(shape, jnp.float32)

    dhs = grad_or_zeros("Hidden@GRAD", (B, T, H))
    dcs = grad_or_zeros("Cell@GRAD", (B, T, H))
    # LastH/LastC are the scan's final states — time 0 of a reversed
    # layer, else T-1 — so their cotangents fold there
    last = 0 if reverse else -1
    g = ins.get("LastH@GRAD")
    if g and g[0] is not None:
        dhs = dhs.at[:, last].add(g[0].astype(jnp.float32))
    g = ins.get("LastC@GRAD")
    if g and g[0] is not None:
        dcs = dcs.at[:, last].add(g[0].astype(jnp.float32))

    dxs, dw, dh0, dc0 = _rnn.lstm_fused_grad(
        xproj, w, h0.astype(xproj.dtype), c0.astype(xproj.dtype), mask,
        hs, cs, dhs, dcs, reverse=reverse)
    outs = {"Input@GRAD": [dxs], "Weight@GRAD": [dw]}
    if ins.get("H0"):
        outs["H0@GRAD"] = [dh0]
    if ins.get("C0"):
        outs["C0@GRAD"] = [dc0]
    return outs


@register("attention_lstm", no_grad_slots=("SeqLen",))
def _attention_lstm(ctx, ins, attrs):
    """attention_lstm_op.cc: per decode step, a 1-unit additive attention
    over the WHOLE input sequence conditioned on the previous cell state,
    sum-pooled into the LSTM's x input.  Padded redesign: X [B,T,M] with a
    length mask; per step the attention softmax masks padding positions;
    finished rows pass h/c through (same contract as the lstm op).

    Weights: AttentionWeight [(M+D),1] (+AttentionBias [1,1], optional
    AttentionScalar/AttentionScalarBias [1,1]), LSTMWeight [(D+M),4D]
    with the reference's [forget|input|output|candidate] gate order,
    LSTMBias [1,4D]."""
    x = ins["X"][0]                                   # [B,T,M]
    B, T, M = x.shape
    lstm_w = ins["LSTMWeight"][0]                     # [(D+M),4D]
    D = lstm_w.shape[1] // 4
    lstm_b = ins["LSTMBias"][0].reshape(-1)           # [4D]
    atten_w = ins["AttentionWeight"][0]               # [(M+D),1]
    atten_b = (ins["AttentionBias"][0].reshape(())
               if ins.get("AttentionBias") else None)
    atten_s = (ins["AttentionScalar"][0].reshape(())
               if ins.get("AttentionScalar") else None)
    atten_sb = (ins["AttentionScalarBias"][0].reshape(())
                if ins.get("AttentionScalarBias") else None)
    h0 = ins["H0"][0] if ins.get("H0") else jnp.zeros((B, D), x.dtype)
    c0 = ins["C0"][0]                                 # required (attention)
    seq_len = ins["SeqLen"][0] if ins.get("SeqLen") else None
    # the whole scan runs in f32 (attention logits + cell state) and the
    # outputs cast back — bf16 carries would both break lax.scan's carry
    # dtype invariant under mixed masking and underflow the -1e30 fill
    cdt = jnp.float32
    mask = _length_mask(seq_len, B, T, cdt)           # [B,T]

    w_x, w_c = (atten_w[:M, 0].astype(cdt),
                atten_w[M:, 0].astype(cdt))           # [M], [D]
    w_h, w_in = lstm_w[:D].astype(cdt), lstm_w[D:].astype(cdt)
    xf = x.astype(cdt)
    atted_x = jnp.einsum("btm,m->bt", xf, w_x)        # [B,T]
    if atten_b is not None:
        atted_x = atted_x + atten_b

    def step(carry, t):
        h, c = carry                                  # [B,D] f32
        e = atted_x + (c * w_c[None, :]).sum(-1, keepdims=True)
        e = jax.nn.relu(e)
        if atten_s is not None:
            e = e * atten_s
            e = jax.nn.relu(e + (atten_sb if atten_sb is not None else 0.0))
        e = jnp.where(mask > 0, e, -1e30)
        alpha = jax.nn.softmax(e, axis=-1)            # [B,T]
        lstm_x = jnp.einsum("bt,btm->bm", alpha, xf)  # [B,M]
        gates = lstm_x @ w_in + h @ w_h + lstm_b.astype(cdt)
        f = jax.nn.sigmoid(gates[:, :D])
        i = jax.nn.sigmoid(gates[:, D:2 * D])
        o = jax.nn.sigmoid(gates[:, 2 * D:3 * D])
        cand = jnp.tanh(gates[:, 3 * D:])
        c_new = f * c + i * cand
        h_new = jnp.tanh(c_new) * o
        m_t = mask[:, t][:, None]
        c_new = m_t * c_new + (1 - m_t) * c
        h_new = m_t * h_new + (1 - m_t) * h
        return (h_new, c_new), (h_new, c_new)

    (h_last, c_last), (hs, cs) = lax.scan(
        step, (h0.astype(cdt), c0.astype(cdt)), jnp.arange(T))
    return {"Hidden": [jnp.swapaxes(hs, 0, 1).astype(x.dtype)],
            "Cell": [jnp.swapaxes(cs, 0, 1).astype(x.dtype)],
            "AttentionedX": [atted_x[..., None].astype(x.dtype)],
            # AttentionFCOut/LSTMX/LSTMOUT are per-step SCRATCH in the
            # reference kernel (overwritten every iteration, exposed only
            # because C++ kernels need declared workspaces); emitted as
            # shape-correct zero placeholders here
            "AttentionFCOut": [jnp.zeros((B, T, 1), x.dtype)],
            "LSTMX": [jnp.zeros((B, M), x.dtype)],
            "LSTMOUT": [jnp.zeros((B, 4 * D), x.dtype)]}


def _gru_pallas_eligible(ctx, B, T, H, dtype, attrs):
    from ..kernels import rnn as _rnn
    return _rnn_pallas_eligible(ctx, B, T, H, dtype, attrs,
                                _rnn.gru_supported)


@register("gru", no_grad_slots=("SeqLen",))
def _gru(ctx, ins, attrs):
    """Fused GRU over a padded batch (gru_op.cc + math/gru_compute).
    Input [B,T,3H] (x-projection), Weight [H,3H] as [update|reset|candidate].
    """
    xproj = ins["Input"][0]
    w = ins["Weight"][0]
    B, T, H3 = xproj.shape
    H = H3 // 3
    h0 = ins["H0"][0] if ins.get("H0") else jnp.zeros((B, H), xproj.dtype)
    seq_len = ins["SeqLen"][0] if ins.get("SeqLen") else None
    mask = _length_mask(seq_len, B, T, xproj.dtype)
    reverse = attrs.get("is_reverse", False)

    # Fused Pallas cell (same dispatch contract as lstm above)
    use_pallas = _gru_pallas_eligible(ctx, B, T, H, xproj.dtype, attrs)
    if use_pallas:
        from ..kernels import rnn as _rnn
        hs_bt = _rnn.gru_fused(xproj, w, h0.astype(xproj.dtype),
                               mask.astype(jnp.float32), reverse=reverse)
        return {"Hidden": [hs_bt],
                "LastH": [hs_bt[:, 0 if reverse else -1]]}

    w_uz = w[:, : 2 * H]
    w_c = w[:, 2 * H :]
    xs = jnp.swapaxes(xproj, 0, 1)
    ms = jnp.swapaxes(mask, 0, 1)[..., None]
    if reverse:
        xs, ms = jnp.flip(xs, 0), jnp.flip(ms, 0)

    def step(h, inp):
        x_t, m_t = inp
        x_uz, x_c = x_t[:, : 2 * H], x_t[:, 2 * H :]
        uz = jax.nn.sigmoid(x_uz + jnp.matmul(h, w_uz))
        u, r = uz[:, :H], uz[:, H:]
        c = jnp.tanh(x_c + jnp.matmul(r * h, w_c))
        h_new = u * h + (1 - u) * c
        h_new = m_t * h_new + (1 - m_t) * h
        return h_new, h_new

    h_last, hs = lax.scan(step, h0, (xs, ms))
    if reverse:
        hs = jnp.flip(hs, 0)
    return {"Hidden": [jnp.swapaxes(hs, 0, 1)], "LastH": [h_last]}


@register_grad("gru")
def _gru_grad(ctx, ins, attrs):
    """Explicit gru backward: Pallas path calls the fused backward kernel
    (gates recomputed in-kernel); other shapes fall back to jax.vjp of
    the XLA scan lowering (same rationale as _lstm_grad)."""
    from ..core import registry as _registry
    from ..kernels import rnn as _rnn

    xproj = ins["Input"][0]
    B, T, H3 = xproj.shape
    H = H3 // 3
    if not _gru_pallas_eligible(ctx, B, T, H, xproj.dtype, attrs):
        fwd_attrs = {**attrs, "use_pallas_kernel": False}
        return _registry.vjp_grad(_registry.get("gru"), ctx, ins, fwd_attrs)

    w = ins["Weight"][0]
    h0 = ins["H0"][0] if ins.get("H0") else jnp.zeros((B, H), xproj.dtype)
    seq_len = ins["SeqLen"][0] if ins.get("SeqLen") else None
    mask = _length_mask(seq_len, B, T, jnp.float32)
    reverse = attrs.get("is_reverse", False)
    hs = ins["Hidden"][0]

    g = ins.get("Hidden@GRAD")
    dhs = (g[0].astype(jnp.float32) if g and g[0] is not None
           else jnp.zeros((B, T, H), jnp.float32))
    g = ins.get("LastH@GRAD")
    if g and g[0] is not None:
        dhs = dhs.at[:, 0 if reverse else -1].add(g[0].astype(jnp.float32))

    dxs, dw, dh0 = _rnn.gru_fused_grad(
        xproj, w, h0.astype(xproj.dtype), mask, hs, dhs, reverse=reverse)
    outs = {"Input@GRAD": [dxs], "Weight@GRAD": [dw]}
    if ins.get("H0"):
        outs["H0@GRAD"] = [dh0]
    return outs


@register("fused_fc")
def _fused_fc(ctx, ins, attrs):
    """Fused mul + bias + activation, emitted by the inference fc fuser
    (framework/ir/fc_fuse_pass.cc analogue; see inference/passes.py).
    Delegates to the registered mul/elementwise_add/act lowerings so the
    fused op is semantics-identical to the chain it replaced."""
    from ..core import registry as _registry

    out = _registry.get("mul").lower(
        ctx, {"X": ins["X"], "Y": ins["W"]},
        {"x_num_col_dims": attrs.get("x_num_col_dims", 1),
         "y_num_col_dims": attrs.get("y_num_col_dims", 1)})["Out"][0]
    if ins.get("Bias"):
        out = _registry.get("elementwise_add").lower(
            ctx, {"X": [out], "Y": ins["Bias"]},
            {"axis": attrs.get("axis", -1)})["Out"][0]
    act = attrs.get("act") or ""
    if act:
        out = _registry.get(act).lower(
            ctx, {"X": [out]}, dict(attrs.get("act_attrs") or {}))["Out"][0]
    return {"Out": [out]}


# ---------------------------------------------------------------------------
# 3-D conv / pool family (conv3d_op, pool3d, conv3d_transpose — NCDHW)
# ---------------------------------------------------------------------------

def _triple(v):
    return tuple(v) if isinstance(v, (list, tuple)) else (v, v, v)


@register("conv3d")
def _conv3d(ctx, ins, attrs):
    """conv_op.cc 3-D branch: NCDHW activations, OIDHW filters."""
    x, w = ins["Input"][0], ins["Filter"][0]
    st = _triple(attrs.get("strides", [1, 1, 1]))
    pd = _triple(attrs.get("paddings", [0, 0, 0]))
    dl = _triple(attrs.get("dilations", [1, 1, 1]))
    out = lax.conv_general_dilated(
        x, w, window_strides=st,
        padding=[(pd[0], pd[0]), (pd[1], pd[1]), (pd[2], pd[2])],
        rhs_dilation=dl,
        feature_group_count=attrs.get("groups", 1) or 1,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
    )
    return {"Output": [out]}


@register("conv3d_transpose")
def _conv3d_transpose(ctx, ins, attrs):
    """conv_transpose_op 3-D branch: input-gradient of a forward conv3d."""
    x, w = ins["Input"][0], ins["Filter"][0]
    st = _triple(attrs.get("strides", [1, 1, 1]))
    pd = _triple(attrs.get("paddings", [0, 0, 0]))
    dl = _triple(attrs.get("dilations", [1, 1, 1]))
    groups = attrs.get("groups", 1) or 1
    n = x.shape[0]
    _, cout_pg, kd, kh, kw = w.shape
    cout = cout_pg * groups
    dims = [(x.shape[2 + i] - 1) * st[i] - 2 * pd[i]
            + dl[i] * (w.shape[2 + i] - 1) + 1 for i in range(3)]

    def fwd(y):
        return lax.conv_general_dilated(
            y, w, window_strides=st,
            padding=[(pd[0], pd[0]), (pd[1], pd[1]), (pd[2], pd[2])],
            rhs_dilation=dl,
            feature_group_count=groups,
            dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        )

    _, vjp_fn = jax.vjp(fwd, jnp.zeros((n, cout) + tuple(dims), x.dtype))
    (out,) = vjp_fn(x)
    return {"Output": [out]}


@register("pool3d")
def _pool3d(ctx, ins, attrs):
    """pool_op.cc 3-D branch (NCDHW max/avg)."""
    x = ins["X"][0]
    ptype = attrs.get("pooling_type", "max")
    if attrs.get("global_pooling", False):
        ks = x.shape[2:5]
        st, pd = ks, (0, 0, 0)
    else:
        ks = _triple(attrs["ksize"])
        st = _triple(attrs.get("strides", [1, 1, 1]))
        pd = _triple(attrs.get("paddings", [0, 0, 0]))
    window = (1, 1) + tuple(ks)
    strides = (1, 1) + tuple(st)
    padding = ((0, 0), (0, 0)) + tuple((p, p) for p in pd)
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) \
            else jnp.iinfo(x.dtype).min
        out = lax.reduce_window(x, init, lax.max, window, strides, padding)
    else:
        summed = lax.reduce_window(x.astype(jnp.float32), 0.0, lax.add,
                                   window, strides, padding)
        if attrs.get("exclusive", True) and any(pd):
            ones = jnp.ones((1, 1) + x.shape[2:5], jnp.float32)
            counts = lax.reduce_window(ones, 0.0, lax.add, window, strides,
                                       padding)
            out = (summed / counts).astype(x.dtype)
        else:
            out = (summed / float(np.prod(ks))).astype(x.dtype)
    return {"Out": [out]}


@register("lstmp", no_grad_slots=("SeqLen",))
def _lstmp(ctx, ins, attrs):
    """lstmp_op.cc: LSTM with recurrent projection (Sak et al. 2014).
    Input [B,T,4H] (x-projection), Weight [P,4H] recurrent weights over the
    projected state, ProjWeight [H,P].  Outputs Projection [B,T,P] and
    Cell [B,T,H]."""
    xproj = ins["Input"][0]
    w = ins["Weight"][0]
    wproj = ins["ProjWeight"][0]
    B, T, H4 = xproj.shape
    H = H4 // 4
    P = wproj.shape[1]
    c0 = ins["C0"][0] if ins.get("C0") else jnp.zeros((B, H), xproj.dtype)
    r0 = ins["H0"][0] @ wproj if ins.get("H0") \
        else jnp.zeros((B, P), xproj.dtype)
    seq_len = ins["SeqLen"][0] if ins.get("SeqLen") else None
    mask = _length_mask(seq_len, B, T, xproj.dtype)
    reverse = attrs.get("is_reverse", False)
    proj_act = attrs.get("proj_activation", "identity")

    xs = jnp.swapaxes(xproj, 0, 1)
    ms = jnp.swapaxes(mask, 0, 1)[..., None]
    if reverse:
        xs, ms = jnp.flip(xs, 0), jnp.flip(ms, 0)

    def step(carry, inp):
        r, c = carry
        x_t, m_t = inp
        gates = x_t + jnp.matmul(r, w)
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
        g = jnp.tanh(g)
        c_new = f * c + i * g
        h_new = o * jnp.tanh(c_new)
        r_new = jnp.matmul(h_new, wproj)
        if proj_act == "tanh":
            r_new = jnp.tanh(r_new)
        elif proj_act == "relu":
            r_new = jax.nn.relu(r_new)
        c_new = m_t * c_new + (1 - m_t) * c
        r_new = m_t * r_new + (1 - m_t) * r
        return (r_new, c_new), (r_new, c_new)

    (r_last, c_last), (rs, cs) = lax.scan(step, (r0, c0), (xs, ms))
    if reverse:
        rs, cs = jnp.flip(rs, 0), jnp.flip(cs, 0)
    return {
        "Projection": [jnp.swapaxes(rs, 0, 1)],
        "Cell": [jnp.swapaxes(cs, 0, 1)],
        "LastH": [r_last],
        "LastC": [c_last],
    }


def _fused_lstm_tail(ctx, op_name, xproj, ins, attrs):
    """Shared tail of the fused-LSTM family: bias add on the x-projection,
    carry slots forwarded, the lstm scan, {Hidden, Cell, XX} packaging."""
    if attrs.get("use_peepholes", False):
        raise NotImplementedError(
            f"{op_name}: use_peepholes=True (the [1, 7D] bias layout) is "
            "not ported; the in-scope models run peephole-free")
    if ins.get("Bias"):
        xproj = xproj + ins["Bias"][0].reshape(1, 1, -1)
    sub = {"Input": [xproj], "Weight": [ins["WeightH"][0]]}
    for slot in ("H0", "C0", "SeqLen"):
        if ins.get(slot):
            sub[slot] = ins[slot]
    # training: XLA scan only — the fused family's backward is vjp_grad
    # through this lowering and jax.vjp cannot see through a pallas_call.
    # Inference (ctx.training False, e.g. the Predictor after the
    # fuse_fc_lstm pass) keeps the Pallas cell dispatch.
    if ctx.training:
        attrs = {**attrs, "use_pallas_kernel": False}
    out = _lstm(ctx, sub, attrs)
    return {"Hidden": out["Hidden"], "Cell": out["Cell"], "XX": [xproj]}


@register("fusion_lstm", no_grad_slots=("SeqLen",))
def _fusion_lstm(ctx, ins, attrs):
    """fusion_lstm_op.cc: fc(x) + LSTM in one op (the CPU jit_kernel
    fusion; on TPU one XLA region anyway).  X [B,T,M], WeightX [M,4D],
    WeightH [D,4D], Bias [1,4D]; reuses the lstm scan lowering."""
    xproj = jnp.einsum("btm,mf->btf", ins["X"][0], ins["WeightX"][0])
    return _fused_lstm_tail(ctx, "fusion_lstm", xproj, ins, attrs)


@register("fusion_gru", no_grad_slots=("SeqLen",))
def _fusion_gru(ctx, ins, attrs):
    """fusion_gru_op.cc: fc(x) + GRU in one op; reuses the gru scan."""
    x = ins["X"][0]
    wx = ins["WeightX"][0]
    bias = ins["Bias"][0] if ins.get("Bias") else None
    xproj = jnp.einsum("btm,mf->btf", x, wx)
    if bias is not None:
        xproj = xproj + bias.reshape(1, 1, -1)
    sub = {"Input": [xproj], "Weight": [ins["WeightH"][0]]}
    for slot in ("H0", "SeqLen"):
        if ins.get(slot):
            sub[slot] = ins[slot]
    # training: XLA scan only (vjp cannot see through the Pallas cell);
    # inference keeps the Pallas dispatch (see _fused_lstm_tail)
    if ctx.training:
        attrs = {**attrs, "use_pallas_kernel": False}
    out = _gru(ctx, sub, attrs)
    return {"Hidden": out["Hidden"], "XX": [xproj]}


@register("fused_elemwise_activation")
def _fused_elemwise_activation(ctx, ins, attrs):
    """fused_elemwise_activation_op.cc: functor_list pairs like
    ["elementwise_add", "relu"] / ["relu", "elementwise_add"] — binary op
    and unary activation composed in one op (XLA fuses either way; the
    op exists for graph parity with the reference's fusion passes)."""
    x, y = ins["X"][0], ins["Y"][0]
    functors = [f.lower() for f in attrs["functor_list"]]
    axis = attrs.get("axis", -1)

    def binary(name, a, b):
        if b.ndim < a.ndim and axis != -1:
            b = b.reshape(b.shape + (1,) * (a.ndim - b.ndim - axis))
        return {"elementwise_add": a + b, "elementwise_sub": a - b,
                "elementwise_mul": a * b}[name]

    def unary(name, a):
        return {"relu": jax.nn.relu, "sigmoid": jax.nn.sigmoid,
                "tanh": jnp.tanh, "scale": lambda v: v * attrs.get(
                    "scale", 1.0)}[name](a)

    if functors[0].startswith("elementwise"):
        inter = binary(functors[0], x, y)
        out = unary(functors[1], inter)
    else:
        inter = unary(functors[0], y)
        out = binary(functors[1], x, inter)
    return {"Out": [out], "IntermediateOut": [inter]}


@register("fused_embedding_fc_lstm", no_grad_slots=("Ids", "SeqLen"))
def _fused_embedding_fc_lstm(ctx, ins, attrs):
    """fused_embedding_fc_lstm_op.cc: the embedding table IS the
    pre-multiplied x-projection (Embeddings [V, 4D] = emb @ Wx fused
    offline), so a lookup replaces the fc; then the LSTM scan."""
    ids = ins["Ids"][0]
    table = ins["Embeddings"][0]
    if ids.ndim == 3 and ids.shape[-1] == 1:
        ids = ids.reshape(ids.shape[:-1])
    xproj = table[ids.astype(jnp.int32)]          # [B, T, 4D]
    return _fused_lstm_tail(ctx, "fused_embedding_fc_lstm", xproj, ins,
                            attrs)


@register("fusion_seqexpand_concat_fc", no_grad_slots=("SeqLen",))
def _fusion_seqexpand_concat_fc(ctx, ins, attrs):
    """fusion_seqexpand_concat_fc_op.cc: X[0] is a [B,T,D0] sequence, the
    rest are per-batch [B,Di] rows broadcast over T; concat features,
    fc + activation in one op."""
    xs = ins["X"]
    seq = xs[0]
    B, T = seq.shape[0], seq.shape[1]
    parts = [seq]
    for x in xs[1:]:
        parts.append(jnp.broadcast_to(x[:, None, :], (B, T, x.shape[-1])))
    cat = jnp.concatenate(parts, axis=-1)
    w = ins["FCWeight"][0]
    out = jnp.einsum("btm,mf->btf", cat, w)
    if ins.get("FCBias"):
        out = out + ins["FCBias"][0].reshape(1, 1, -1)
    act = attrs.get("fc_activation", "identity")
    acts = {"identity": lambda v: v, "relu": jax.nn.relu,
            "tanh": jnp.tanh, "sigmoid": jax.nn.sigmoid}
    if act not in acts:
        raise ValueError(
            f"fusion_seqexpand_concat_fc: unknown fc_activation {act!r} "
            f"(supported: {sorted(acts)})")
    return {"Out": [acts[act](out)]}
