"""Composite network helpers (reference python/paddle/fluid/nets.py):
compositions over the layer DSL, no new ops."""
from __future__ import annotations

import math

from . import layers
from .param_attr import ParamAttr

__all__ = [
    "switch_moe",
    "moe_sharding_rules",
    "simple_img_conv_pool",
    "sequence_conv_pool",
    "glu",
    "scaled_dot_product_attention",
    "img_conv_group",
]


def simple_img_conv_pool(input, num_filters, filter_size, pool_size,
                         pool_stride, pool_padding=0, pool_type="max",
                         global_pooling=False, conv_stride=1, conv_padding=0,
                         conv_dilation=1, conv_groups=1, param_attr=None,
                         bias_attr=None, act=None, use_cudnn=True):
    conv_out = layers.conv2d(
        input, num_filters, filter_size, stride=conv_stride,
        padding=conv_padding, dilation=conv_dilation, groups=conv_groups,
        param_attr=param_attr, bias_attr=bias_attr, act=act)
    return layers.pool2d(conv_out, pool_size, pool_type, pool_stride,
                         pool_padding, global_pooling=global_pooling)


def img_conv_group(input, conv_num_filter, pool_size, conv_padding=1,
                   conv_filter_size=3, conv_act=None, param_attr=None,
                   conv_with_batchnorm=False, conv_batchnorm_drop_rate=0.0,
                   pool_stride=1, pool_type="max", use_cudnn=True):
    """Stacked conv (+optional BN/dropout) blocks followed by one pool —
    the VGG building block."""
    tmp = input
    assert isinstance(conv_num_filter, (list, tuple))

    def expand(v):
        return v if isinstance(v, (list, tuple)) \
            else [v] * len(conv_num_filter)

    conv_padding = expand(conv_padding)
    conv_filter_size = expand(conv_filter_size)
    param_attr = expand(param_attr) if isinstance(param_attr, (list, tuple)) \
        else [param_attr] * len(conv_num_filter)
    conv_with_batchnorm = expand(conv_with_batchnorm)
    conv_batchnorm_drop_rate = expand(conv_batchnorm_drop_rate)

    for i in range(len(conv_num_filter)):
        local_conv_act = conv_act
        if conv_with_batchnorm[i]:
            local_conv_act = None
        tmp = layers.conv2d(
            tmp, conv_num_filter[i], conv_filter_size[i],
            padding=conv_padding[i], param_attr=param_attr[i],
            act=local_conv_act)
        if conv_with_batchnorm[i]:
            tmp = layers.batch_norm(tmp, act=conv_act)
            if conv_batchnorm_drop_rate[i]:
                tmp = layers.dropout(tmp, conv_batchnorm_drop_rate[i])
    return layers.pool2d(tmp, pool_size, pool_type, pool_stride)


def sequence_conv_pool(input, num_filters, filter_size, param_attr=None,
                       act="sigmoid", pool_type="max"):
    conv_out = layers.sequence_conv(input, num_filters, filter_size,
                                    param_attr=param_attr, act=act)
    return layers.sequence_pool(conv_out, pool_type)


def glu(input, dim=-1):
    a, b = layers.split(input, num_or_sections=2, dim=dim)
    from .layers.ops import sigmoid
    return layers.elementwise_mul(a, sigmoid(b))


def scaled_dot_product_attention(queries, keys, values, num_heads=1,
                                 dropout_rate=0.0):
    """Multi-head scaled dot-product attention over [B, T, D] tensors
    (reference nets.py:333); returns [B, Tq, Dv]."""
    if queries.shape[-1] != keys.shape[-1]:
        raise ValueError("queries and keys must have the same hidden size")
    d_key = int(keys.shape[-1]) // num_heads

    def split_heads(x):
        if num_heads == 1:
            return x
        b, t, d = x.shape
        r = layers.reshape(x, [b, t, num_heads, d // num_heads])
        return layers.transpose(r, [0, 2, 1, 3])

    def combine_heads(x):
        if num_heads == 1:
            return x
        b, h, t, d = x.shape
        return layers.reshape(layers.transpose(x, [0, 2, 1, 3]),
                              [b, t, h * d])

    q, k, v = split_heads(queries), split_heads(keys), split_heads(values)
    scaled_q = layers.scale(q, scale=d_key ** -0.5)
    product = layers.matmul(scaled_q, k, transpose_y=True)
    weights = layers.softmax(product)
    if dropout_rate:
        weights = layers.dropout(weights, dropout_prob=dropout_rate)
    ctx = layers.matmul(weights, v)
    return combine_heads(ctx)


def switch_moe(input, num_experts, d_ffn, capacity_factor=1.25,
              capacity_per_expert=None, name_prefix=None,
              return_aux=False):
    """Switch-style top-1 mixture-of-experts FFN with expert parallelism
    (no reference analogue — the TPU-native §7 extension; GShard-pattern
    dispatch/combine einsums expressed as one-hot matmuls so GSPMD turns
    them into all-to-alls when the expert weight dim is sharded over an
    ``ep`` mesh axis via :func:`moe_sharding_rules`).

    input [N, D] -> output [N, D]; each token is routed to its top-1
    expert (capacity C = ceil(N/E * capacity_factor); overflow tokens
    drop to zero, the standard Switch contract), runs that expert's
    2-layer relu FFN, and is scaled by its gate probability (the
    gradient path that trains the router).

    ``name_prefix=None`` (default) generates a unique prefix per call so
    stacked MoE layers never share weights; pass an explicit prefix to
    share weights across programs (train/infer) — and the SAME prefix to
    :func:`moe_sharding_rules`.

    ``return_aux=True`` returns ``(output, aux_loss, dropped_frac)``:

    - ``aux_loss`` [scalar] — the standard Switch load-balancing loss,
      ``E * sum_e(f_e * P_e)`` with ``f_e`` the fraction of tokens
      routed to expert ``e`` (pre-capacity argmax routing) and ``P_e``
      the mean gate probability of ``e``.  Uniform routing gives 1.0;
      add a small multiple (Switch uses 0.01) to the training loss to
      regularize against router collapse.
    - ``dropped_frac`` [scalar] — the fraction of tokens dropped by the
      capacity limit this batch (overflow tokens pass through as
      zeros); a rising value means the router is hot-spotting or
      ``capacity_factor`` is too small.

    This is the TRAINER's mixture (top-1, a capacity, dense one-hot
    dispatch over a mesh axis).  Serving computes every assignment and
    drops none: the decode plane's routed experts — top-k of a softmax
    router by sort, a grouped SwiGLU over the experts — are
    :func:`paddle_tpu.kernels.moe.routed_experts`
    (``decode/mla.py`` is its caller).
    """
    from .core import unique_name

    if name_prefix is None:
        name_prefix = unique_name.generate("moe")
    N, D = int(input.shape[0]), int(input.shape[1])
    E = int(num_experts)
    if capacity_per_expert is not None:
        C = int(capacity_per_expert)
    elif N > 0:
        C = int(math.ceil(N / E * capacity_factor))
    else:
        raise ValueError(
            "switch_moe needs capacity_per_expert when the token/batch "
            "dim is dynamic (-1): the dispatch tensor's [E, C] extent "
            "must be static for XLA")

    gate_probs = layers.softmax(layers.fc(
        input, E, param_attr=ParamAttr(name=f"{name_prefix}.gate.w"),
        bias_attr=False))                                   # [N, E]
    expert_idx = layers.argmax(gate_probs, axis=-1)         # [N]
    mask = layers.one_hot(
        layers.unsqueeze(expert_idx, [1]), E)               # [N, E] f32
    gate = layers.reduce_sum(layers.elementwise_mul(gate_probs, mask),
                             dim=-1, keep_dim=True)         # [N, 1]

    if return_aux:
        # Switch load-balancing loss over the PRE-capacity routing
        # decisions (capacity drops are what the loss prevents, they
        # must not hide from it): E * <f_e, P_e>
        frac_routed = layers.reduce_mean(mask, dim=0)       # [E]
        mean_prob = layers.reduce_mean(gate_probs, dim=0)   # [E]
        aux_loss = layers.scale(
            layers.reduce_sum(
                layers.elementwise_mul(frac_routed, mean_prob)),
            scale=float(E))                                 # scalar
        # token count as a tensor (the batch dim may be dynamic; the
        # pre-capacity mask has exactly one 1 per token)
        total_tokens = layers.reduce_sum(mask)              # scalar

    # position of each token within its expert; tokens past capacity drop
    pos = layers.elementwise_mul(
        layers.cumsum(mask, axis=0, exclusive=True), mask)  # [N, E]
    keep = layers.cast(layers.less_than(
        pos, layers.fill_constant([1], "float32", float(C))), "float32")
    mask = layers.elementwise_mul(mask, keep)
    pos_ids = layers.cast(
        layers.reduce_sum(layers.elementwise_mul(pos, mask), dim=-1),
        "int64")                                            # [N]
    pos_hot = layers.one_hot(
        layers.unsqueeze(pos_ids, [1]), C)                  # [N, C] f32

    # dispatch [N, E, C] = mask[N,E] x pos_hot[N,C] (outer product)
    dispatch = layers.elementwise_mul(
        layers.unsqueeze(mask, [2]),
        layers.unsqueeze(pos_hot, [1]))                     # [N, E, C]
    disp_flat = layers.reshape(dispatch, [-1, E * C])

    # expert_in [E, C, D] = dispatch^T @ x — the GSPMD all-to-all site
    expert_in = layers.reshape(
        layers.matmul(layers.transpose(disp_flat, [1, 0]), input),
        [E, C, D])

    w1 = layers.create_parameter([E, D, d_ffn], "float32",
                                 name=f"{name_prefix}.w1")
    b1 = layers.create_parameter([E, 1, d_ffn], "float32",
                                 name=f"{name_prefix}.b1")  # per-expert
    w2 = layers.create_parameter([E, d_ffn, D], "float32",
                                 name=f"{name_prefix}.w2")
    h = layers.relu(layers.elementwise_add(
        layers.matmul(expert_in, w1), b1))                  # [E, C, F]
    expert_out = layers.matmul(h, w2)                       # [E, C, D]

    # combine [N, D] = dispatch @ expert_out, scaled by the gate prob
    out = layers.matmul(disp_flat,
                        layers.reshape(expert_out, [E * C, D]))
    out = layers.elementwise_mul(out, gate)
    if not return_aux:
        return out
    # dropped-token fraction: tokens whose dispatch row zeroed out at
    # the capacity cut (post-capacity mask sums to kept tokens)
    kept = layers.reduce_sum(mask)                          # scalar
    dropped_frac = layers.scale(
        layers.elementwise_div(kept, total_tokens), scale=-1.0, bias=1.0)
    # EP health observability: register both scalars as step-stat vars —
    # whenever a run FETCHES them (convergence loops, the ep dryrun
    # phase) and FLAGS_runtime_stats is on, the executor stamps them
    # into the StepStats record (/stepz) and same-named gauges
    # (/metrics); runlog picks scalar fetches up by name already
    prog = input.block.program
    prog.step_stat_vars[aux_loss.name] = f"moe.{name_prefix}.aux_loss"
    prog.step_stat_vars[dropped_frac.name] = \
        f"moe.{name_prefix}.dropped_frac"
    return out, aux_loss, dropped_frac


def moe_sharding_rules(name_prefix="moe"):
    """PartitionSpecs sharding every expert-batched weight over the
    ``ep`` mesh axis (use with BuildStrategy.sharding_rules; the
    dispatch/combine matmuls then carry the tokens across experts via
    GSPMD-inserted collectives)."""
    return [
        # trailing .* shards the Adam moment accumulators with their
        # expert weights (the deepfm.tp_sharding_rules precedent —
        # replicated moments would cost 2x the sharded weight bytes on
        # every device); scalar beta-pow accumulators stay replicated
        # via the divisibility guard
        (rf"{name_prefix}\.w1.*", ("ep", None, None)),
        (rf"{name_prefix}\.b1.*", ("ep", None, None)),
        (rf"{name_prefix}\.w2.*", ("ep", None, None)),
    ]
