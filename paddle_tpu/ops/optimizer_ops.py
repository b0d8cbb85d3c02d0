"""Optimizer op lowerings — per-parameter device-side updates.

Reference coverage: ``sgd_op``, ``momentum_op``, ``adam_op``, ``adagrad_op``,
``adamax_op``, ``adadelta_op``, ``rmsprop_op``, ``ftrl_op``,
``decayed_adagrad_op``, ``lars_momentum`` (paddle/fluid/operators/*.cc).

These ops write to persistable vars (ParamOut aliases Param etc.); the
executor detects the writes and returns updated state — functional in-place
updates with donated buffers, so XLA reuses the parameter's HBM allocation.
Accumulator math runs in the accumulator's own dtype (keep fp32 accumulators
under bf16 params — the standard TPU mixed-precision recipe).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from ..core.registry import register
from ..core.selected_rows import (
    SelectedRows, dense_grad_and_mask, gather_rows, merge_rows,
    prefer_dense_update, scatter_set_rows)


def _lr(ins, dtype=None):
    lr = ins["LearningRate"][0]
    lr = lr.reshape(()) if hasattr(lr, "reshape") else lr
    return lr.astype(dtype) if dtype is not None else lr


def _is_sparse(g):
    return isinstance(g, SelectedRows)


def _dense_only(g, op):
    if isinstance(g, SelectedRows):
        raise NotImplementedError(
            f"optimizer op {op!r} has no sparse (SelectedRows) update path; "
            "use sgd/momentum/adam/adagrad for is_sparse embeddings")
    return g


@register("sgd")
def _sgd(ctx, ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    lr = _lr(ins, p.dtype)
    if _is_sparse(g):
        # sparse path (sgd_op.h:47-52): scatter-add touches only the looked-up
        # rows; duplicates accumulate, which is exact for plain SGD
        return {"ParamOut": [
            p.at[g.rows].add(-lr * g.values.astype(p.dtype), mode="drop")]}
    return {"ParamOut": [p - lr * g.astype(p.dtype)]}


@register("momentum")
def _momentum(ctx, ins, attrs):
    p, g, v = ins["Param"][0], ins["Grad"][0], ins["Velocity"][0]
    mu = jnp.asarray(attrs.get("mu", 0.9), v.dtype)
    lr = _lr(ins, v.dtype)
    if _is_sparse(g):
        if prefer_dense_update(g):
            gd, t = dense_grad_and_mask(g, v.dtype)
            v_new = jnp.where(t, mu * v + gd, v)
            pf = p.astype(v.dtype)
            if attrs.get("use_nesterov", False):
                p_new = jnp.where(t, pf - (gd + mu * v_new) * lr, pf)
            else:
                p_new = jnp.where(t, pf - lr * v_new, pf)
            return {"ParamOut": [p_new.astype(p.dtype)],
                    "VelocityOut": [v_new]}
        m = merge_rows(g)
        rows, gf = m.rows, m.values.astype(v.dtype)
        vr = gather_rows(v, rows)
        pr = gather_rows(p, rows).astype(v.dtype)
        v_new_r = mu * vr + gf
        if attrs.get("use_nesterov", False):
            p_new_r = pr - (gf + mu * v_new_r) * lr
        else:
            p_new_r = pr - lr * v_new_r
        return {"ParamOut": [scatter_set_rows(p, rows, p_new_r)],
                "VelocityOut": [scatter_set_rows(v, rows, v_new_r)]}
    g = g.astype(v.dtype)
    v_new = mu * v + g
    if attrs.get("use_nesterov", False):
        p_new = p - (g + mu * v_new).astype(p.dtype) * lr.astype(p.dtype)
    else:
        p_new = p - (lr * v_new).astype(p.dtype)
    return {"ParamOut": [p_new], "VelocityOut": [v_new]}


@register("adam")
def _adam(ctx, ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    m1, m2 = ins["Moment1"][0], ins["Moment2"][0]
    b1p, b2p = ins["Beta1Pow"][0], ins["Beta2Pow"][0]
    beta1 = jnp.asarray(attrs.get("beta1", 0.9), m1.dtype)
    beta2 = jnp.asarray(attrs.get("beta2", 0.999), m2.dtype)
    eps = jnp.asarray(attrs.get("epsilon", 1e-8), m1.dtype)
    if _is_sparse(g):
        # sparse (lazy) adam: update moments and param for touched rows only
        # (reference adam_op.h SelectedRows path)
        lr = (_lr(ins, m1.dtype)
              * jnp.sqrt(1 - b2p.reshape(())) / (1 - b1p.reshape(())))
        if prefer_dense_update(g):
            gd, t = dense_grad_and_mask(g, m1.dtype)
            m1n = jnp.where(t, beta1 * m1 + (1 - beta1) * gd, m1)
            m2n = jnp.where(t, beta2 * m2 + (1 - beta2) * gd * gd, m2)
            step = lr * m1n / (jnp.sqrt(m2n) + eps)
            pf = p.astype(m1.dtype)
            return {
                "ParamOut": [jnp.where(t, pf - step, pf).astype(p.dtype)],
                "Moment1Out": [m1n],
                "Moment2Out": [m2n],
                "Beta1PowOut": [b1p * beta1],
                "Beta2PowOut": [b2p * beta2],
            }
        m = merge_rows(g)
        rows, gf = m.rows, m.values.astype(m1.dtype)
        m1r, m2r = gather_rows(m1, rows), gather_rows(m2, rows)
        pr = gather_rows(p, rows).astype(m1.dtype)
        m1n = beta1 * m1r + (1 - beta1) * gf
        m2n = beta2 * m2r + (1 - beta2) * gf * gf
        step = lr * m1n / (jnp.sqrt(m2n) + eps)
        return {
            "ParamOut": [scatter_set_rows(p, rows, pr - step)],
            "Moment1Out": [scatter_set_rows(m1, rows, m1n)],
            "Moment2Out": [scatter_set_rows(m2, rows, m2n)],
            "Beta1PowOut": [b1p * beta1],
            "Beta2PowOut": [b2p * beta2],
        }
    gf = g.astype(m1.dtype)
    m1n = beta1 * m1 + (1 - beta1) * gf
    m2n = beta2 * m2 + (1 - beta2) * gf * gf
    lr = _lr(ins, m1.dtype) * jnp.sqrt(1 - b2p.reshape(())) / (1 - b1p.reshape(()))
    step = lr * m1n / (jnp.sqrt(m2n) + eps)
    return {
        "ParamOut": [(p.astype(m1.dtype) - step).astype(p.dtype)],
        "Moment1Out": [m1n],
        "Moment2Out": [m2n],
        "Beta1PowOut": [b1p * beta1],
        "Beta2PowOut": [b2p * beta2],
    }


@register("adagrad")
def _adagrad(ctx, ins, attrs):
    p, g, mom = ins["Param"][0], ins["Grad"][0], ins["Moment"][0]
    eps = jnp.asarray(attrs.get("epsilon", 1e-6), mom.dtype)
    if _is_sparse(g):
        if prefer_dense_update(g):
            gd, t = dense_grad_and_mask(g, mom.dtype)
            mom_new = jnp.where(t, mom + gd * gd, mom)
            pf = p.astype(mom.dtype)
            step = _lr(ins, mom.dtype) * gd / (jnp.sqrt(mom_new) + eps)
            return {"ParamOut": [jnp.where(t, pf - step, pf).astype(p.dtype)],
                    "MomentOut": [mom_new]}
        m = merge_rows(g)
        rows, gf = m.rows, m.values.astype(mom.dtype)
        momr = gather_rows(mom, rows)
        pr = gather_rows(p, rows).astype(mom.dtype)
        mom_new_r = momr + gf * gf
        p_new_r = pr - _lr(ins, mom.dtype) * gf / (jnp.sqrt(mom_new_r) + eps)
        return {"ParamOut": [scatter_set_rows(p, rows, p_new_r)],
                "MomentOut": [scatter_set_rows(mom, rows, mom_new_r)]}
    gf = g.astype(mom.dtype)
    mom_new = mom + gf * gf
    p_new = p - (_lr(ins, mom.dtype) * gf / (jnp.sqrt(mom_new) + eps)).astype(p.dtype)
    return {"ParamOut": [p_new], "MomentOut": [mom_new]}


@register("decayed_adagrad")
def _decayed_adagrad(ctx, ins, attrs):
    ins = {**ins, "Grad": [_dense_only(ins["Grad"][0], "decayed_adagrad")]}
    p, g, mom = ins["Param"][0], ins["Grad"][0], ins["Moment"][0]
    decay = jnp.asarray(attrs.get("decay", 0.95), mom.dtype)
    eps = jnp.asarray(attrs.get("epsilon", 1e-6), mom.dtype)
    gf = g.astype(mom.dtype)
    mom_new = decay * mom + (1 - decay) * gf * gf
    p_new = p - (_lr(ins, mom.dtype) * gf / (jnp.sqrt(mom_new) + eps)).astype(p.dtype)
    return {"ParamOut": [p_new], "MomentOut": [mom_new]}


@register("adamax")
def _adamax(ctx, ins, attrs):
    ins = {**ins, "Grad": [_dense_only(ins["Grad"][0], "adamax")]}
    p, g = ins["Param"][0], ins["Grad"][0]
    m, inf = ins["Moment"][0], ins["InfNorm"][0]
    b1p = ins["Beta1Pow"][0]
    beta1 = jnp.asarray(attrs.get("beta1", 0.9), m.dtype)
    beta2 = jnp.asarray(attrs.get("beta2", 0.999), m.dtype)
    eps = jnp.asarray(attrs.get("epsilon", 1e-8), m.dtype)
    gf = g.astype(m.dtype)
    m_new = beta1 * m + (1 - beta1) * gf
    inf_new = jnp.maximum(beta2 * inf, jnp.abs(gf))
    lr = _lr(ins, m.dtype) / (1 - b1p.reshape(()))
    p_new = p - (lr * m_new / (inf_new + eps)).astype(p.dtype)
    return {"ParamOut": [p_new], "MomentOut": [m_new], "InfNormOut": [inf_new],
            "Beta1PowOut": [b1p * beta1]}


@register("adadelta")
def _adadelta(ctx, ins, attrs):
    ins = {**ins, "Grad": [_dense_only(ins["Grad"][0], "adadelta")]}
    p, g = ins["Param"][0], ins["Grad"][0]
    avg_sq_g, avg_sq_u = ins["AvgSquaredGrad"][0], ins["AvgSquaredUpdate"][0]
    rho = jnp.asarray(attrs.get("rho", 0.95), avg_sq_g.dtype)
    eps = jnp.asarray(attrs.get("epsilon", 1e-6), avg_sq_g.dtype)
    gf = g.astype(avg_sq_g.dtype)
    asg_new = rho * avg_sq_g + (1 - rho) * gf * gf
    update = -jnp.sqrt((avg_sq_u + eps) / (asg_new + eps)) * gf
    asu_new = rho * avg_sq_u + (1 - rho) * update * update
    return {"ParamOut": [(p.astype(gf.dtype) + update).astype(p.dtype)],
            "AvgSquaredGradOut": [asg_new], "AvgSquaredUpdateOut": [asu_new]}


@register("rmsprop")
def _rmsprop(ctx, ins, attrs):
    ins = {**ins, "Grad": [_dense_only(ins["Grad"][0], "rmsprop")]}
    p, g = ins["Param"][0], ins["Grad"][0]
    ms, mom = ins["MeanSquare"][0], ins["Moment"][0]
    rho = jnp.asarray(attrs.get("decay", 0.95), ms.dtype)
    eps = jnp.asarray(attrs.get("epsilon", 1e-6), ms.dtype)
    momentum = jnp.asarray(attrs.get("momentum", 0.0), ms.dtype)
    gf = g.astype(ms.dtype)
    ms_new = rho * ms + (1 - rho) * gf * gf
    if attrs.get("centered", False):
        mg = ins["MeanGrad"][0]
        mg_new = rho * mg + (1 - rho) * gf
        denom = ms_new - mg_new * mg_new + eps
    else:
        mg_new = None
        denom = ms_new + eps
    mom_new = momentum * mom + _lr(ins, ms.dtype) * gf * lax.rsqrt(denom)
    out = {"ParamOut": [(p.astype(gf.dtype) - mom_new).astype(p.dtype)],
           "MeanSquareOut": [ms_new], "MomentOut": [mom_new]}
    if mg_new is not None:
        out["MeanGradOut"] = [mg_new]
    return out


@register("ftrl")
def _ftrl(ctx, ins, attrs):
    ins = {**ins, "Grad": [_dense_only(ins["Grad"][0], "ftrl")]}
    p, g = ins["Param"][0], ins["Grad"][0]
    sq_acc, lin_acc = ins["SquaredAccumulator"][0], ins["LinearAccumulator"][0]
    l1 = jnp.asarray(attrs.get("l1", 0.0), sq_acc.dtype)
    l2 = jnp.asarray(attrs.get("l2", 0.0), sq_acc.dtype)
    lr_power = jnp.asarray(attrs.get("lr_power", -0.5), sq_acc.dtype)
    lr = _lr(ins, sq_acc.dtype)
    gf = g.astype(sq_acc.dtype)
    new_sq = sq_acc + gf * gf
    sigma = (jnp.power(new_sq, -lr_power) - jnp.power(sq_acc, -lr_power)) / lr
    lin_new = lin_acc + gf - sigma * p.astype(sq_acc.dtype)
    x = jnp.clip(lin_new, -l1, l1) - lin_new
    y = jnp.power(new_sq, -lr_power) / lr + 2 * l2
    p_new = (x / y).astype(p.dtype)
    return {"ParamOut": [p_new], "SquaredAccumOut": [new_sq], "LinearAccumOut": [lin_new]}


@register("lars_momentum")
def _lars_momentum(ctx, ins, attrs):
    ins = {**ins, "Grad": [_dense_only(ins["Grad"][0], "lars_momentum")]}
    p, g, v = ins["Param"][0], ins["Grad"][0], ins["Velocity"][0]
    mu = jnp.asarray(attrs.get("mu", 0.9), v.dtype)
    lars_coeff = attrs.get("lars_coeff", 1e-3)
    lars_wd = attrs.get("lars_weight_decay", 5e-4)
    lr = _lr(ins, v.dtype)
    gf = g.astype(v.dtype)
    pf = p.astype(v.dtype)
    p_norm = jnp.sqrt(jnp.sum(pf * pf))
    g_norm = jnp.sqrt(jnp.sum(gf * gf))
    local_lr = lr * lars_coeff * p_norm / (g_norm + lars_wd * p_norm + 1e-12)
    v_new = mu * v + local_lr * (gf + lars_wd * pf)
    return {"ParamOut": [(pf - v_new).astype(p.dtype)], "VelocityOut": [v_new]}


@register("average_accumulates",
          no_grad_slots=("param", "in_sum_1", "in_sum_2", "in_sum_3",
                         "in_num_accumulates", "in_old_num_accumulates",
                         "in_num_updates"))
def _average_accumulates(ctx, ins, attrs):
    """average_accumulates_op.h: sliding-window parameter sums for
    ModelAverage.  sum_1 accumulates every step; every 16384 updates it
    rolls into sum_2 (precision); when the window closes (num_accumulates
    >= min(max_window, num_updates*window_rate)) everything rolls into
    sum_3 and the window restarts."""
    k_max = 16384
    param = ins["param"][0]
    s1, s2, s3 = ins["in_sum_1"][0], ins["in_sum_2"][0], ins["in_sum_3"][0]
    num_acc = ins["in_num_accumulates"][0].reshape(()).astype(jnp.int64)
    old_acc = ins["in_old_num_accumulates"][0].reshape(()).astype(jnp.int64)
    num_upd = ins["in_num_updates"][0].reshape(()).astype(jnp.int64)
    window = float(attrs.get("average_window", 0.0))
    max_w = int(attrs.get("max_average_window", 2 ** 62))
    min_w = int(attrs.get("min_average_window", 10000))

    num_upd = num_upd + 1
    num_acc = num_acc + 1
    s1 = s1 + param.astype(s1.dtype)

    roll_precision = (num_upd % k_max) == 0
    s2 = jnp.where(roll_precision, s2 + s1, s2)
    s1 = jnp.where(roll_precision, 0.0, s1)

    close = (num_acc >= min_w) & (
        num_acc >= jnp.minimum(
            jnp.asarray(max_w, jnp.int64),
            (num_upd.astype(jnp.float32) * window).astype(jnp.int64)))
    s3 = jnp.where(close, s1 + s2 + s3 * 0, s3)
    s1 = jnp.where(close, 0.0, s1)
    s2 = jnp.where(close, 0.0, s2)
    old_acc = jnp.where(close, num_acc, old_acc)
    num_acc = jnp.where(close, 0, num_acc)

    return {"out_sum_1": [s1], "out_sum_2": [s2], "out_sum_3": [s3],
            "out_num_accumulates": [num_acc.reshape(1)],
            "out_old_num_accumulates": [old_acc.reshape(1)],
            "out_num_updates": [num_upd.reshape(1)]}


def _prox(prox_param, lr, l1, l2):
    """Proximal step (proximal_gd_op.cc): soft-threshold by lr*l1 then
    shrink by 1/(1+lr*l2)."""
    return (jnp.sign(prox_param)
            * jnp.maximum(jnp.abs(prox_param) - lr * l1, 0.0)
            / (1.0 + lr * l2))


@register("proximal_gd")
def _proximal_gd(ctx, ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    _dense_only(g, "proximal_gd")
    lr = _lr(ins, jnp.float32)
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    prox = p.astype(jnp.float32) - lr * g.astype(jnp.float32)
    return {"ParamOut": [_prox(prox, lr, l1, l2).astype(p.dtype)]}


@register("proximal_adagrad")
def _proximal_adagrad(ctx, ins, attrs):
    p, g = ins["Param"][0], ins["Grad"][0]
    _dense_only(g, "proximal_adagrad")
    mom = ins["Moment"][0]
    lr = _lr(ins, jnp.float32)
    l1 = attrs.get("l1", 0.0)
    l2 = attrs.get("l2", 0.0)
    gf = g.astype(mom.dtype)
    mom_out = mom + gf * gf
    eff_lr = lr / jnp.sqrt(mom_out + 1e-12)
    prox = p.astype(jnp.float32) - eff_lr * gf
    return {"ParamOut": [_prox(prox, eff_lr, l1, l2).astype(p.dtype)],
            "MomentOut": [mom_out]}
