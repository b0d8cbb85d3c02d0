"""Trained tokens per second (of the traced run itself) times the model's
operations per token, over chips times the table's bf16 peak, in %.  The
operations are those the forward and backward passes require
(``peaks.encdec_train_flops_per_token``); nothing recomputed is counted."""
from benchmark import peaks


def read(ctx):
    rate = (ctx.get("end_to_end") or {}).get("train_tokens_per_s")
    if not rate:
        return None
    cfg, mix = ctx["config"], ctx["mix"]
    per_token = peaks.encdec_train_flops_per_token(
        cfg, int(mix["src_len"]), int(mix["tgt_len"]))
    return peaks.model_flops_util(rate, per_token, int(ctx["chips"]),
                                  ctx["memory"]["kind"])
