"""``kernel_roofline.py``'s share for the SmallThinker stack's kernels: the
same timing of a named kernel inside the launches of a named program
(``kernel_roofline.timed``), with the work counted by
``benchmark/kernel_counts_smallthinker.COUNTS``.  Nothing where the run has
no raw trace, no such launch, no such kernel or no such span (the parent of
the PR that added them)."""
from benchmark import kernel_counts_smallthinker, peaks
from benchmark.metrics import kernel_roofline, program_spans


def read(ctx, program, kernel, count, span):
    spans = program_spans.load() if ctx.get("trace_raw") else None
    if not spans:
        return None
    got = kernel_roofline.timed(ctx["trace_raw"], spans, program, kernel,
                                span)
    if got is None or got[0] <= 0:
        return None
    seconds, work, _ = got
    try:
        ops, moved = kernel_counts_smallthinker.COUNTS[count](ctx["config"], work)
    except KeyError:
        return None
    peak = peaks.peaks_for(ctx["memory"]["kind"])
    return 100.0 * max(ops / seconds / peak["bf16_flops_per_s"],
                       moved / seconds / peak["hbm_bytes_per_s"])
