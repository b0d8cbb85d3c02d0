"""Mean duration, in ms, of the program's spans called ``span`` that lie
wholly inside the traced window; with ``minus``, each span's self time: its
duration less what its children called ``minus`` (on its own thread) cover.
Both are whole-name regular expressions.  Nothing where the trace holds no
such span."""
from benchmark.metrics import program_spans


def read(ctx, span, minus=None):
    raw = program_spans.load()
    if not raw:
        return None
    return mean_ms(raw, span, minus)


def mean_ms(raw, span, minus=None):
    spans = program_spans.inside(raw, span)
    if not spans:
        return None
    ns = sum(s[3] - (program_spans.covered(raw, s, minus) if minus else 0.0)
             for s in spans)
    return ns / len(spans) / 1e6
