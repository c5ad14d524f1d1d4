"""acquire_ms: milliseconds per ask in ``jx.search.acquire`` (each pick:
scalarising, the GP's fit and posterior, the acquisition score, sorting and
decoding the pick), over the traced window.  It holds the ``jx.gp.*``
calls the picks make."""
from bench import host_spans as hs


def read(run):
    if run.trace is None:
        return None
    picks = hs.named(hs.program_spans(run.trace), "jx.search.acquire")
    if not picks:
        return None
    return hs.per_ask_ms(run, hs.uncovered_s(picks, [], *run.trace.window))
