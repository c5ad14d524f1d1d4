"""pool_ms: milliseconds per ask in ``jx.search.pool``, the searcher sampling
its candidate pool on the host, over the traced window."""
from bench import host_spans as hs


def read(run):
    if run.trace is None:
        return None
    pools = hs.named(hs.program_spans(run.trace), "jx.search.pool")
    if not pools:
        return None
    return hs.per_ask_ms(run, hs.uncovered_s(pools, [], *run.trace.window))
