"""ask_idle_ms: milliseconds of each ask (``jx.host.ask``, the searcher
proposing a batch) in which no chip ran an operation, averaged over the asks
that started in the traced window."""
from bench import host_spans as hs


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    asks = hs.started_in(hs.named(hs.program_spans(run.trace),
                                  "jx.host.ask"), lo, hi)
    if not asks:
        return None
    idle = hs.uncovered_s(asks, hs.device_ops(run.trace), lo, hi)
    return 1e3 * idle / len(asks)
