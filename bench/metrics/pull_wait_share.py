"""pull_wait_share: the share of the traced window the host loop spent in
``jx.host.pull``, waiting on the boards' results, in percent."""
from bench import host_spans as hs


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    pulls = hs.named(hs.program_spans(run.trace), "jx.host.pull")
    if not pulls:
        return None
    secs = hs.uncovered_s(pulls, [], *run.trace.window)
    return 100.0 * secs / run.trace.window_s
