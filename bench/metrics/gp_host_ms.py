"""gp_host_ms: milliseconds per ask spent inside the device GP's calls
(``jx.gp.*``: padding and copying arguments, dispatching the jitted
programs, waiting for and copying back results) while none of the GP's
programs ran on a chip, over the traced window."""
from bench import host_spans as hs
from bench.metrics.gp_device_ms import GP_PROGRAMS


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    programs = [ev for evs in run.trace.modules.values() for ev in evs
                if any(p in ev[0] for p in GP_PROGRAMS)]
    gp = hs.named(hs.program_spans(run.trace), "jx.gp.")
    if not gp:
        return None
    return hs.per_ask_ms(run, hs.uncovered_s(gp, programs, lo, hi))
