"""gp_device_ms: device milliseconds of the GP surrogate's compiled programs
per ask-and-tell cycle of the traced window, averaged over chips that ran
them.  The programs are found by the names of the surrogate's jitted
functions in the trace's XLA modules."""
from bench import trace_reduce

GP_PROGRAMS = ("_append_jit", "_refactor_jit", "_rethin_jit", "_fit_y_jit",
               "_predict_jit", "_predict_mean_jit", "_ehvi_jit")


def gp_device_s(run):
    """Seconds the GP's programs ran in the traced window, averaged over the
    chips that ran them; None where none ran."""
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    per_chip = [trace_reduce.time_by_name(evs, GP_PROGRAMS, lo, hi)
                for evs in run.trace.modules.values()]
    per_chip = [t for t in per_chip if t > 0]
    return sum(per_chip) / len(per_chip) if per_chip else None


def read(run):
    secs = gp_device_s(run)
    cycles = len(run.spans("ask"))
    if secs is None or not cycles:
        return None
    return 1e3 * secs / cycles
