"""device_idle_share: 1 - (union of device-operation intervals / traced
window), per chip, in percent; the idlest chip is reported."""
from bench import trace_reduce


def read(run):
    if run.trace is None or not run.trace.ops or run.trace.window_s <= 0:
        return None
    return 100.0 * max(trace_reduce.idle_shares(run.trace).values())
