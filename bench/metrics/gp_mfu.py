"""gp_mfu: the float64 operations the surrogate's calls need (counted from
their shapes by ``bench/counts.py``) over the device seconds of the GP's
programs (``gp_device_ms``'s source), both in the traced part of the window,
as a percentage of the chip's bf16 peak from ``peaks.json``.

The chip publishes no float64 peak and XLA emulates float64 with several
bf16 passes per operation, so the number sits far under 100% by design: it
is the rate the emulated GP reaches, stated on the bf16 scale.  It rises
when the same counted work takes less device time."""
from bench.metrics.gp_device_ms import gp_device_s


def read(run):
    secs = gp_device_s(run)
    ops = sum(n for t, n in run.rec.gp_flops if run.lo <= t < run.hi)
    if not secs or ops <= 0:
        return None
    return 100.0 * ops / (secs * run.peaks["flops_bf16"])
