"""build_analyze_s: seconds of each board build (``jx.client.build``) that
started in the traced window spent reading the compiled programs' costs and
the analytic HBM bytes (``jx.build.analyze``), averaged over those
builds."""
from bench import host_spans as hs


def read(run):
    return hs.per_build_s(run, "jx.build.analyze")
