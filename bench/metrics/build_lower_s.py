"""build_lower_s: seconds of each board build (``jx.client.build``) that
started in the traced window spent tracing the model and lowering it for
prefill and decode (``jx.build.lower``), averaged over those builds."""
from bench import host_spans as hs


def read(run):
    return hs.per_build_s(run, "jx.build.lower")
