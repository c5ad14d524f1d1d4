"""build_compile_s: seconds of each board build (``jx.client.build``) that
started in the traced window spent compiling the lowered programs, or
loading them from the persistent compile cache (``jx.build.compile``),
averaged over those builds."""
from bench import host_spans as hs


def read(run):
    return hs.per_build_s(run, "jx.build.compile")
