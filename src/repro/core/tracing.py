"""Host spans of the program, for the profiler's trace.

``span(name, **stats)`` is a ``jax.profiler.TraceAnnotation``.  With no
profiler session running it records nothing and costs under a microsecond
to enter and leave; under ``jax.profiler.trace(dir)`` it lands
on the host line of the thread that opened it, on the clock of the device's
``XLA Ops`` and ``XLA Modules`` lines, with ``stats`` as its arguments.

Every span is named ``jx.<layer>.<phase>`` (README, "Tracing a sweep"):
``jx.host.*`` in the one exploration loop (``ExploreService.run``, which
``JHost.explore`` runs for a solo sweep), ``jx.search.*`` in the model-based
searchers, ``jx.gp.*`` in the device GP, ``jx.client.*`` on the board's
thread, ``jx.build.*`` in the build.  No span sits inside a per-config
loop and none waits on the device: ``jx.gp.fetch`` wraps the copies back to
the host that the code makes anyway.
"""
from __future__ import annotations

import jax.profiler


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """A host span ``name`` carrying ``stats`` (ints or strings)."""
    return jax.profiler.TraceAnnotation(name, **stats)
