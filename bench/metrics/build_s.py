"""build_s: mean wall seconds of a build (one call of the ``build_fn`` each
board is given) that started in the traced part of the window."""


def read(run):
    times = [e - s for s, e in run.rec.builds if run.lo <= s < run.hi]
    if not times:
        return None
    return sum(times) / len(times)
