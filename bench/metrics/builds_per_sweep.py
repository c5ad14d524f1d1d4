"""builds_per_sweep: compiles the boards counted (``JClient.n_compiled``)
over the sweeps that started in the traced part of the window, per
sweep."""


def read(run):
    sweeps = run.window_sweeps()
    total = sum(s.n_compiled for s in sweeps)
    if not sweeps or not total:
        return None
    return total / len(sweeps)
