"""search_share: the share of the window spent inside the searcher's ask and
tell calls (host spans of the harness's search recorder), in percent."""


def read(run):
    spans = run.spans("ask", "tell")
    if not spans or run.window_s <= 0:
        return None
    return 100.0 * sum(e - s for _, s, e in spans) / run.window_s
