"""The window layers' sums over the window's drained segments
(``serve/segment_drain`` carries ``rows_window`` / ``rows_window_live`` where
the model has sliding-window layers in a paged cache), each weighted by the
segment's ``steps_run``: ``(rows the window layers' walk covered, the rows
inside the lanes' windows, the lanes' lengths, lane-steps, decode steps)``,
or ``None`` on a program or a model without them."""

from benchmarks.layer_metrics import _loop_spans as ls


def sums(run: dict):
    w = ls.window(run)
    if w is None:
        return None
    segs = [a for a in ls.drained(w)
            if "rows_window" in a and "rows_window_live" in a]
    steps = sum(a["steps_run"] for a in segs)
    if not steps:
        return None
    return (sum(a["rows_window"] * a["steps_run"] for a in segs),
            sum(a["rows_window_live"] * a["steps_run"] for a in segs),
            sum(a["rows_live"] * a["steps_run"] for a in segs),
            sum(a["lanes"] * a["steps_run"] for a in segs), steps)
