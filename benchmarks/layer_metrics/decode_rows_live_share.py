"""Percent of the cache rows the paged decode kernel's arithmetic covered
that lay under a lane's length: sum(``rows_live`` x ``steps_run``) over
sum(``rows`` x ``steps_run``) of the window's ``serve/segment_drain`` spans
(``rows`` is the host's ``walk_rows`` of each live lane's length at the
segment's dispatch, ``rows_live`` the lengths).  ``None`` on a program whose
spans carry neither, and in a window that computed no row."""

from benchmarks.layer_metrics import _loop_spans as ls


def read(run: dict):
    w = ls.window(run)
    if w is None:
        return None
    segs = [a for a in ls.drained(w) if "rows" in a and "rows_live" in a]
    rows = sum(a["rows"] * a["steps_run"] for a in segs)
    if not rows:
        return None
    return 100.0 * sum(a["rows_live"] * a["steps_run"] for a in segs) / rows
