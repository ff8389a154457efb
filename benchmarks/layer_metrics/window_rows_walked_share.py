"""Percent of the lanes' cache rows that a sliding-window layer's decode
call still walks: sum(``rows_window`` x ``steps_run``) over sum(``rows_live``
x ``steps_run``) of the window's ``serve/segment_drain`` spans
(``rows_window`` is the host's ``walk_rows`` with the window of each live
lane's length, ``rows_live`` the lengths).  With one table for all layers it
would read about 100; ``min(length, window) / length`` is what the trim can
reach.  A program counter, no device read."""

from benchmarks.layer_metrics import _window_spans as ws


def read(run: dict):
    sums = ws.sums(run)
    if sums is None or not sums[2]:
        return None
    return 100.0 * sums[0] / sums[2]
