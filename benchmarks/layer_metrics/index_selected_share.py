"""Percent of the rows an indexer scored that attention then read:
sum(``rows_selected`` x ``steps_run``) over sum(``rows_scored`` x
``steps_run``) of the window's ``serve/segment_drain`` spans
(``rows_scored`` the live lanes' lengths at the segment's dispatch, every
one of which the scores read; ``rows_selected`` ``min(length, index_topk)``
of each).  100 where no lane holds more than ``index_topk`` rows; ``None``
on a program or a model without the fields."""

from benchmarks.layer_metrics import _index_spans as ix


def read(run: dict):
    sums = ix.per_step(run)
    if sums is None or not sums[0]:
        return None
    return 100.0 * sums[1] / sums[0]
