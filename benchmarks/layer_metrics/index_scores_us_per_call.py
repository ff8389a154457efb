"""Device time of one call of ``paged_index_scores`` in the decode step (one
layer of one step), found in the trace by its name inside runs of the
segment program; the mean over the traced seconds."""

from benchmarks.layer_metrics import _index_spans as ix


def read(run: dict):
    seconds = ix.kernel_call(run, ix.SCORES)
    return None if seconds is None else 1e6 * seconds
