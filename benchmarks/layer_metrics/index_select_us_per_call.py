"""Device time of one selection in the decode step (one layer of one step):
from the end of ``paged_index_scores`` to where the attention over the
chosen rows begins (``_index_spans.routines``: the first gather of chosen
rows, or the kernel ``sparse_gqa_attend`` where nothing gathers them),
inside runs of the segment program.

The selection is not one kernel (the staged rows' scores, the bisection's
counting passes, the prefix sums and the products that place the chosen
columns, the flat row ids: XLA's operations, under names of the
compiler's), so it is read as what lies between the scores and the first
instruction that touches a chosen row."""

from benchmarks.layer_metrics import _index_spans as ix


def read(run: dict):
    seconds = ix.routines(run)
    return None if seconds is None else 1e6 * seconds[0]
