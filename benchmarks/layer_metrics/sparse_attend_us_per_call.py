"""Device time of one attention over the chosen rows in the decode step
(one layer of one step), inside runs of the segment program: the WHOLE
routine ``ops/flash_decode.sparse_gqa_attend``, from the first instruction
that yields a buffer of gathered rows (this version gathers K's and V's
with XLA) to the end of the kernel of that name
(``_index_spans.routines``).  A version whose kernel reads the rows itself
is timed as the kernel alone, so taking the gathers off the path shows
here."""

from benchmarks.layer_metrics import _index_spans as ix


def read(run: dict):
    seconds = ix.routines(run)
    return None if seconds is None else 1e6 * seconds[1]
