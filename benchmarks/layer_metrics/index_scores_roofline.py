"""``paged_index_scores``' share of its roofline in the decode step: the
kernel's device time per call (by name, in the segment program) against the
operations and bytes of the index keys the window's mean step reads
(``rows_scored`` and ``lanes`` of the drained segments), each key counted
once at the 64 numbers it has."""

from benchmarks.layer_metrics import _index_spans as ix
from benchmarks.roofline import bound, index_scores


def read(run: dict):
    dims, sums = run["dims"], ix.per_step(run)
    if sums is None or not run["trace"]:
        return None
    seconds = ix.kernel_call(run, ix.SCORES)
    if seconds is None:
        return None
    scored, _, lanes = sums
    return bound.share(
        index_scores.flops(scored, dims.index_heads, dims.index_dim),
        index_scores.bytes_moved(scored, lanes, dims.index_heads,
                                 dims.index_dim),
        seconds, run["peaks"])
