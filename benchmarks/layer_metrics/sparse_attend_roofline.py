"""``sparse_gqa_attend``'s share of its roofline in the decode step: the
WHOLE routine's device time per call (the gathers of the chosen rows, the
staged rows' patch and the kernel, ``sparse_attend_us_per_call``) against
the operations and bytes of the rows the window's mean step attends
(``rows_selected`` and ``lanes`` of the drained segments), each chosen row
of each pool counted once at its stored width: a version that gathers them
first reads as a third or less."""

from benchmarks.layer_metrics import _index_spans as ix
from benchmarks.roofline import bound, sparse_attend


def read(run: dict):
    dims, sums = run["dims"], ix.per_step(run)
    if sums is None or not run["trace"]:
        return None
    seconds = ix.routines(run)
    if seconds is None:
        return None
    _, selected, lanes = sums
    return bound.share(
        sparse_attend.flops(selected, dims.heads, dims.head_dim),
        sparse_attend.bytes_moved(selected, lanes, dims.heads,
                                  dims.kv_heads, dims.head_dim),
        seconds[1], run["peaks"])
