"""Operations and bytes one call of ``ops/flash_decode.sparse_gqa_attend``
needs in a decode step (one layer), from shapes alone.

Grouped-query attention over the CHOSEN rows of each lane.  The chosen rows
of both pools are counted once at their stored width, whatever implements
the reads: a version that gathers them first (writes them, reads them
again) spends three times these bytes and reads as a third; a masked walk
of every live row reads the lanes' lengths and is held to the chosen rows
all the same."""

from benchmarks.roofline import paged_decode


def flops(rows_selected: float, heads: int, head_dim: int) -> float:
    """``paged_flash_decode``'s arithmetic a row, over the chosen rows alone
    (``rows_selected`` is the sum of ``min(length, index_topk)``)."""
    return paged_decode.flops(rows_selected, heads, head_dim)


def bytes_moved(rows_selected: float, lanes: float, heads: int,
                kv_heads: int, head_dim: int, itemsize: int = 2) -> float:
    """K and V of every chosen row once, its row id in (int32), the queries
    in, the output out."""
    return (paged_decode.bytes_moved(rows_selected, lanes, heads, kv_heads,
                                     head_dim, itemsize)
            + rows_selected * 4)
