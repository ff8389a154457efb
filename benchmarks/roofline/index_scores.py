"""Operations and bytes one call of ``ops/flash_decode.paged_index_scores``
needs in a decode step (one layer), from shapes alone.

A learned indexer scores every cached row of every lane: ``heads`` index
queries of ``dim`` against ONE index key a row, a ReLU and a weighted sum
over the heads.  The keys below each lane's length are read once, whatever
implements the walk, at the ``dim`` numbers a key HAS (the cache stores a
64-wide key in a 128-lane row: the zeros a walk also reads are not
work)."""


def flops(rows_scored: float, heads: int, dim: int) -> float:
    """``2 x heads x dim`` a scored row for the products and ``2 x heads``
    for the weighted sum (``rows_scored`` is the sum of the lanes'
    lengths)."""
    return 2.0 * heads * (dim + 1) * rows_scored


def bytes_moved(rows_scored: float, lanes: float, heads: int, dim: int,
                itemsize: int = 2) -> float:
    """Every index key below a length once, the float32 score of each out,
    the lanes' index queries and float32 head weights in."""
    keys = rows_scored * dim * itemsize
    scores = rows_scored * 4
    queries = lanes * heads * (dim * itemsize + 4)
    return float(keys + scores + queries)
