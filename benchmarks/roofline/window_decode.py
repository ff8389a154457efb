"""Operations and bytes one call of ``ops/flash_decode.paged_flash_decode``
with a window (the kernel ``paged_window_decode``: one sliding-window layer,
one decode step) needs, from shapes and the rows its lanes' windows hold.

Grouped-query attention of ``heads`` query rows against the ``kv_heads``
key and value rows of every cache row inside a lane's window: a row the
window has left is neither read nor computed, so the count is over
``min(length, window)`` a lane and not over the lengths."""


def flops(window_rows: float, heads: int, head_dim: int) -> float:
    """Scores and values over every windowed row of every lane
    (``window_rows`` is the sum over the lanes of ``min(length, window)``):
    ``2 x heads x head_dim`` each."""
    return 4.0 * heads * head_dim * window_rows


def bytes_moved(window_rows: float, lanes: float, heads: int, kv_heads: int,
                head_dim: int, itemsize: int = 2) -> float:
    """A key row and a value row of ``kv_heads x head_dim`` for every
    windowed row, once (the query heads of a group share them); the queries
    in and the output out."""
    rows = window_rows * 2 * kv_heads * head_dim * itemsize
    qo = lanes * 2 * heads * head_dim * itemsize
    return float(rows + qo)
