"""Operations and bytes one call of ``ops/flash_decode.paged_mla_decode``
needs (one layer, one decode step), from shapes alone.

The absorbed decode of latent attention is multi-query attention of
``heads`` query rows against ONE cached row a token: the scores contract
the row's ``latent + rope`` live columns, and the values are its first
``latent`` columns again, so the row is read once."""


def flops(context_tokens: float, heads: int, latent: int, rope: int) -> float:
    """Scores and values over every live row of every lane
    (``context_tokens`` is the sum of the lanes' context lengths): ``2 x
    heads x (latent + rope)`` and ``2 x heads x latent`` a row.  The zero
    padding of a stored row is not counted as work."""
    return 2.0 * heads * (2 * latent + rope) * context_tokens


def bytes_moved(context_tokens: float, lanes: float, heads: int,
                row_width: int, latent: int, itemsize: int = 2) -> float:
    """Every live row once at its STORED width (padding included: it is
    read), the absorbed queries in, the attended latent out."""
    rows = context_tokens * row_width * itemsize
    qo = lanes * heads * (row_width + latent) * itemsize
    return float(rows + qo)
