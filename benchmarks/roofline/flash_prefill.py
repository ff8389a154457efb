"""Operations and bytes one call of ``ops/flash_attention._flash_forward``
needs at a query offset (one layer, one prefill chunk of one lane)."""


def flops(offset: int, width: int, heads: int, head_dim: int) -> float:
    """Row ``r`` of the chunk attends ``offset + r + 1`` keys (causal)."""
    keys = width * offset + width * (width + 1) / 2
    return 4.0 * heads * head_dim * keys


def bytes_moved(offset: int, width: int, heads: int, kv_heads: int,
                head_dim: int, itemsize: int = 2) -> float:
    """K and V up to the chunk's end once, queries in, output out."""
    kv = 2 * (offset + width) * kv_heads * head_dim * itemsize
    qo = 2 * width * heads * head_dim * itemsize
    return float(kv + qo)
