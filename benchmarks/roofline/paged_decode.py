"""Operations and bytes one call of ``ops/flash_decode.paged_flash_decode``
needs (one layer, one decode step), from shapes alone."""

def is_kernel(op: dict) -> bool:
    """The paged decode kernel in a trace: a Pallas call with ONE output
    (``[lanes * kv_heads, group, head_dim]``) and six operands (meta, q,
    the two pools, the two side buffers)."""
    return len(op["outputs"]) == 1 and op["operands"] == 6


def flops(context_tokens: int, heads: int, head_dim: int) -> float:
    """QK^T and PV over every live position of every lane:
    ``context_tokens`` is the sum of the lanes' context lengths."""
    return 4.0 * heads * head_dim * context_tokens


def bytes_moved(context_tokens: int, lanes: int, heads: int, kv_heads: int,
                head_dim: int, itemsize: int = 2) -> float:
    """K and V of every live position once, the queries in, the output
    out."""
    kv = 2 * context_tokens * kv_heads * head_dim * itemsize
    qo = 2 * lanes * heads * head_dim * itemsize
    return float(kv + qo)
