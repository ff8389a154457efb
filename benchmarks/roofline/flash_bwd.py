"""Operations and bytes of the two flash-attention backward kernels taken
together (dQ, then dK/dV; ``ops/flash_attention.flash_block_grads``)."""

NAMES = ("flash_bwd_dq", "flash_bwd_dkv")


def is_kernel(op: dict) -> bool:
    """The two backward kernels in a trace, by their instructions' names
    (``trace/reduce.parse_op``'s): dQ, and dK with dV."""
    return op["name"] in NAMES


def flops(rows: int, seq: int, heads: int, head_dim: int) -> float:
    """What the gradient needs over the causal half: S = QK^T, dP = dO V^T,
    dQ = dS K, dK = dS^T Q, dV = P^T dO: five matmuls of ``2 D`` each.
    (Each of the two kernels recomputes S and dP; recomputation is not
    counted.)"""
    return 10.0 * heads * head_dim * rows * seq * (seq + 1) / 2


def bytes_moved(rows: int, seq: int, heads: int, kv_heads: int,
                head_dim: int, itemsize: int = 2) -> float:
    """Q, O, dO in and dQ out at the query heads; K, V in and dK, dV out
    at the K/V heads; log-sum-exp in (float32)."""
    q_side = 4 * rows * seq * heads * head_dim * itemsize
    kv_side = 4 * rows * seq * kv_heads * head_dim * itemsize
    lse = rows * seq * heads * 4
    return float(q_side + kv_side + lse)
