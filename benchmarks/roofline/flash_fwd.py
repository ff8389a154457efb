"""Operations and bytes of the causal flash-attention forward kernel over
whole sequences (``ops/flash_attention._flash_forward``, training)."""

def is_kernel(op: dict) -> bool:
    """The forward kernel in a trace: a Pallas call that produces the
    output and the float32 log-sum-exp from four operands (offsets, q, k,
    v)."""
    return (len(op["outputs"]) == 2 and op["outputs"][1].startswith("f32")
            and op["operands"] == 4)


def flops(rows: int, seq: int, heads: int, head_dim: int) -> float:
    """QK^T and PV over the causal half: ``seq (seq + 1) / 2`` pairs."""
    return 4.0 * heads * head_dim * rows * seq * (seq + 1) / 2


def bytes_moved(rows: int, seq: int, heads: int, kv_heads: int,
                head_dim: int, itemsize: int = 2) -> float:
    """Q in, O out, K and V in once, the log-sum-exp out in float32."""
    qo = 2 * rows * seq * heads * head_dim * itemsize
    kv = 2 * rows * seq * kv_heads * head_dim * itemsize
    lse = rows * seq * heads * 4
    return float(qo + kv + lse)
