"""Operations and bytes of the causal flash-attention forward kernel over
whole sequences (``ops/flash_attention._flash_forward``, training)."""

# the ``name=`` of the ``pallas_call``, which names its HLO instruction: what
# the call takes and what it keeps may change, its name says what it is (in
# serving the dense prefill chunk calls the same kernel under the same name)
NAMES = ("flash_fwd",)


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
