"""Model FLOPs per trained token, from shapes: what the forward and the
backward pass require, recomputation not counted."""


def train_flops_per_token(matmul_params: int, layers: int, seq: int,
                          heads: int, head_dim: int) -> float:
    """``6 x`` the parameters a token multiplies by (2 forward, 4
    backward), plus causal attention: forward ``4 H D (seq + 1) / 2`` a
    token and layer, backward two and a half times that."""
    attention = 3.5 * 4.0 * heads * head_dim * (seq + 1) / 2 * layers
    return 6.0 * matmul_params + attention
