"""Operations and bytes the gated delta rule needs (one linear-attention
layer), from shapes alone: of one decode step
(``ops/delta_rule.gated_delta_step``, the kernel ``delta_step``) and of one
prefill chunk (``gated_delta_chunk``, the scope ``delta_chunk``).

Both count the RECURRENT form, which is the least any implementation has to
do: a head's ``[dk, dv]`` float32 state is decayed (1 operation an
element), read against the key (2), written by the outer product (2) and
read against the query (2): ``7 x dk x dv`` a head and token.  A chunkwise
implementation does more arithmetic (its products inside a sub-block, a
triangular solve) to do it on the matrix unit; that is its own cost and not
counted as work."""

STATE_ITEMSIZE = 4   # the state is float32


def flops(tokens: float, heads: int, dk: int, dv: int) -> float:
    """``tokens``: lanes of a step, or the tokens of a chunk."""
    return 7.0 * dk * dv * heads * tokens


def step_bytes(lanes: float, heads: int, dk: int, dv: int) -> float:
    """A lane's state read once and written once; its q and k (``dk``
    each), v and o (``dv`` each) in float32, and alpha and beta, a head."""
    state = 2 * lanes * heads * dk * dv * STATE_ITEMSIZE
    vectors = lanes * heads * (2 * dk + 2 * dv + 2) * 4
    return float(state + vectors)


def chunk_bytes(tokens: float, heads: int, dk: int, dv: int) -> float:
    """q, k, v, the two gates and the output of every token in float32;
    the carried state in and out, once a chunk."""
    vectors = tokens * heads * (2 * dk + 2 * dv + 2) * 4
    state = 2 * heads * dk * dv * STATE_ITEMSIZE
    return float(vectors + state)
