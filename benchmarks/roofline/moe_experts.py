"""Operations and bytes one call of the gated grouped expert product
(``ops/moe_dispatch.grouped_gated_mlp``: the kernels ``moe_experts_gate_up``
and ``moe_experts_down`` together) needs, from shapes and the number of
tokens the held experts were given."""


def flops(tokens: float, d_model: int, d_ff: int) -> float:
    """Three products a routed token: gate, up (``d x f`` each) and down
    (``f x d``)."""
    return 3.0 * 2.0 * tokens * d_model * d_ff


def experts_touched(tokens: float, held: int) -> float:
    """How many of the ``held`` experts an EVEN router gives at least one
    of ``tokens`` assignments (an expert no token chose is not read):
    ``held x (1 - (1 - 1/held) ^ tokens)``, never more than ``held`` or
    ``tokens``."""
    if held <= 0 or tokens <= 0:
        return 0.0
    return min(held * (1.0 - (1.0 - 1.0 / held) ** tokens), tokens)


def bytes_moved(tokens: float, held: int, d_model: int, d_ff: int,
                itemsize: int = 2) -> float:
    """The three matrices of every expert touched, once; a token's row in
    and out of both kernels (``d`` in, ``f`` out, ``f`` in, ``d`` out)."""
    weights = experts_touched(tokens, held) * 3 * d_model * d_ff * itemsize
    rows = tokens * 2 * (d_model + d_ff) * itemsize
    return float(weights + rows)
