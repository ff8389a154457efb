"""The chunk form's share of its roofline: ``bound.least_seconds`` over the
RECURRENT form's operations and bytes for a chunk's tokens
(``roofline/delta_rule.py``: the same work whatever implements it) against
the device time of one call (``delta_chunk_ms_per_call``).  A chunk counts
at its width (``prefill_chunk``): the padded rows of a prompt's last chunk
are computed like the rest."""

from benchmarks.layer_metrics import _delta_spans as ds
from benchmarks.roofline import bound, delta_rule


def read(run: dict):
    found = ds.chunk_calls(run)
    if not found or not found[1] or not run.get("peaks"):
        return None
    calls, seconds = found
    _, heads, dk, dv = ds.linear_dims(run)
    tokens = run["options"]["prefill_chunk"]
    return bound.share(delta_rule.flops(tokens, heads, dk, dv),
                       delta_rule.chunk_bytes(tokens, heads, dk, dv),
                       seconds / calls, run["peaks"])
