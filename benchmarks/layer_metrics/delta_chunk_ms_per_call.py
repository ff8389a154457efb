"""Device milliseconds of the gated delta rule's chunk form for ONE linear
layer of ONE prefill chunk: the self-time under the routine scope
``delta_chunk`` inside the whole runs of the prefill-chunk program, over
those runs times the linear layers (``_delta_spans.chunk_calls``)."""

from benchmarks.layer_metrics import _delta_spans as ds


def read(run: dict):
    found = ds.chunk_calls(run)
    if not found or not found[1]:
        return None
    calls, seconds = found
    return 1e3 * seconds / calls
