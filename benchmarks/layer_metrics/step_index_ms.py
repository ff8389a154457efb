"""Device milliseconds of a decode step under the routine scope
``attn/index`` (an indexer's scores, the exact top-k, the chosen columns'
positions): self-time inside the joined segment runs over their
``steps_run`` (``_scopes``)."""

from benchmarks.layer_metrics import _scopes


def read(run: dict):
    return _scopes.under(run, "attn/index")
