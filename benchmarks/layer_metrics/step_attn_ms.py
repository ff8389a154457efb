"""Device milliseconds of a decode step under the routine scopes ``attn/*``
(projections, cache writes, an indexer's routines, the gathers, the
attention itself): self-time inside the joined segment runs over their
``steps_run`` (``_scopes``)."""

from benchmarks.layer_metrics import _scopes


def read(run: dict):
    return _scopes.under(run, "attn/")
