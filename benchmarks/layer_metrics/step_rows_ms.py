"""Device milliseconds of a decode step under the routine scope
``attn/rows`` (the gathers of the chosen rows of each pool, the staged
rows patched in): self-time inside the joined segment runs over their
``steps_run`` (``_scopes``)."""

from benchmarks.layer_metrics import _scopes


def read(run: dict):
    return _scopes.under(run, "attn/rows")
