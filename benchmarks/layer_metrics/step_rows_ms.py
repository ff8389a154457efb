"""Device milliseconds of a decode step under the routine scope
``attn/rows`` (the gathers of the chosen rows of each pool, the staged
rows patched in): self-time inside the joined segment runs over their
``steps_run`` (``_scopes``).  What counts is what the program scoped so,
whatever its buffers' shapes; 0 where a kernel reads the rows itself."""

from benchmarks.layer_metrics import _scopes


def read(run: dict):
    return _scopes.under(run, "attn/rows")
