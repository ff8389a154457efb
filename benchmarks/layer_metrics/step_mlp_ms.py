"""Device milliseconds of a decode step under the routine scopes ``mlp/*``
(the dense MLP, or router, experts and shared expert): self-time inside the
joined segment runs over their ``steps_run`` (``_scopes``)."""

from benchmarks.layer_metrics import _scopes


def read(run: dict):
    return _scopes.under(run, "mlp/")
