"""Device milliseconds of a decode step under the routine scope
``mlp/experts`` (the gated grouped products over the held experts and the
combine): self-time inside the joined segment runs over their ``steps_run``
(``_scopes``).  A part of ``step_mlp_ms``, read apart from the shared
experts' (``step_shared_ms``) and the router's (``mlp/route``).  ``None``
on a program without expert layers, as on one without scopes."""

from benchmarks.layer_metrics import _scopes

SCOPE = "mlp/experts"


def read(run: dict):
    split = _scopes.per_step(run)
    if split is None or SCOPE not in split:
        return None
    return _scopes.under(run, SCOPE)
