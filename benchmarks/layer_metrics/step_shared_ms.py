"""Device milliseconds of a decode step under the routine scope
``mlp/shared`` (the shared experts' gated MLP and, where they are averaged,
the ``1 / n_shared``): self-time inside the joined segment runs over their
``steps_run`` (``_scopes``).  A part of ``step_mlp_ms``, read apart from the
held experts' (``step_experts_ms``).  ``None`` on a program whose expert
layer has no shared expert, as on one without scopes."""

from benchmarks.layer_metrics import _scopes

SCOPE = "mlp/shared"


def read(run: dict):
    split = _scopes.per_step(run)
    if split is None or SCOPE not in split:
        return None
    return _scopes.under(run, SCOPE)
