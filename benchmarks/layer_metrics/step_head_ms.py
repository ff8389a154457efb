"""Device milliseconds of a decode step under the routine scope ``head``
(final norm, ``lm_head``, sampling, the emit buffer's write): self-time
inside the joined segment runs over their ``steps_run`` (``_scopes``)."""

from benchmarks.layer_metrics import _scopes


def read(run: dict):
    return _scopes.under(run, "head")
