"""Device milliseconds of a decode step that NO routine scope covers: the
joined step (``segment_ms_per_step_joined``) less the scopes' sum, so that
``step_attn_ms + step_mlp_ms + step_head_ms + step_other_ms`` is the step by
construction.  Embedding, residual adds, the block norms XLA left alone, the
``while_loop``'s bookkeeping, the gaps between operations; a routine whose
scope fell off shows here."""

from benchmarks.layer_metrics import _scopes


def read(run: dict):
    split = _scopes.per_step(run)
    return None if split is None else split["other"]
