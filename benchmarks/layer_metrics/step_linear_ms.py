"""Device milliseconds of a decode step under the linear-attention layers'
routine scopes (``linear_attn``: projections, the convolution, the gates,
the norms; ``delta_step``, nested in it: the recurrence's kernel):
self-time inside the joined segment runs over their ``steps_run``
(``_scopes``).  With ``step_attn_ms`` (the full layers), ``step_mlp_ms``,
``step_head_ms`` and ``step_other_ms`` it sums to the joined step."""

from benchmarks.layer_metrics import _scopes


def read(run: dict):
    split = _scopes.per_step(run)
    if split is None or not any(k in split for k in (
            "linear_attn", "delta_step", "delta_chunk")):
        return None
    return _scopes.under(run, "linear_attn", "delta_step", "delta_chunk")
