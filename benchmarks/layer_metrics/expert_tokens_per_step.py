"""Tokens the held experts of ONE layer were given a decode step, over the
window's drained segments: ``expert_tokens`` over ``steps_run`` x expert
layers.  ``lanes x top_k x held / num_experts`` under an even router."""

from benchmarks.layer_metrics import _expert_spans as es


def read(run: dict):
    sums = es.sums(run)
    if sums is None:
        return None
    return sums[0] / (sums[2] * es.expert_layers(run))
