"""The busiest (layer, held expert)'s share of the tokens the held experts
were given, in percent, over the window's drained segments: the straggler
a grouped product waits for.  ``100 / (expert layers x held)`` when even."""

from benchmarks.layer_metrics import _expert_spans as es


def read(run: dict):
    sums = es.sums(run)
    if sums is None or not sums[0]:
        return None
    return 100.0 * sums[1] / sums[0]
