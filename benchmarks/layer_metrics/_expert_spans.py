"""The expert layer's sums over the window's drained segments
(``serve/segment_drain`` carries ``expert_tokens`` and ``expert_tokens_max``
where the model has expert layers): ``(tokens, busiest, decode steps)``, or
``None`` on a program or a model without them."""

from benchmarks.layer_metrics import _loop_spans as ls


def sums(run: dict):
    w = ls.window(run)
    if w is None:
        return None
    segs = [a for a in ls.drained(w) if "expert_tokens" in a]
    steps = sum(a["steps_run"] for a in segs)
    if not steps:
        return None
    return (sum(a["expert_tokens"] for a in segs),
            sum(a["expert_tokens_max"] for a in segs), steps)


def expert_layers(run: dict) -> int:
    return run["dims"].layers - run["dims"].first_k_dense
