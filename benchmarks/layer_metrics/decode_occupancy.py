"""Percent of the lane-steps the window's segments ran that gave a request
a token: sum(``tokens`` - ``first_tokens``) over ``num_slots`` x
sum(``steps_run``) of the ``serve/segment_drain`` spans (a first token comes
from the admission, not from a decode step)."""

from benchmarks.layer_metrics import _loop_spans as ls


def read(run: dict):
    w = ls.window(run)
    if w is None:
        return None
    segs = ls.drained(w)
    steps = sum(a["steps_run"] for a in segs)
    if not steps:
        return None
    decoded = sum(a["tokens"] - a["first_tokens"] for a in segs)
    return 100.0 * decoded / (run["options"]["num_slots"] * steps)
