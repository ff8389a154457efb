"""What a prefill chunk costs a request in wall time: the mean, over the
window's requests, of (first token at the drain - admission) / chunks
computed, from the ``serve/request`` spans.  The loop gives a lane one chunk
per iteration, so this is an iteration and not a chunk's device time."""

import statistics

from benchmarks.layer_metrics import _loop_spans as ls


def read(run: dict):
    w = ls.window(run)
    if w is None:
        return None
    per = [(a["first_token"] - a["admit"]) / a["chunks"]
           for a in w.requests
           if a["chunks"] and a["first_token"] is not None]
    return 1e3 * statistics.fmean(per) if per else None
