"""90th percentile, over the window's admitted requests, of enqueue -> the
drain that brought the request's first token to the host (``first_token`` of
its ``serve/request`` span); a request that got none counts as slower than
any, up to the window's length plus the mix's drain limit."""

import math

from benchmarks.layer_metrics import _loop_spans as ls


def read(run: dict):
    w = ls.window(run)
    if w is None or not w.requests:
        return None
    waits = sorted(math.inf if a["first_token"] is None
                   else a["first_token"] for a in w.requests)
    p90 = waits[math.ceil(0.9 * len(waits)) - 1]
    limit = w.hi - w.lo + float(run["cell"]["traffic"]["drain_limit_s"])
    return 1e3 * min(p90, limit)
