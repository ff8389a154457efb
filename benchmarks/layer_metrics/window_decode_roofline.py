"""``paged_window_decode``'s share of its roofline: the kernel's device time
per call (found by name) against the operations and bytes of the rows inside
the lanes' windows at the window's mean decode step (``rows_window_live`` of
the loop's ``serve/segment_drain`` spans: ``min(length, window)`` a lane)."""

from benchmarks.layer_metrics import _named_kernels as nk
from benchmarks.layer_metrics import _window_spans as ws
from benchmarks.layer_metrics.window_decode_us_per_call import KERNEL
from benchmarks.roofline import bound, window_decode


def read(run: dict):
    dims, sums = run["dims"], ws.sums(run)
    if sums is None or not run["trace"]:
        return None
    calls, seconds = nk.calls(run, KERNEL)
    if not calls:
        return None
    _, live, _, lane_steps, steps = sums
    return bound.share(
        window_decode.flops(live / steps, dims.heads, dims.head_dim),
        window_decode.bytes_moved(live / steps, lane_steps / steps,
                                  dims.heads, dims.kv_heads, dims.head_dim),
        seconds / calls, run["peaks"])
