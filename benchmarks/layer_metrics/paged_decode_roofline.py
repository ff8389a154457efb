"""``paged_flash_decode``'s share of its roofline: the device time of ONE
event of the kernel (found by name and counted, as
``paged_decode_us_per_call`` does: a run of the segment program that the
trace's edge cut brings the events it holds and no more) against the bytes
and operations that the window's mean decode step needs (lanes and context
lengths from the loop's events)."""

from benchmarks.layer_metrics import _named_kernels as nk
from benchmarks.layer_metrics import _serve_trace as st
from benchmarks.layer_metrics.paged_decode_us_per_call import KERNEL
from benchmarks.roofline import bound, paged_decode


def read(run: dict):
    dims = run["dims"]
    if not run["trace"] or not run["events"]:
        return None
    calls, seconds = nk.calls(run, KERNEL)
    lanes, ctx = st.decode_context(run)
    if not (calls and ctx):
        return None
    return bound.share(
        paged_decode.flops(ctx, dims.heads, dims.head_dim),
        paged_decode.bytes_moved(ctx, lanes, dims.heads, dims.kv_heads,
                                 dims.head_dim),
        seconds / calls, run["peaks"])
