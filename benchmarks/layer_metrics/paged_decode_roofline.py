"""``paged_flash_decode``'s share of its roofline: the kernel's device time
per call in the trace against the bytes and operations that the window's
mean decode step needs (lanes and context lengths from the loop's events)."""

from benchmarks.layer_metrics import _serve_trace as st
from benchmarks.roofline import bound, paged_decode
from benchmarks.trace import reduce as tr


def read(run: dict):
    trace, dims = run["trace"], run["dims"]
    if not trace or not run["events"]:
        return None
    sec = tr.pallas_seconds(trace["by_op"], paged_decode.is_kernel)
    runs = st.module_runs(trace, st.SEGMENT)
    lanes, ctx = st.decode_context(run)
    if not (sec and runs and ctx):
        return None
    calls = runs * run["options"]["steps_per_sync"] * dims.layers
    return bound.share(
        paged_decode.flops(ctx, dims.heads, dims.head_dim),
        paged_decode.bytes_moved(ctx, lanes, dims.heads, dims.kv_heads,
                                 dims.head_dim),
        sec / calls, run["peaks"])
