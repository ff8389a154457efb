"""``paged_mla_decode``'s share of its roofline: the kernel's device time
per call (found by name) against the operations and bytes that the window's
mean decode step needs (lanes and live rows from the loop's events)."""

from benchmarks.layer_metrics import _named_kernels as nk
from benchmarks.layer_metrics import _serve_trace as st
from benchmarks.layer_metrics.mla_decode_us_per_call import KERNEL
from benchmarks.roofline import bound, mla_decode

ROW_WIDTH_MULTIPLE = 128  # a stored row is padded to whole lanes


def read(run: dict):
    dims = run["dims"]
    if not run["trace"] or not run["events"]:
        return None
    calls, seconds = nk.calls(run, KERNEL)
    if not calls:
        return None
    lanes, ctx = st.decode_context(run)
    if not ctx:
        return None
    live = dims.kv_lora + dims.rope
    width = -(-live // ROW_WIDTH_MULTIPLE) * ROW_WIDTH_MULTIPLE
    return bound.share(
        mla_decode.flops(ctx, dims.heads, dims.kv_lora, dims.rope),
        mla_decode.bytes_moved(ctx, lanes, dims.heads, width, dims.kv_lora),
        seconds / calls, run["peaks"])
