"""``_flash_forward``'s share of its roofline in chunked prefill: the
kernel's device time per call in the trace against what the window's mean
chunk needs (offsets and widths from the loop's ``prefill_chunk`` events)."""

from benchmarks.layer_metrics import _serve_trace as st
from benchmarks.roofline import bound, flash_prefill
from benchmarks.trace import reduce as tr


def read(run: dict):
    trace, dims = run["trace"], run["dims"]
    chunks = [e for e in run["events"] if e["kind"] == "prefill_chunk"]
    if not trace or not chunks:
        return None
    sec = tr.pallas_seconds(trace["by_op"], flash_prefill.is_kernel)
    runs = st.module_runs(trace, st.PREFILL)
    if not (sec and runs):
        return None
    args = (dims.heads, dims.head_dim)
    flops = sum(flash_prefill.flops(e["off"], e["width"], *args)
                for e in chunks) / len(chunks)
    nbytes = sum(flash_prefill.bytes_moved(
        e["off"], e["width"], dims.heads, dims.kv_heads, dims.head_dim)
        for e in chunks) / len(chunks)
    return bound.share(flops, nbytes, sec / (runs * dims.layers),
                       run["peaks"])
