"""``flash_fwd``'s share of its roofline in chunked prefill: the device time
of ONE event of the kernel (found by name and counted: one a layer of a
dense chunk; a sparse chunk's masked pass is ``sparse_gqa_prefill``, a
kernel of its own) against what the window's mean chunk needs (offsets and
widths from the loop's ``prefill_chunk`` events)."""

from benchmarks.layer_metrics import _named_kernels as nk
from benchmarks.layer_metrics.flash_fwd_roofline import KERNEL
from benchmarks.roofline import bound, flash_prefill


def read(run: dict):
    dims = run["dims"]
    chunks = [e for e in run["events"] if e["kind"] == "prefill_chunk"]
    if not run["trace"] or not chunks:
        return None
    calls, seconds = nk.calls(run, KERNEL)
    if not calls:
        return None
    args = (dims.heads, dims.head_dim)
    flops = sum(flash_prefill.flops(e["off"], e["width"], *args)
                for e in chunks) / len(chunks)
    nbytes = sum(flash_prefill.bytes_moved(
        e["off"], e["width"], dims.heads, dims.kv_heads, dims.head_dim)
        for e in chunks) / len(chunks)
    return bound.share(flops, nbytes, seconds / calls, run["peaks"])
