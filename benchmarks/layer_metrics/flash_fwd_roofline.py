"""The flash forward kernel's share of its roofline in the train step: the
device time of ONE event of the kernel, the mean over the events the trace
holds (found by name and counted: with ``remat`` a step calls it twice a
layer, forward and again when the backward pass recomputes the block; a
step that keeps the output calls it once), against one call's operations
and bytes."""

from benchmarks.harness import common
from benchmarks.layer_metrics import _named_kernels as nk
from benchmarks.layer_metrics import _train_trace as tt
from benchmarks.roofline import bound, flash_fwd

KERNEL = nk.kernel_pattern(*flash_fwd.NAMES)


def read(run: dict):
    events, seconds = nk.calls(run, KERNEL)
    if not events:
        return None
    common.say(phase="flash_fwd_roofline", events=events,
               events_per_traced_step=events / tt.steps_traced(run),
               kernel_s=seconds)
    d = run["dims"]
    rows = run["rows_per_chip"]
    return bound.share(
        flash_fwd.flops(rows, run["seq"], d.heads, d.head_dim),
        flash_fwd.bytes_moved(rows, run["seq"], d.heads, d.kv_heads,
                              d.head_dim),
        seconds / events, run["peaks"])
