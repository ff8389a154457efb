"""The flash forward kernel's share of its roofline in the train step.
With ``remat`` the kernel runs twice a layer and step (forward, and again
when the backward pass recomputes the block): both calls are timed, each
against one call's operations."""

from benchmarks.layer_metrics import _train_trace as tt
from benchmarks.roofline import bound, flash_fwd


def read(run: dict):
    remat = bool(run["cell"]["config"]["program"]["options"]["remat"])
    sec = tt.kernel_seconds_per_call(run, flash_fwd.is_kernel,
                                     2 if remat else 1)
    if sec is None:
        return None
    d = run["dims"]
    rows = run["rows_per_chip"]
    return bound.share(
        flash_fwd.flops(rows, run["seq"], d.heads, d.head_dim),
        flash_fwd.bytes_moved(rows, run["seq"], d.heads, d.kv_heads,
                              d.head_dim),
        sec, run["peaks"])
