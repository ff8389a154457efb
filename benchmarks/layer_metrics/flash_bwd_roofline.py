"""The two flash backward kernels' (dQ; dK/dV) share of their roofline,
taken together: their device time a layer and step against the operations
the gradient needs."""

from benchmarks.layer_metrics import _train_trace as tt
from benchmarks.roofline import bound, flash_bwd


def read(run: dict):
    sec = tt.kernel_seconds_per_layer(run, flash_bwd.is_kernel)
    if sec is None:
        return None
    d = run["dims"]
    rows = run["rows_per_chip"]
    return bound.share(
        flash_bwd.flops(rows, run["seq"], d.heads, d.head_dim),
        flash_bwd.bytes_moved(rows, run["seq"], d.heads, d.kv_heads,
                              d.head_dim),
        sec, run["peaks"])
