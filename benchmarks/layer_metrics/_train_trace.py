"""What the training readers share."""

from benchmarks.trace import reduce as tr


def steps_traced(run: dict) -> int:
    return int(run["cell"]["traffic"]["trace_steps"])


def kernel_seconds_per_layer(run: dict, is_kernel):
    """The device seconds a layer of a step spends in the kernels
    ``is_kernel`` accepts: their events' time on one chip over the traced
    steps and the layers."""
    trace = run["trace"]
    if not trace:
        return None
    sec = tr.pallas_seconds(trace["by_op"], is_kernel)
    return sec / (steps_traced(run) * run["dims"].layers) if sec else None
