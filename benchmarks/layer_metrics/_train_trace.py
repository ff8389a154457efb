"""What the training readers share."""

from benchmarks.trace import reduce as tr

def steps_traced(run: dict) -> int:
    return int(run["cell"]["traffic"]["trace_steps"])


def kernel_seconds_per_call(run: dict, is_kernel, calls_per_layer: int):
    """A kernel's device seconds per call: its events' time on one chip
    over the traced steps, the layers and its calls a layer and step."""
    trace = run["trace"]
    if not trace:
        return None
    sec = tr.pallas_seconds(trace["by_op"], is_kernel)
    calls = steps_traced(run) * run["dims"].layers * calls_per_layer
    return sec / calls if sec else None
