"""Device time of one call of ``paged_window_decode`` (the paged decode
kernel of a sliding-window layer), found in the trace by its name; every
event of that name counts as one call (one window layer of one decode
step); the mean over the traced seconds.  ``None`` on a program that has no
kernel of that name."""

from benchmarks.harness import common
from benchmarks.layer_metrics import _named_kernels as nk

KERNEL = nk.kernel_pattern("paged_window_decode")


def read(run: dict):
    calls, seconds = nk.calls(run, KERNEL)
    if not calls:
        return None
    common.say(phase="window_decode_us_per_call", calls=calls,
               kernel_s=seconds)
    return 1e6 * seconds / calls
