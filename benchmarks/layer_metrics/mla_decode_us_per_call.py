"""Device time of one call of ``paged_mla_decode``, found in the trace by
its name; every event of that name counts as one call (one layer of one
decode step); the mean over the traced seconds."""

from benchmarks.harness import common
from benchmarks.layer_metrics import _named_kernels as nk

KERNEL = nk.kernel_pattern("paged_mla_decode")


def read(run: dict):
    calls, seconds = nk.calls(run, KERNEL)
    if not calls:
        return None
    common.say(phase="mla_decode_us_per_call", calls=calls,
               kernel_s=seconds)
    return 1e6 * seconds / calls
