"""Device time of one call of the paged decode kernel, found in the trace BY
ITS NAME: the ``name=`` of the ``pallas_call`` in ``ops/flash_decode.py`` is
the name of its HLO instruction, and an ``XLA Ops`` event is named by its
whole instruction (``%paged_flash_decode.7 = ...``).  Every event of that
name counts as one call; the mean over the traced seconds."""

import re

from benchmarks.harness import common, tracing
from benchmarks.trace import reduce as tr

KERNEL = re.compile(r"^%?paged_flash_decode[.\d]* = ")


def read(run: dict):
    if not run["trace"]:
        return None
    path = tr.find_xplane(str(tracing.TRACE_ROOT / run["cell"]["name"]))
    if not path:
        return None
    calls, seconds = 0, 0.0
    for dev in tr.load(path)["devices"].values():
        for e in dev["ops"]:
            if KERNEL.match(e.name):
                calls += 1
                seconds += e.end - e.start
    if not calls:
        return None
    common.say(phase="paged_decode_us_per_call", calls=calls,
               kernel_s=seconds)
    return 1e6 * seconds / calls
