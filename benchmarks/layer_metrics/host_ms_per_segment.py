"""The host's own time per drained segment: the window's wall less the
time blocked in ``serve/segment_fetch``, over the segments drained in it.
Unlike ``host_wait_share`` it does not fall when the device gets faster.
Prints the window's seconds by loop phase (a ``serve/admit`` lies inside a
``serve/admit_poll``) and how much of the wall the phases cover together."""

from benchmarks.harness import common
from benchmarks.layer_metrics import _loop_spans as ls
from benchmarks.trace import reduce as tr


def read(run: dict):
    w = ls.window(run)
    if w is None:
        return None
    segments = len(ls.drained(w))
    if not segments:
        return None
    parts = {name: ls.clipped(w, name) for name in ls.PHASES}
    wall = w.hi - w.lo
    covered = sum(e - s for s, e in tr.union(
        iv for ivs in parts.values() for iv in ivs))
    by_phase = {name: sum(e - s for s, e in ivs)
                for name, ivs in parts.items()}
    common.say(phase="host_ms_per_segment", window_s=wall,
               segments=segments, seconds_by_span=by_phase,
               covered_share=covered / wall)
    return 1e3 * (wall - by_phase["serve/segment_fetch"]) / segments
