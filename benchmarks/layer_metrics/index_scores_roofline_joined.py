"""``paged_index_scores``' share of its roofline in the decode step, like
for like: the kernel's events (by name) inside the JOINED runs of the
segment program (``_joined``) against the operations and bytes of the index
keys THOSE segments read (``rows_scored`` and ``lanes`` of each run's own
drain, once a call of the kernel), not of the window's mean step.  Each key
counted once at the 64 numbers it has: the walk reads the 256 B a key is
stored at, so 50% is its ceiling."""

from benchmarks.layer_metrics import _index_spans as ix
from benchmarks.layer_metrics import _joined, _scopes
from benchmarks.roofline import bound, index_scores


def read(run: dict):
    joined = _joined.segments(run)
    if joined is None or not run.get("peaks"):
        return None
    dims = run["dims"]
    least = seconds = 0.0
    for r, times in zip(joined, _scopes.by_run(run, joined)):
        if "rows_scored" not in r.drain:
            return None
        call = bound.least_seconds(
            index_scores.flops(r.drain["rows_scored"], dims.index_heads,
                               dims.index_dim),
            index_scores.bytes_moved(r.drain["rows_scored"],
                                     r.drain["lanes"], dims.index_heads,
                                     dims.index_dim), run["peaks"])
        for name, (spent, calls) in times.items():
            if ix.SCORES.match(name):
                least += call * calls
                seconds += spent
    return 100.0 * least / seconds if seconds else None
