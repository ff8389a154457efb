"""``delta_step``'s share of its roofline in the decode step, like for
like: the kernel's events (by name) inside the JOINED runs of the segment
program (``_joined``) against the operations and bytes of the states THOSE
segments held (``state_lanes`` of each run's own drain: the lanes whose
state the slot cache held when it was dispatched), once a call.  The kernel
reads and writes every SLOT's state whatever the lane holds, so the share
follows the occupancy: an empty lane's bytes are moved and not counted."""

from benchmarks.layer_metrics import _delta_spans as ds
from benchmarks.layer_metrics import _joined, _scopes
from benchmarks.roofline import bound, delta_rule


def read(run: dict):
    joined = _joined.segments(run)
    shape = ds.linear_dims(run)
    if joined is None or shape is None or not run.get("peaks"):
        return None
    _, heads, dk, dv = shape
    least = seconds = 0.0
    for r, times in zip(joined, _scopes.by_run(run, joined)):
        if "state_lanes" not in r.drain:
            return None
        lanes = r.drain["state_lanes"]
        call = bound.least_seconds(
            delta_rule.flops(lanes, heads, dk, dv),
            delta_rule.step_bytes(lanes, heads, dk, dv), run["peaks"])
        for name, (spent, calls) in times.items():
            if ds.STEP.match(name):
                least += call * calls
                seconds += spent
    return 100.0 * least / seconds if seconds else None
