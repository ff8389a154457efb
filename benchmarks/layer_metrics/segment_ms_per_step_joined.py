"""Device time of the segment program per decode step it RAN: the whole
runs of the trace joined to their ``seq`` (``_joined``), their time over the
sum of the ``steps_run`` their own drains report.  A run the trace's edge
cut is left out and a segment that froze early divides by what it ran."""

from benchmarks.layer_metrics import _joined


def read(run: dict):
    return _joined.ms_per_step(run)
