"""Device time of the prefill-chunk and admit-finish programs, in percent
of the device's busy time in the trace."""

from benchmarks.layer_metrics import _serve_trace as st


def read(run: dict):
    trace = run["trace"]
    if not trace or not trace["busy_s"]:
        return None
    sec = (st.module_seconds(trace, st.PREFILL)
           + st.module_seconds(trace, st.FINISH))
    return 100.0 * sec / trace["busy_s"]
