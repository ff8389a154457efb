"""Device time of the compiled segment program in the trace, per decode
step dispatched (runs of the program times ``steps_per_sync``)."""

from benchmarks.layer_metrics import _serve_trace as st


def read(run: dict):
    trace = run["trace"]
    if not trace:
        return None
    runs = st.module_runs(trace, st.SEGMENT)
    if not runs:
        return None
    steps = runs * run["options"]["steps_per_sync"]
    return 1e3 * st.module_seconds(trace, st.SEGMENT) / steps
