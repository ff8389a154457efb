"""Peak of the WINDOW block group's ``used_blocks`` over the window's polls,
in percent of that group (``kv_blocks_peak_share`` is the full group's).
``None`` where the run's sums carry no window group."""


def read(run: dict):
    s = run["stats"]
    if not s.get("kv_window_blocks_total"):
        return None
    return 100.0 * s["kv_window_blocks_peak"] / s["kv_window_blocks_total"]
