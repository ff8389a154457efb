"""Peak of ``loop.pool.used_blocks`` over the window's polls, in percent of
the pool."""


def read(run: dict):
    s = run["stats"]
    if not s["kv_blocks_total"]:
        return None
    return 100.0 * s["kv_blocks_peak"] / s["kv_blocks_total"]
