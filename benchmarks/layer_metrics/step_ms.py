"""Median step on the host clock (feed, step, loss back on the host)."""


def read(run: dict):
    return 1e3 * run["stats"]["step_median_s"]
