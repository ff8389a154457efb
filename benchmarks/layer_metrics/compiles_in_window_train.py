"""``xla/compiles`` at the window's end less its start; must be 0."""


def read(run: dict):
    return run["stats"]["compiles_in_window"]
