"""``xla/compile_seconds`` summed over set-up (cache hits cost none)."""


def read(run: dict):
    return run["compile_s_setup"]
