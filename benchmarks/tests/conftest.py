"""Run by hand: ``JAX_PLATFORMS=cpu python3 -m pytest benchmarks/tests -q``."""

import json
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))


@pytest.fixture
def run_tiny(capsys):
    """Drive one ``--tiny`` run in this process (the harness's look for a
    chip is skipped, the rest of a run is as on the chip) and return its
    last line."""
    from benchmarks import run

    def go(workload: str, seed: int = 3, seconds: float = 3.0,
           trace: int = 0) -> dict:
        capsys.readouterr()
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace),
                       "--tiny"])
        assert rc == 0
        lines = capsys.readouterr().out.strip().splitlines()
        out = json.loads(lines[-1])
        out["lines"] = [json.loads(ln) for ln in lines[:-1]
                        if ln.startswith("{")]
        return out

    return go
