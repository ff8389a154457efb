"""The no-fallback contract of ``chip_smoke.py`` and the compile-cache rule.

Off a TPU the smoke can rehearse its phases (``--tiny``) but can never
report success: non-zero exit, ``"ok": false`` on the last line."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from tpudist.runtime import cache

REPO = Path(__file__).resolve().parents[1]


def test_tiny_rehearsal_runs_every_phase_and_still_fails_off_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one CPU device, as the one-chip run sees
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--tiny"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.strip()]
    assert proc.returncode != 0, proc.stdout
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    assert lines[-1]["failed"] == ["device"], proc.stderr[-2000:]
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    assert phases["device"]["ok"] is False
    assert phases["serve"]["ok"] and phases["train"]["ok"], proc.stderr[-2000:]
    assert phases["serve"]["exact_argmax_share"] > 0.9


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls made by the cache module
    instead of applying them (conftest already configured this process)."""
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.append((name, value)))
    return seen


def test_cache_dir_from_environment_is_not_set_in_code(
        monkeypatch, tmp_path, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache.enable_compilation_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in dict(config_updates)


def test_cache_dir_default_is_one_fixed_path_in_the_checkout(
        monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = cache.enable_compilation_cache()
    second = cache.enable_compilation_cache()
    assert first == second == str(REPO / ".jax_cache")
    assert dict(config_updates)["jax_compilation_cache_dir"] == first
