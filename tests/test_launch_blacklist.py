"""Per-worker blacklist in the launcher (round-4 verdict #6) — the
``horovodrun --blacklist-cooldown-range`` per-host semantics
(`/root/reference/horovod/horovod_mnist_elastic.py:108`): the SPECIFIC
repeatedly-failing spawn slot is excluded, healthy workers keep their
place, and the world re-grows with a fresh slot.

The flaky worker is jax-free, so this file runs in the DEFAULT (not-slow)
test lane, unlike test_launch.py."""

import sys
from pathlib import Path

import pytest

from tpudist.runtime.launch import launch

FLAKY = str(Path(__file__).parent / "workers" / "flaky_worker.py")


class TestPerWorkerBlacklist:
    """Per-host blacklist semantics (round-4 verdict #6,
    `horovod_mnist_elastic.py:108`): the SPECIFIC repeatedly-failing spawn
    slot is excluded — healthy workers keep their place — and the world
    re-grows with a fresh slot.  The flaky worker is jax-free, so these
    run in the default (not-slow) lane."""

    def _events(self, tmp_path):
        import json

        p = tmp_path / "events.jsonl"
        return [json.loads(line) for line in p.read_text().splitlines()]

    def test_repeat_offender_excluded_world_regrows(self, tmp_path):
        rc = launch(
            [sys.executable, FLAKY], nprocs=3, max_restarts=3,
            blacklist_after=2, coord_server=False,
            env={"PYTHONPATH": "", "WORKER_OUT_DIR": str(tmp_path),
                 "WORKER_FAIL_SPAWN_IDS": "1"},
        )
        assert rc == 0
        ev = self._events(tmp_path)
        by_attempt = {}
        for e in ev:
            by_attempt.setdefault(e["attempt"], set()).add(e["sid"])
        # sid 1 gets blacklist_after=2 chances, then is excluded while a
        # FRESH slot (3) fills the world back to 3 — healthy 0/2 stay
        assert by_attempt[0] == {"0", "1", "2"}
        assert by_attempt[1] == {"0", "1", "2"}
        assert by_attempt[2] == {"0", "2", "3"}
        assert all(e["world"] == 3 for e in ev)

    def test_healthy_workers_never_dropped_vs_shrink(self, tmp_path):
        """blacklist_after=1: one failure excludes the slot immediately;
        the success attempt still runs at FULL world (contrast with the
        min_nprocs shrink path, which drops a healthy worker)."""
        rc = launch(
            [sys.executable, FLAKY], nprocs=2, max_restarts=1,
            blacklist_after=1, coord_server=False,
            env={"PYTHONPATH": "", "WORKER_OUT_DIR": str(tmp_path),
                 "WORKER_FAIL_SPAWN_IDS": "1"},
        )
        assert rc == 0
        ev = self._events(tmp_path)
        last = {e["sid"] for e in ev if e["attempt"] == 1}
        assert last == {"0", "2"}          # sid 1 out, fresh sid 2 in
        assert all(e["world"] == 2 for e in ev)
        # sid 1 ran exactly once (no second chance at blacklist_after=1)
        assert sum(e["sid"] == "1" for e in ev) == 1

    def test_cooldown_readmits_slot_with_reset_count(self, tmp_path):
        """A cooled-down slot rejoins the roster (failure count reset)
        when capacity needs it — horovod's cooldown-range behavior.
        Healthy/fresh slots take precedence, so readmission is forced by
        making the fresh replacement fail too."""
        rc = launch(
            [sys.executable, FLAKY], nprocs=2, max_restarts=2,
            blacklist_after=1, blacklist_cooldown=0.0, coord_server=False,
            env={"PYTHONPATH": "", "WORKER_OUT_DIR": str(tmp_path),
                 "WORKER_FAIL_SPAWN_IDS": "1,2"},   # fresh sid 2 bad too
        )
        assert rc != 0
        ev = self._events(tmp_path)
        a1 = {e["sid"] for e in ev if e["attempt"] == 1}
        a2 = {e["sid"] for e in ev if e["attempt"] == 2}
        assert a1 == {"0", "2"}            # 1 excluded while cooling
        assert "1" in a2                   # readmitted: 2 blacklisted and
        assert "2" not in a2               # 1's cooldown had elapsed

    def test_recovered_slot_rescheduled_ahead_of_fresh_sids(self, tmp_path):
        """A slot whose cooldown expires with the roster FULL must rejoin
        ahead of the synthetic replacement sids, not behind them: the
        scheduled set is roster[:world], so a tail append would leave the
        recovered slot parked forever.  Here sid 1 fails attempts 0-1 and
        is blacklisted with an instant cooldown; fresh sid 2 replaces it
        at attempt 2, where sid 0 fails (once — not enough to blacklist).
        At attempt 3 the roster holds [0, 1, 2]: recovered 1 must outrank
        replacement 2 (the buggy tail append scheduled {0, 2})."""
        rc = launch(
            [sys.executable, FLAKY], nprocs=2, max_restarts=3,
            blacklist_after=2, blacklist_cooldown=0.0, coord_server=False,
            env={"PYTHONPATH": "", "WORKER_OUT_DIR": str(tmp_path),
                 "WORKER_FAIL_SPAWN_IDS": "1@0,1@1,0@2"},
        )
        assert rc == 0
        ev = self._events(tmp_path)
        by_attempt = {}
        for e in ev:
            by_attempt.setdefault(e["attempt"], set()).add(e["sid"])
        assert by_attempt[0] == {"0", "1"}
        assert by_attempt[1] == {"0", "1"}
        assert by_attempt[2] == {"0", "2"}   # 1 cooling; fresh 2 fills in
        assert by_attempt[3] == {"0", "1"}   # recovered 1 ahead of fresh 2
        assert all(e["world"] == 2 for e in ev)

    def test_blacklist_after_validation(self):
        with pytest.raises(ValueError, match="blacklist_after"):
            launch([sys.executable, FLAKY], nprocs=2, blacklist_after=0)


@pytest.mark.parametrize("platform,env", [
    ("tpu", {}),                        # asked for outright
    ("", {"JAX_PLATFORMS": "tpu"}),     # '' = inherit the caller's
])
def test_many_workers_on_a_chip_platform_rejected(platform, env):
    """A chip belongs to one process: N workers that would all reach for
    this host's chips are refused up front instead of hanging there."""
    with pytest.raises(ValueError, match="share this host's chips"):
        launch([sys.executable, FLAKY], nprocs=2, platform=platform,
               env=env, coord_server=False)
