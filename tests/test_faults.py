"""Fault-injection harness (ISSUE 6): env parsing, deterministic
injection, CoordClient's idempotent-op retry riding through injected
faults, heartbeat drop, and the SIGKILL-after-K-segments schedule."""

import os
import signal
import subprocess
import sys
from pathlib import Path

import pytest

from tpudist import obs
from tpudist.runtime import faults
from tpudist.runtime.faults import FaultInjected, FaultPlan


@pytest.fixture(autouse=True)
def _clean_plan():
    """Never leak an installed plan (or env-parsed state) across tests."""
    faults.reset()
    yield
    faults.reset()


def _coord_pair():
    try:
        from tpudist.runtime.coord import CoordClient, CoordServer

        server = CoordServer(0)
    except Exception as e:  # NativeUnavailable or build failure
        pytest.skip(f"native coord store unavailable: {e}")
    return server, CoordClient("127.0.0.1", server.port)


class TestPlan:
    def test_env_parsing(self):
        plan = FaultPlan.from_env({
            "TPUDIST_FAULT_COORD_ERROR_P": "0.25",
            "TPUDIST_FAULT_COORD_DELAY_P": "0.5",
            "TPUDIST_FAULT_COORD_DELAY_S": "0.01",
            "TPUDIST_FAULT_HEARTBEAT_STOP_AFTER_S": "3.5",
            "TPUDIST_FAULT_KILL_AFTER_SEGMENTS": "7",
            "TPUDIST_FAULT_PUBLISH_DROP": "2.5",
            "TPUDIST_FAULT_SEED": "42",
        })
        assert plan.active
        assert plan.coord_error_p == 0.25
        assert plan.coord_delay_p == 0.5
        assert plan.coord_delay_s == 0.01
        assert plan.heartbeat_stop_after_s == 3.5
        assert plan.kill_after_segments == 7
        assert plan.publish_drop_after_s == 2.5
        assert plan.seed == 42

    def test_empty_env_is_inert(self):
        plan = FaultPlan.from_env({})
        assert not plan.active
        # inert hooks are no-ops
        plan.coord_op("get")
        assert not plan.drop_heartbeat()
        assert not plan.drop_publish()
        plan.on_segment()
        plan.on_warmup()
        assert not plan.corrupt_canary("canary-0")
        plan.autoscale_poll()
        assert all(n == 0 for n in plan.injected.values())

    def test_probability_validation(self):
        with pytest.raises(ValueError, match="coord_error_p"):
            FaultPlan(coord_error_p=1.5)
        with pytest.raises(ValueError, match="coord_delay_p"):
            FaultPlan(coord_delay_p=-0.1)

    def test_injection_is_seed_deterministic(self):
        """Same seed => bit-identical injection schedule (a failing CI
        run replays); different seed => (almost surely) different."""

        def schedule(seed):
            plan = FaultPlan(coord_error_p=0.3, seed=seed)
            out = []
            for _ in range(64):
                try:
                    plan.coord_op("get")
                    out.append(0)
                except FaultInjected:
                    out.append(1)
            return out

        a, b = schedule(7), schedule(7)
        assert a == b and 0 < sum(a) < 64
        assert schedule(8) != a

    def test_module_plan_reads_env_once(self, monkeypatch):
        monkeypatch.setenv("TPUDIST_FAULT_COORD_ERROR_P", "1.0")
        faults.reset()
        assert faults.plan().coord_error_p == 1.0
        with pytest.raises(FaultInjected):
            faults.coord_op("get")
        monkeypatch.delenv("TPUDIST_FAULT_COORD_ERROR_P")
        # still cached until reset
        assert faults.plan().coord_error_p == 1.0
        faults.reset()
        assert faults.plan().coord_error_p == 0.0


class _FailFirstN(FaultPlan):
    """Raise on the first ``fail_n`` coord ops, then pass — the
    deterministic shape of a transient network blip."""

    def __init__(self, fail_n):
        super().__init__()
        self.active = True
        self.fail_n = fail_n
        self.calls = 0

    def coord_op(self, op):
        self.calls += 1
        if self.calls <= self.fail_n:
            raise FaultInjected(f"injected: {op} call #{self.calls}")


class TestCoordRetry:
    def test_idempotent_get_retries_through_transient_fault(self):
        server, client = _coord_pair()
        client.set("k", b"v")  # before the plan goes in
        before = obs.snapshot()["counters"].get(
            "coord/retries", {}).get("value", 0)
        plan = _FailFirstN(2)
        faults.install(plan)
        try:
            assert client.get("k") == b"v"  # default retries=2 suffice
        finally:
            faults.reset()
        assert plan.calls == 3  # 2 failures + 1 success
        after = obs.snapshot()["counters"]["coord/retries"]["value"]
        assert after - before == 2

    def test_retry_budget_exhausts(self):
        server, client = _coord_pair()
        faults.install(_FailFirstN(10))
        try:
            with pytest.raises(FaultInjected):
                client.get("k")
        finally:
            faults.reset()

    def test_non_idempotent_add_surfaces_immediately(self):
        """add is a read-modify-write: a lost reply may have applied, so
        replaying it risks double-counting — the client must NOT retry."""
        server, client = _coord_pair()
        plan = _FailFirstN(1)
        faults.install(plan)
        try:
            with pytest.raises(FaultInjected):
                client.add("ctr", 1)
        finally:
            faults.reset()
        assert plan.calls == 1  # exactly one attempt
        # the fault fired BEFORE the RPC: nothing was applied
        assert client.add("ctr", 1) == 1

    def test_publish_drop_swallows_store_write_not_heartbeat(self):
        """PUBLISH_DROP starves the obs plane while the TTL plane keeps
        beating — the exact stale-not-lost shape HealthMonitor
        classifies.  The publisher must still return the snapshot (its
        local callers keep working); only the store write vanishes."""
        from tpudist.obs.aggregate import MetricsPublisher, collect

        server, client = _coord_pair()
        faults.install(FaultPlan(publish_drop_after_s=0.0))
        try:
            pub = MetricsPublisher(client, 0, obs.registry,
                                   namespace="pd")
            snap = pub.publish()
            assert snap["rank"] == 0            # local snapshot intact
            assert collect(client, namespace="pd") == {}  # write dropped
            client.heartbeat("pd-live", 5.0)    # heartbeats unaffected
            assert "pd-live" in client.live()
            assert faults.plan().injected["publish_drop"] >= 1
        finally:
            faults.reset()
            client.heartbeat("pd-live", 0.0)
        pub.publish()
        assert 0 in collect(client, namespace="pd")  # flows again

    def test_heartbeat_drop_swallows_lease_refresh(self):
        server, client = _coord_pair()
        faults.install(FaultPlan(heartbeat_stop_after_s=0.0))
        try:
            client.heartbeat("hb-dropped", 5.0)
            assert "hb-dropped" not in client.live()
        finally:
            faults.reset()
        client.heartbeat("hb-live", 5.0)
        assert "hb-live" in client.live()
        client.heartbeat("hb-live", 0.0)  # leave


class TestKillSchedule:
    def test_sigkill_after_k_segments(self, tmp_path):
        """The subprocess counts segments and must vanish (SIGKILL, no
        cleanup) on the Kth — asserted by return code -9 and by which
        progress markers made it to stdout."""
        script = (
            "from tpudist.runtime import faults\n"
            "for i in range(5):\n"
            "    print(f'seg{i}', flush=True)\n"
            "    faults.on_segment()\n"
            "print('survived', flush=True)\n"
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [str(Path(__file__).resolve().parents[1])]
            + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
        env["TPUDIST_FAULT_KILL_AFTER_SEGMENTS"] = "3"
        res = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, timeout=60)
        assert res.returncode == -signal.SIGKILL
        assert "seg2" in res.stdout  # the fatal segment was dispatched
        assert "survived" not in res.stdout


class TestControlPlaneInjections:
    """ISSUE 9 knobs: delayed first heartbeat, warmup kill, canary
    corruption, autoscaler poll stall."""

    def test_env_parsing_new_knobs(self):
        plan = FaultPlan.from_env({
            "TPUDIST_FAULT_HEARTBEAT_DELAY_S": "1.5",
            "TPUDIST_FAULT_KILL_AT_WARMUP": "1",
            "TPUDIST_FAULT_CANARY_CORRUPT": "1",
            "TPUDIST_FAULT_AUTOSCALE_POLL_DELAY_S": "0.25",
        })
        assert plan.active
        assert plan.heartbeat_delay_s == 1.5
        assert plan.kill_at_warmup is True
        assert plan.canary_corrupt is True
        assert plan.autoscale_poll_delay_s == 0.25

    def test_heartbeat_delay_drops_early_then_flows(self):
        plan = FaultPlan(heartbeat_delay_s=1e6)
        assert plan.drop_heartbeat()          # uptime < delay: swallowed
        assert plan.injected["heartbeat_delay"] == 1
        plan2 = FaultPlan(heartbeat_delay_s=1e-9)
        import time as _time
        _time.sleep(0.01)
        assert not plan2.drop_heartbeat()     # past the delay: flows

    def test_heartbeat_delay_composes_with_stop(self):
        # delay only suppresses EARLY beats; stop suppresses late ones
        plan = FaultPlan(heartbeat_delay_s=1e-9,
                         heartbeat_stop_after_s=1e6)
        import time as _time
        _time.sleep(0.01)
        assert not plan.drop_heartbeat()

    def test_canary_corrupt_only_hits_canary_rids(self):
        plan = FaultPlan(canary_corrupt=True)
        assert plan.corrupt_canary("canary-0")
        assert not plan.corrupt_canary("req-7")
        assert plan.injected["canary_corrupt"] == 1
        assert not FaultPlan().corrupt_canary("canary-0")

    def test_autoscale_poll_stalls(self):
        import time as _time
        plan = FaultPlan(autoscale_poll_delay_s=0.05)
        t0 = _time.monotonic()
        plan.autoscale_poll()
        assert _time.monotonic() - t0 >= 0.05
        assert plan.injected["autoscale_delay"] == 1

    def test_kill_at_warmup_sigkills_subprocess(self, tmp_path):
        code = (
            "from tpudist.runtime import faults\n"
            "faults.on_warmup()\n"
            "print('survived')\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code],
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "TPUDIST_FAULT_KILL_AT_WARMUP": "1"},
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == -signal.SIGKILL
        assert "survived" not in proc.stdout

    def test_module_hooks_inert_by_default(self):
        faults.reset()
        faults.on_warmup()
        assert not faults.corrupt_canary("canary-9")
        faults.autoscale_poll()
        faults.on_router_poll()


class TestControlPlaneCrashKnobs:
    """ISSUE 12 knobs: router kill-after-polls and the full-store
    coord-outage window."""

    def test_env_parsing(self):
        plan = FaultPlan.from_env({
            "TPUDIST_FAULT_ROUTER_KILL_AFTER_POLLS": "25",
            "TPUDIST_FAULT_COORD_OUTAGE_AT_S": "3.0",
            "TPUDIST_FAULT_COORD_OUTAGE_S": "2.5",
        })
        assert plan.active
        assert plan.router_kill_after_polls == 25
        assert plan.coord_outage_at_s == 3.0
        assert plan.coord_outage_s == 2.5
        # the outage length defaults to 5 s once the start is set
        assert FaultPlan.from_env(
            {"TPUDIST_FAULT_COORD_OUTAGE_AT_S": "1.0"}).coord_outage_s \
            == 5.0

    def test_validation(self):
        with pytest.raises(ValueError, match="router_kill_after_polls"):
            FaultPlan(router_kill_after_polls=0)
        with pytest.raises(ValueError, match="coord_outage_s"):
            FaultPlan(coord_outage_at_s=1.0, coord_outage_s=0.0)

    def test_outage_window_refuses_every_op_then_lifts(self):
        plan = FaultPlan(coord_outage_at_s=0.0, coord_outage_s=0.05)
        assert plan.in_outage()
        for op in ("get", "set", "delete", "add", "keys", "live"):
            with pytest.raises(FaultInjected, match="coord outage"):
                plan.coord_op(op)
        assert plan.injected["coord_outage"] == 6
        import time as _time
        _time.sleep(0.06)
        assert not plan.in_outage()
        plan.coord_op("get")   # flows again

    def test_outage_not_yet_open_is_inert(self):
        plan = FaultPlan(coord_outage_at_s=1e6)
        assert not plan.in_outage()
        plan.coord_op("get")

    def test_router_kill_raise_is_one_shot(self):
        from tpudist.runtime.faults import RouterKilled

        plan = FaultPlan(router_kill_after_polls=3,
                         router_kill_raise=True)
        plan.on_router_poll()
        plan.on_router_poll()
        with pytest.raises(RouterKilled, match="poll 3"):
            plan.on_router_poll()
        assert plan.injected["router_kill"] == 1
        # disarmed: the recovery router's polls must not re-trip it
        for _ in range(10):
            plan.on_router_poll()
        assert plan.injected["router_kill"] == 1

    def test_router_kill_sigkills_subprocess(self):
        """The live shape: a subprocess router counting polls must
        vanish (SIGKILL, no cleanup) on the Kth."""
        script = (
            "from tpudist.runtime import faults\n"
            "for i in range(6):\n"
            "    print(f'poll{i}', flush=True)\n"
            "    faults.on_router_poll()\n"
            "print('survived', flush=True)\n"
        )
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            [str(Path(__file__).resolve().parents[1])]
            + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
        env["TPUDIST_FAULT_ROUTER_KILL_AFTER_POLLS"] = "4"
        res = subprocess.run([sys.executable, "-c", script], env=env,
                             capture_output=True, text=True, timeout=60)
        assert res.returncode == -signal.SIGKILL
        assert "poll3" in res.stdout
        assert "survived" not in res.stdout

    def test_refused_gate_retries_outage_for_all_verbs(self):
        """During a declared outage the fault fires BEFORE the RPC
        leaves the process ("connection refused"), so even the
        non-idempotent add retries through a window that closes inside
        the retry budget."""
        server, _ = _coord_pair()
        from tpudist.runtime.coord import CoordClient

        # a retry budget comfortably longer than the window (naps are
        # >= 20 ms each), so the gate deterministically outlives it
        client = CoordClient("127.0.0.1", server.port, retries=30)
        stretches = obs.snapshot()["histograms"].get(
            "coord/outage_s", {}).get("count", 0)
        faults.install(FaultPlan(coord_outage_at_s=0.0,
                                 coord_outage_s=0.15))
        try:
            # backoff sleeps carry the retries past the window's end
            assert client.add("outage-ctr", 1) == 1
            assert faults.plan().injected["coord_outage"] >= 1
        finally:
            faults.reset()
        snap = obs.snapshot()
        assert snap["histograms"].get(
            "coord/retry_backoff_s", {}).get("count", 0) >= 1
        # the store is reachable again and the stretch went on record
        assert snap["gauges"]["coord/unavailable"]["value"] == 0
        assert snap["histograms"]["coord/outage_s"]["count"] > stretches


class TestIntegrityKnobs:
    """ISSUE 13 knobs: wire bit flips, NaN logit poisoning, golden-probe
    corruption."""

    def test_env_parsing(self):
        plan = FaultPlan.from_env({
            "TPUDIST_FAULT_FLIP_WIRE_BITS": "2:5",
            "TPUDIST_FAULT_NAN_AFTER_TOKENS": "40",
            "TPUDIST_FAULT_PROBE_FAIL": "2",
        })
        assert plan.active
        assert (plan.flip_wire_every, plan.flip_wire_max) == (2, 5)
        assert plan.nan_after_tokens == 40
        assert plan.probe_fail == 2
        # uncapped form: every Nth payload, forever
        plan = FaultPlan.from_env({"TPUDIST_FAULT_FLIP_WIRE_BITS": "3"})
        assert (plan.flip_wire_every, plan.flip_wire_max) == (3, None)

    @pytest.mark.parametrize("bad", ["0", "x", "2:0", "1:y", ":3"])
    def test_flip_spec_validation(self, bad):
        with pytest.raises(ValueError, match="flip_wire_bits"):
            FaultPlan(flip_wire_bits=bad)

    def test_threshold_validation(self):
        with pytest.raises(ValueError, match="nan_after_tokens"):
            FaultPlan(nan_after_tokens=-1)
        with pytest.raises(ValueError, match="probe_fail"):
            FaultPlan(probe_fail=0)

    def test_flip_every_nth_with_cap(self):
        """'2:2': payloads 2 and 4 get ONE bit flipped past the frame
        header (so the CHECKSUM, not a parse error, is what catches
        it); the cap then disarms the injection — the transient shape
        whose reinstatement path the fleet test drives."""
        from tpudist.runtime import wire

        plan = FaultPlan(flip_wire_bits="2:2")
        clean = wire.encode_record("completion", {
            "key": "k", "tokens": list(range(16)), "reason": "length",
            "replica": "r1"})
        out = [plan.flip_wire_bits(clean) for _ in range(6)]
        assert out[0] == clean and out[2] == clean    # off-cycle
        assert out[4] == clean and out[5] == clean    # cap reached
        for flipped in (out[1], out[3]):
            assert flipped != clean
            assert len(flipped) == len(clean)
            diff = [i for i in range(len(clean))
                    if flipped[i] != clean[i]]
            assert len(diff) == 1 and diff[0] >= 9    # inside the body
            with pytest.raises(wire.WireError) as ei:
                wire.decode_record(flipped)
            assert ei.value.reason == "checksum"
        assert plan.injected["wire_flip"] == 2

    def test_flip_passthrough_cases(self):
        plan = FaultPlan(flip_wire_bits="1")
        assert plan.flip_wire_bits(b"") == b""
        assert FaultPlan().flip_wire_bits(b"payload") == b"payload"

    def test_poison_logits_threshold(self):
        plan = FaultPlan(nan_after_tokens=10)
        assert not plan.poison_logits(9)
        assert plan.injected["nan_logits"] == 0
        assert plan.poison_logits(10)
        assert plan.poison_logits(11)
        assert plan.injected["nan_logits"] == 2
        assert not FaultPlan().poison_logits(10 ** 9)

    def test_corrupt_probe_first_n_only(self):
        plan = FaultPlan(probe_fail=2)
        assert plan.corrupt_probe("probe-r1-000000")
        assert not plan.corrupt_probe("q7")          # not a probe
        assert plan.corrupt_probe("probe-r1-000001")
        assert not plan.corrupt_probe("probe-r1-000002")  # budget spent
        assert plan.injected["probe_corrupt"] == 2

    def test_module_hooks_inert_by_default(self):
        faults.reset()
        assert faults.flip_wire_bits(b"abc") == b"abc"
        assert not faults.poison_logits(10 ** 9)
        assert not faults.corrupt_probe("probe-r1-000000")


class TestMigrateKnobs:
    """ISSUE 19 chaos seams: the MIGRATE payload-drop budget and the
    kill-at-migrate window knob (the SIGKILL itself is exercised by the
    fleet E2E in tests/test_migration.py)."""

    def test_env_parsing(self):
        plan = FaultPlan.from_env({
            "TPUDIST_FAULT_MIGRATE_DROP": "2",
            "TPUDIST_FAULT_KILL_AT_MIGRATE": "1",
        })
        assert plan.active
        assert plan.migrate_drop == 2
        assert plan.kill_at_migrate == 1

    def test_validation(self):
        with pytest.raises(ValueError, match="migrate_drop"):
            FaultPlan(migrate_drop=0)
        with pytest.raises(ValueError, match="kill_at_migrate"):
            FaultPlan(kill_at_migrate=0)

    def test_drop_budget_swallows_first_n_then_flows(self):
        plan = FaultPlan(migrate_drop=2)
        assert plan.drop_migrate()
        assert plan.drop_migrate()
        assert not plan.drop_migrate()      # budget spent
        assert plan.injected["migrate_drop"] == 2

    def test_drop_inert_without_knob(self):
        plan = FaultPlan()
        assert not plan.drop_migrate()
        assert plan.injected["migrate_drop"] == 0

    def test_migrate_drop_is_independent_of_handoff_drop(self):
        # one knob per seam: a migrate budget never swallows handoffs
        plan = FaultPlan(migrate_drop=1)
        assert not plan.drop_publish()
        assert plan.drop_migrate()

    def test_on_migrate_published_inert_without_knob(self):
        plan = FaultPlan()
        plan.on_migrate_published()         # must not kill the test
        assert plan.injected["migrate_kill"] == 0
