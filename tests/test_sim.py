"""Scenario specs, workload synthesis/replay, the SLO-envelope checker,
and the offline fleet simulator (ISSUE 11): spec parsing must reject
typos, workloads must be deterministic under their seed, the envelope
checker must treat the emitter's verdict as evidence (not authority),
and the simulator must reproduce a recorded live run's autoscaler
decision sequence within one poll of the breach."""

import json
import os

import numpy as np
import pytest

from tpudist.sim.envelope import check_row, check_rows, scenario_rows
from tpudist.sim.scenario import (
    BUILTIN, Envelope, ScenarioSpec, builtin, names)
from tpudist.sim.workload import (
    Workload, WorkItem, service_rates_from_trace, synthesize,
    workload_from_trace)

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "sim_replay_fixture.json")


def _spec(**over) -> ScenarioSpec:
    base = {"name": "t", "duration_s": 5.0,
            "arrival": {"kind": "constant", "rate": 10.0}}
    base.update(over)
    return ScenarioSpec.from_dict(base)


class TestScenarioSpec:
    def test_minimal_spec_gets_fleet_defaults(self):
        spec = _spec()
        assert spec.fleet["replicas"] == 1
        assert spec.fleet["seconds_per_token"] == pytest.approx(0.002)
        assert spec.fleet["autoscale"] is None
        assert spec.deadline == {"kind": "none"}

    def test_fleet_overrides_merge_not_replace(self):
        spec = _spec(fleet={"replicas": 3})
        assert spec.fleet["replicas"] == 3
        assert spec.fleet["warmup_s"] == pytest.approx(2.0)

    def test_unknown_top_level_key_rejected(self):
        with pytest.raises(ValueError, match="unknown keys.*'rate_limit'"):
            _spec(rate_limit=5)

    def test_unknown_fleet_key_rejected(self):
        # a typo'd knob must fail parsing, not run the default scenario
        with pytest.raises(ValueError, match="unknown keys.*'replica'"):
            _spec(fleet={"replica": 2})

    def test_missing_required_keys_rejected(self):
        with pytest.raises(ValueError, match="missing required keys"):
            ScenarioSpec.from_dict({"name": "t", "duration_s": 1.0})

    def test_arrival_validation(self):
        with pytest.raises(ValueError, match="not in"):
            _spec(arrival={"kind": "bursty", "rate": 1.0})
        with pytest.raises(ValueError, match="rate > 0"):
            _spec(arrival={"kind": "constant", "rate": 0.0})
        with pytest.raises(ValueError, match="base_rate <= peak_rate"):
            _spec(arrival={"kind": "diurnal", "base_rate": 9.0,
                           "peak_rate": 3.0, "period_s": 60.0})
        with pytest.raises(ValueError, match="spike_rate > base_rate"):
            _spec(arrival={"kind": "flash_crowd", "base_rate": 5.0,
                           "spike_rate": 5.0, "spike_width_s": 2.0})

    def test_prompt_and_deadline_validation(self):
        with pytest.raises(ValueError, match="lo <= typical < tail"):
            _spec(prompt={"kind": "longtail", "lo": 4, "typical": 512,
                          "tail": 16})
        with pytest.raises(ValueError, match="tight_s < loose_s"):
            _spec(deadline={"kind": "adversarial", "tight_frac": 0.2,
                            "tight_s": 10.0, "loose_s": 1.0})

    def test_tenant_validation(self):
        with pytest.raises(ValueError, match="missing required keys"):
            _spec(tenants=[{"name": "a"}])
        with pytest.raises(ValueError, match="weight > 0"):
            _spec(tenants=[{"name": "a", "weight": 0.0}])

    def test_roundtrip_through_dict(self):
        spec = builtin("deadline_storm")
        back = ScenarioSpec.from_dict(spec.to_dict())
        assert back == spec

    def test_from_json(self, tmp_path):
        path = tmp_path / "s.json"
        path.write_text(json.dumps(
            {"name": "file-spec", "duration_s": 2.0,
             "arrival": {"kind": "constant", "rate": 3.0}}))
        assert ScenarioSpec.from_json(str(path)).name == "file-spec"

    def test_builtin_matrix_parses_and_is_big_enough(self):
        # the CI gate demands >= 5 named scenarios; every one must parse
        assert len(names()) >= 5
        for name in names():
            spec = builtin(name)
            assert spec.name == name
        with pytest.raises(KeyError, match="unknown scenario"):
            builtin("nope")

    def test_fault_validation(self):
        with pytest.raises(ValueError, match="not in"):
            _spec(faults=[{"kind": "meteor"}])
        with pytest.raises(ValueError, match="missing required keys"):
            _spec(faults=[{"kind": "kill_replica", "at_s": 1.0}])
        with pytest.raises(ValueError, match="unknown keys"):
            _spec(faults=[{"kind": "kill_replica", "at_s": 1.0,
                           "rid": "r0", "for_s": 2.0}])
        with pytest.raises(ValueError, match="for_s > 0"):
            _spec(faults=[{"kind": "coord_brownout", "at_s": 1.0,
                           "for_s": 0.0}])
        with pytest.raises(ValueError, match="at_poll >= 1"):
            _spec(faults=[{"kind": "kill_router", "at_poll": 0}])
        with pytest.raises(ValueError, match="at most one kill_router"):
            _spec(faults=[{"kind": "kill_router", "at_poll": 5},
                          {"kind": "kill_router", "at_poll": 9}])
        spec = _spec(faults=[
            {"kind": "kill_replica", "at_s": 1.0, "rid": "r0"},
            {"kind": "drop_heartbeats", "at_s": 2.0, "for_s": 1.0,
             "rid": "r1"}])
        assert isinstance(spec.faults, tuple) and len(spec.faults) == 2

    def test_chaos_builtins_present_and_faulted(self):
        # the chaos third of the matrix (ISSUE 12): present, parsed,
        # and actually scripting faults
        for name in ("replica_death_storm", "router_failover",
                     "coord_brownout"):
            spec = builtin(name)
            assert spec.faults, name
        back = ScenarioSpec.from_dict(
            builtin("replica_death_storm").to_dict())
        assert back == builtin("replica_death_storm")

    def test_corrupt_replica_validation(self):
        # ISSUE 13: the byzantine-replica verb and its integrity bounds
        with pytest.raises(ValueError, match="missing required keys"):
            _spec(faults=[{"kind": "corrupt_replica", "at_s": 1.0}])
        with pytest.raises(ValueError, match="every >= 1"):
            _spec(faults=[{"kind": "corrupt_replica", "at_s": 1.0,
                           "rid": "r1", "every": 0}])
        with pytest.raises(ValueError, match="count must be >= 1"):
            _spec(faults=[{"kind": "corrupt_replica", "at_s": 1.0,
                           "rid": "r1", "count": 0}])
        spec = _spec(faults=[{"kind": "corrupt_replica", "at_s": 1.0,
                              "rid": "r1", "every": 2, "count": 4}])
        assert spec.faults[0]["count"] == 4
        sc = builtin("silent_corruption")
        assert sc.faults and sc.faults[0]["kind"] == "corrupt_replica"
        assert sc.envelope.max_corrupted_terminals == 0
        assert sc.envelope.min_quarantines >= 1
        assert sc.envelope.min_reinstated >= 1
        assert ScenarioSpec.from_dict(sc.to_dict()) == sc


class TestEnvelope:
    def test_unknown_key_rejected(self):
        with pytest.raises(ValueError, match="unknown keys"):
            Envelope.from_dict({"max_p99": 1.0})
        with pytest.raises(ValueError, match="unknown keys"):
            Envelope.from_dict({"decisions": {"shed": {"atmost": 3}}})

    def test_clean_row_passes(self):
        env = Envelope.from_dict({
            "max_lost": 0, "max_p99_queue_wait_s": 1.0,
            "min_scale_ups": 1,
            "decisions": {"failed": {"max": 0},
                          "completed": {"min": 10}}})
        row = {"lost_requests": 0, "p99_queue_wait_s": 0.2,
               "scale_ups": 2, "decisions_failed": 0,
               "decisions_completed": 50}
        assert env.check(row) == []

    def test_each_bound_reports_its_violation(self):
        env = Envelope.from_dict({
            "max_lost": 0, "max_p99_queue_wait_s": 0.5,
            "max_recovery_s": 10.0, "min_scale_ups": 1,
            "max_priority_bad": 0,
            "decisions": {"completed": {"min": 100},
                          "failed": {"max": 0}}})
        row = {"lost_requests": 2, "p99_queue_wait_s": 3.0,
               "recovery_s": 60.0, "scale_ups": 0, "priority_bad": 4,
               "decisions_completed": 10, "decisions_failed": 1}
        bad = env.check(row)
        assert len(bad) == 7
        assert any("lost_requests" in b for b in bad)
        assert any("decisions_failed" in b for b in bad)

    def test_missing_fields_read_as_zero(self):
        # min bounds still bite on an empty row; max bounds don't
        assert Envelope(min_scale_ups=1).check({}) \
            == ["scale_ups=0 < min 1"]
        assert Envelope(max_p99_queue_wait_s=1.0).check({}) == []

    def test_chaos_bounds(self):
        env = Envelope.from_dict({
            "max_burn_rate_300s": 2.0, "max_replica_deaths": 1,
            "min_router_recoveries": 1})
        good = {"lost_requests": 0, "burn_rate_300s": 0.5,
                "replica_deaths": 1, "router_recoveries": 1}
        assert env.check(good) == []
        bad = env.check({"lost_requests": 0, "burn_rate_300s": 9.0,
                         "replica_deaths": 3, "router_recoveries": 0})
        assert len(bad) == 3
        assert any("burn_rate_300s" in b for b in bad)
        assert any("replica_deaths" in b for b in bad)
        assert any("router_recoveries" in b for b in bad)


class TestWorkloadSynthesis:
    def test_deterministic_under_seed(self):
        spec = _spec(seed=7)
        assert synthesize(spec).items == synthesize(spec).items
        other = _spec(seed=8)
        assert synthesize(spec).items != synthesize(other).items

    def test_arrival_count_tracks_rate(self):
        wl = synthesize(_spec(duration_s=20.0,
                              arrival={"kind": "constant", "rate": 10.0},
                              seed=3))
        # Poisson(200): a 4-sigma band, deterministic under the seed
        assert 140 <= len(wl) <= 260
        ats = [w.at for w in wl.items]
        assert ats == sorted(ats)
        assert all(0.0 <= t < 20.0 for t in ats)

    def test_flash_crowd_concentrates_in_spike(self):
        wl = synthesize(_spec(
            duration_s=20.0, seed=4,
            arrival={"kind": "flash_crowd", "base_rate": 2.0,
                     "spike_rate": 100.0, "spike_at_s": 5.0,
                     "spike_width_s": 2.0}))
        in_spike = sum(1 for w in wl.items if 5.0 <= w.at < 7.0)
        assert in_spike > len(wl) / 2

    def test_longtail_prompts_stay_in_range(self):
        wl = synthesize(_spec(
            duration_s=30.0, seed=5,
            arrival={"kind": "constant", "rate": 20.0},
            prompt={"kind": "longtail", "lo": 4, "typical": 16,
                    "tail": 256, "tail_frac": 0.2}))
        lens = [w.prompt_tokens for w in wl.items]
        assert min(lens) >= 4 and max(lens) <= 256
        assert max(lens) > 16   # the tail actually fires at frac=0.2

    def test_adversarial_deadlines_are_bimodal(self):
        wl = synthesize(_spec(
            duration_s=30.0, seed=6,
            arrival={"kind": "constant", "rate": 20.0},
            deadline={"kind": "adversarial", "tight_frac": 0.3,
                      "tight_s": 0.05, "loose_s": 30.0}))
        vals = {w.rel_deadline_s for w in wl.items}
        assert vals == {0.05, 30.0}

    def test_tenant_mix_rides_items(self):
        wl = synthesize(_spec(
            duration_s=20.0, seed=9,
            arrival={"kind": "constant", "rate": 20.0},
            tenants=[{"name": "sys", "weight": 5.0, "prefix_tokens": 16},
                     {"name": "paid", "weight": 1.0, "priority": 1}]))
        by_tenant = {t: [w for w in wl.items if w.tenant == t]
                     for t in ("sys", "paid")}
        assert len(by_tenant["sys"]) > len(by_tenant["paid"]) > 0
        assert all(w.priority == 1 for w in by_tenant["paid"])
        assert all(w.prefix_tokens == 16 for w in by_tenant["sys"])


class TestWorkloadRequests:
    def test_requests_and_arrivals_align(self):
        wl = synthesize(_spec(
            duration_s=10.0, seed=2,
            arrival={"kind": "constant", "rate": 5.0},
            deadline={"kind": "uniform", "lo": 1.0, "hi": 2.0}))
        reqs, arrivals = wl.requests(base_wall=1000.0)
        assert len(reqs) == len(arrivals) == len(wl)
        for req, at, item in zip(reqs, arrivals, wl.items):
            assert at == item.at
            assert req.prompt.size == item.prompt_tokens
            assert req.max_new_tokens == item.max_new
            # deadlines anchored at the caller's wall clock + arrival
            assert req.deadline_s == pytest.approx(
                1000.0 + item.at + item.rel_deadline_s)
        assert len({r.rid for r in reqs}) == len(reqs)

    def test_tenant_prefix_is_shared_and_stable(self):
        wl = synthesize(_spec(
            duration_s=20.0, seed=9,
            arrival={"kind": "constant", "rate": 20.0},
            prompt={"kind": "uniform", "lo": 32, "hi": 48},
            tenants=[{"name": "sys", "weight": 1.0,
                      "prefix_tokens": 16}]))
        reqs, _ = wl.requests(base_wall=0.0)
        heads = {tuple(r.prompt[:16].tolist()) for r in reqs[:10]}
        assert len(heads) == 1        # one shared system prefix
        reqs2, _ = wl.requests(base_wall=5000.0)
        assert np.array_equal(reqs[0].prompt, reqs2[0].prompt)


class TestTraceReplay:
    def _doc(self):
        return {"schema": "tpudist.events/1", "events": [
            {"t": 100.0, "kind": "enqueue", "trace": "a",
             "prompt_tokens": 8, "max_new": 16, "priority": 0,
             "rel_deadline_s": None},
            {"t": 100.5, "kind": "enqueue", "trace": "b",
             "prompt_tokens": 32, "max_new": 4, "priority": 1,
             "rel_deadline_s": 2.5},
            {"t": 100.1, "kind": "segment", "trace": "a", "src": "r0",
             "steps": 8, "spt": 0.004},
            {"t": 100.2, "kind": "segment", "trace": "a", "src": "r0",
             "steps": 8, "spt": 0.002},
            {"t": 100.3, "kind": "segment", "trace": "b", "src": "r1",
             "steps": 4, "spt": 0.01},
        ]}

    def test_workload_from_trace_normalizes_offsets(self):
        wl = workload_from_trace(self._doc())
        assert [w.at for w in wl.items] == [0.0, 0.5]
        assert wl.items[0].prompt_tokens == 8
        assert wl.items[1].priority == 1
        assert wl.items[1].rel_deadline_s == 2.5

    def test_trace_without_enqueues_is_an_error(self):
        with pytest.raises(ValueError, match="no replayable enqueue"):
            workload_from_trace({"events": [{"kind": "segment"}]})

    def test_service_rates_are_per_source_medians(self):
        rates = service_rates_from_trace(self._doc(), default=0.005)
        assert rates["*"] == pytest.approx(0.005)
        assert rates["r0"] == pytest.approx(0.003)   # median of 4ms/2ms
        assert rates["r1"] == pytest.approx(0.01)


def _passing_row(name: str) -> dict:
    """A summary row comfortably inside the named builtin envelope."""
    env = builtin(name).envelope
    return {"metric": f"scenario/{name}", "scenario": name,
            "lost_requests": 0, "p99_queue_wait_s": 0.05,
            "recovery_s": 5.0,
            "scale_ups": env.min_scale_ups, "drains": env.min_drains,
            "scale_ups_prefill": env.min_scale_ups_prefill,
            "scale_ups_decode": env.min_scale_ups_decode,
            "p99_ttft_s": 0.05,
            "priority_bad": 0, "replica_deaths": 0,
            "preemptions": env.min_preemptions,
            "p99_priority_wait_s": 0.05,
            "router_recoveries": env.min_router_recoveries,
            "quarantines": env.min_quarantines,
            "reinstated": env.min_reinstated,
            "corrupted_terminals": 0,
            "burn_rate_300s": 0.0,
            "decisions_completed": 500,
            "decisions_failed": 0,
            "alerts_fired": sorted(env.alerts.get("must_fire") or []),
            "envelope_ok": True,
            "violations": []}


class TestEnvelopeChecker:
    def test_check_row_rechecks_builtin_from_raw_fields(self):
        # the emitter says ok; the raw fields say otherwise — the
        # checker must recompute, not trust the flag
        row = _passing_row("steady_state")
        row["lost_requests"] = 3
        bad = check_row(row)
        assert bad and "lost_requests" in bad[0]

    def test_check_row_honors_embedded_verdict_for_unknown_scenario(self):
        row = {"scenario": "custom", "envelope_ok": False,
               "violations": ["p99 blew up"]}
        assert check_row(row) == ["p99 blew up"]
        assert check_row({"scenario": "custom", "envelope_ok": True}) == []

    def test_check_rows_demands_the_full_matrix(self):
        rows = [_passing_row(n) for n in names()]
        ok, report = check_rows(rows)
        assert ok, report
        ok, report = check_rows(rows[:-1])
        assert not ok
        assert any("missing" in line for line in report)
        ok, report = check_rows(rows[:3], min_scenarios=5,
                                require_builtin=False)
        assert not ok
        assert any("only 3" in line for line in report)

    def test_scenario_rows_skips_noise(self, tmp_path):
        path = tmp_path / "rows.jsonl"
        path.write_text("\n".join([
            "some log line",
            json.dumps({"metric": "serve/throughput", "value": 1.0}),
            json.dumps(_passing_row("steady_state")),
            "{not json",
        ]) + "\n")
        rows = scenario_rows(str(path))
        assert [r["scenario"] for r in rows] == ["steady_state"]


class TestVirtualClock:
    def test_advance_and_wall(self):
        from tpudist.sim.simulator import VirtualClock

        vc = VirtualClock(wall_base=500.0)
        assert vc.monotonic() == 0.0
        vc.advance(1.5)
        assert vc.monotonic() == pytest.approx(1.5)
        assert vc.wall() == pytest.approx(501.5)
        with pytest.raises(ValueError):
            vc.advance(-0.1)


class TestSimFabricChaos:
    def test_outage_window_gates_client_verbs_not_leases(self):
        from tpudist.runtime.faults import FaultInjected
        from tpudist.sim.fabric import SimFabric
        from tpudist.sim.simulator import VirtualClock

        vc = VirtualClock()
        fab = SimFabric(clock=vc.monotonic)
        fab.add_outage(1.0, 2.0)
        fab.set("k", b"v")                  # before the window: fine
        vc.advance(1.5)
        for op in (lambda: fab.set("k", b"w"), lambda: fab.get("k"),
                   lambda: fab.keys(), lambda: fab.delete("k"),
                   lambda: fab.add("c", 1), lambda: fab.live()):
            with pytest.raises(FaultInjected):
                op()
        # lease flips model SERVER-side state: outage-exempt
        fab.up("ns:r0")
        fab.down("ns:r0")
        vc.advance(1.0)                     # past the window
        assert fab.get("k") == b"v"         # the blind write never landed

    def test_outage_needs_clock_and_sane_window(self):
        from tpudist.sim.fabric import SimFabric
        from tpudist.sim.simulator import VirtualClock

        with pytest.raises(ValueError, match="needs a clock"):
            SimFabric().add_outage(0.0, 1.0)
        with pytest.raises(ValueError, match="bad outage window"):
            SimFabric(clock=VirtualClock().monotonic).add_outage(2.0, 1.0)


class TestFleetSim:
    def _tiny(self, **over):
        base = {"name": "tiny", "duration_s": 4.0,
                "arrival": {"kind": "constant", "rate": 6.0},
                "max_new": {"kind": "const", "value": 8},
                "seed": 21,
                "envelope": {"max_lost": 0, "max_scale_ups": 0}}
        base.update(over)
        return ScenarioSpec.from_dict(base)

    def test_small_scenario_completes_everything(self):
        from tpudist.sim.simulator import FleetSim

        sim = FleetSim(self._tiny())
        row = sim.run()
        assert row["requests"] > 0
        assert row["lost_requests"] == 0
        assert row["completed_ok"] == row["requests"]
        assert row["decisions_completed"] == row["requests"]
        assert row["envelope_ok"], row["violations"]
        # virtual seconds elapsed, in a hurry
        assert row["virtual_s"] >= 4.0
        assert row["sim_wall_s"] < row["virtual_s"]

    def test_same_seed_same_decisions(self):
        from tpudist.sim.simulator import FleetSim

        a = FleetSim(self._tiny()).run()
        b = FleetSim(self._tiny()).run()
        for k in ("requests", "completed_ok", "decisions_completed",
                  "p99_queue_wait_s"):
            assert a[k] == b[k], k

    def test_overload_scales_up_with_real_policy(self):
        from tpudist.sim.simulator import FleetSim

        spec = self._tiny(
            name="hot", duration_s=10.0, seed=22,
            arrival={"kind": "constant", "rate": 40.0},
            fleet={"replicas": 1, "autoscale": {
                "min_replicas": 1, "max_replicas": 3,
                "target_wait_s": 0.3, "low_wait_s": 0.05,
                "quantile": 0.9, "breach_polls": 2, "idle_polls": 50,
                "up_cooldown_s": 5.0, "down_cooldown_s": 600.0,
                "poll_s": 0.5, "max_metric_age_s": 10.0}},
            envelope={"max_lost": 0, "min_scale_ups": 1})
        row = FleetSim(spec).run()
        assert row["scale_ups"] >= 1
        assert row["lost_requests"] == 0
        assert row["final_replicas"] > 1
        assert row["envelope_ok"], row["violations"]

    def test_adversarial_deadlines_shed_not_fail(self):
        from tpudist.sim.simulator import FleetSim

        spec = self._tiny(
            name="storm", duration_s=8.0, seed=23,
            arrival={"kind": "constant", "rate": 30.0},
            deadline={"kind": "adversarial", "tight_frac": 0.4,
                      "tight_s": 0.02, "loose_s": 60.0},
            envelope={"max_lost": 0})
        row = FleetSim(spec).run()
        assert row["lost_requests"] == 0
        assert row["decisions_failed"] == 0
        # impossible deadlines resolve as shed/timeout decisions, and
        # every loose-deadline request still completes
        assert row["decisions_shed"] + row["decisions_timeout"] > 0
        assert row["completed_ok"] > 0


class TestFleetSimChaos:
    """The FaultScript verbs drive the REAL recovery paths on the
    virtual clock: replica death -> redispatch, coord brownout ->
    buffered ride-out, router kill -> journal recovery."""

    def _tiny(self, **over):
        base = {"name": "chaos-tiny", "duration_s": 4.0,
                "arrival": {"kind": "constant", "rate": 6.0},
                "max_new": {"kind": "const", "value": 8},
                "seed": 41, "fleet": {"replicas": 2},
                "envelope": {"max_lost": 0}}
        base.update(over)
        return ScenarioSpec.from_dict(base)

    def test_replica_kill_redispatches_everything(self):
        from tpudist.sim.simulator import FleetSim

        spec = self._tiny(
            name="chaos-kill", seed=42,
            faults=[{"kind": "kill_replica", "at_s": 1.0, "rid": "r1"}],
            envelope={"max_lost": 0, "max_replica_deaths": 1,
                      "decisions": {"failed": {"max": 0}}})
        row = FleetSim(spec).run()
        assert row["lost_requests"] == 0
        assert row["replica_deaths"] == 1
        assert row["decisions_completed"] == row["requests"]
        assert row["envelope_ok"], row["violations"]

    def test_coord_brownout_is_stale_not_lost(self):
        from tpudist.sim.simulator import FleetSim

        spec = self._tiny(
            name="chaos-brownout", seed=43,
            faults=[{"kind": "coord_brownout", "at_s": 1.0,
                     "for_s": 1.5}],
            envelope={"max_lost": 0, "max_replica_deaths": 0,
                      "decisions": {"failed": {"max": 0}}})
        sim = FleetSim(spec)
        row = sim.run()
        assert row["lost_requests"] == 0
        assert row["replica_deaths"] == 0
        assert row["decisions_completed"] == row["requests"]
        # the brownout buffers drained before the fleet shut down
        assert all(not r._done_buf for r in sim.replicas)
        assert row["envelope_ok"], row["violations"]

    def test_router_kill_recovers_with_no_dups(self):
        from tpudist.sim.simulator import FleetSim

        spec = self._tiny(
            name="chaos-failover", seed=44,
            faults=[{"kind": "kill_router", "at_poll": 20}],
            envelope={"max_lost": 0, "min_router_recoveries": 1,
                      "decisions": {"failed": {"max": 0}}})
        sim = FleetSim(spec)
        row = sim.run()
        assert row["lost_requests"] == 0
        assert row["router_recoveries"] == 1
        # exactly-once delivery across the crash: every request decided
        # exactly one terminal, and the journal compacted to empty
        assert row["decisions_completed"] == row["requests"]
        assert sim.fabric.keys(f"{sim.ns}/journal/") == []
        assert row["envelope_ok"], row["violations"]

    def test_drop_heartbeats_comes_back(self):
        from tpudist.sim.simulator import FleetSim

        # a false-positive-death shape: the lease lapses for 1 s, the
        # router redispatches, the replica resumes — nothing is lost
        # and nothing double-delivers
        spec = self._tiny(
            name="chaos-hb", seed=45,
            faults=[{"kind": "drop_heartbeats", "at_s": 1.0,
                     "for_s": 1.0, "rid": "r1"}],
            envelope={"max_lost": 0,
                      "decisions": {"failed": {"max": 0}}})
        row = FleetSim(spec).run()
        assert row["lost_requests"] == 0
        assert row["decisions_completed"] == row["requests"]
        assert row["envelope_ok"], row["violations"]

    def test_silent_corruption_quarantines_before_delivery(self):
        """ISSUE 13's sim acceptance: a replica flipping bits in its
        committed completions is struck into quarantine BEFORE any
        corrupt payload reaches a caller, its work is redispatched,
        and — the injection being capped — golden probes reinstate it.
        Zero lost, zero corrupted terminals, nothing killed."""
        from tpudist.sim.simulator import FleetSim

        row = FleetSim(builtin("silent_corruption")).run()
        assert row["lost_requests"] == 0
        assert row["corrupted_terminals"] == 0
        assert row["checksum_mismatches"] >= 3
        assert row["quarantines"] >= 1
        assert row["reinstated"] >= 1
        assert row["retired"] == 0
        assert row["replica_deaths"] == 0
        assert row["decisions_completed"] == row["requests"]
        assert row["envelope_ok"], row["violations"]


# a dedicated test already runs these four (steady_state and
# coord_brownout in test_alerts.py, the other two below)
_HELD_ELSEWHERE = {"steady_state", "coord_brownout", "silent_corruption",
                   "priority_saturation"}
# what a scenario must show beyond its own envelope
_BUILTIN_EXTRA = {
    "cold_prefix_tenants": {"global_hit_rate": lambda v: v >= 0.8,
                            "tier_hit_blocks": lambda v: v > 0,
                            "alerts_fired":
                                lambda v: v == ["TierHeadroomLow"]},
    "replica_death_storm": {"replica_deaths": lambda v: v == 2,
                            "scale_ups": lambda v: v >= 1,
                            "alerts_fired": lambda v: "ReplicaLost" in v},
    "router_failover": {"router_recoveries": lambda v: v >= 1},
}


class TestBuiltinMatrix:
    @pytest.mark.parametrize("name", sorted(set(BUILTIN) - _HELD_ELSEWHERE))
    def test_builtin_scenario_meets_its_envelope(self, name):
        """Every named scenario, through the real router and autoscaler
        on the virtual clock, stays inside its own SLO envelope — checked
        from the row's raw fields, as ``python -m tpudist.sim.envelope``
        does, not from the flag the simulator set — loses no request and
        fires exactly the alerts its envelope allows."""
        from tpudist.sim.simulator import FleetSim

        row = FleetSim(builtin(name)).run()
        assert check_row(row) == [], row["violations"]
        assert row["envelope_ok"] is True
        assert row["lost_requests"] == 0
        for field, ok in _BUILTIN_EXTRA.get(name, {}).items():
            assert ok(row[field]), (field, row[field])


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="recorded live-run fixture missing")
class TestSimReplayAgreement:
    """The acceptance check, offline: replaying the checked-in recorded
    live run (a 1-replica fleet breaching a millisecond wait target)
    must reproduce the autoscaler's scale-up decision sequence within
    one poll of the first breach — pinned to a fixture so it regresses
    loudly without needing a live fleet."""

    @staticmethod
    def _first_up_rel(decision_log, action_seq, target_wait_s):
        breaches = [r["poll"] for r in decision_log
                    if r["wait_q"] > target_wait_s]
        ups = [a["poll"] for a in action_seq if a["kind"] == "up"]
        if not breaches or not ups:
            return None
        return ups[0] - breaches[0]

    def test_replay_matches_recorded_decisions(self):
        from tpudist.sim.simulator import FleetSim

        with open(FIXTURE) as f:
            fx = json.load(f)
        assert fx["schema"] == "tpudist.sim_replay_fixture/1"
        sim = FleetSim.from_trace(fx["events"],
                                  autoscale=fx["autoscale"], replicas=1)
        row = sim.run()
        assert row["lost_requests"] == 0

        live_ups = sum(1 for a in fx["action_seq"] if a["kind"] == "up")
        sim_actions = sim.scaler.action_seq()
        sim_ups = sum(1 for a in sim_actions if a["kind"] == "up")
        assert sim_ups == live_ups

        target = fx["autoscale"]["target_wait_s"]
        live_rel = self._first_up_rel(fx["decision_log"],
                                      fx["action_seq"], target)
        sim_rel = self._first_up_rel(sim.scaler.decision_log,
                                     sim_actions, target)
        assert live_rel is not None and sim_rel is not None
        assert abs(live_rel - sim_rel) <= 1

    def test_replay_serves_at_recorded_pace(self):
        rates = service_rates_from_trace(json.load(open(FIXTURE))["events"])
        # the live replica stamped spt on its segments; the replayed r0
        # must inherit it rather than the synthetic default
        assert "r0" in rates
        assert 0.0 < rates["r0"] < 1.0


class TestTimelineSummary:
    def _timelines(self):
        return {"t1": [
            {"t": 0.0, "kind": "enqueue"},
            {"t": 1.0, "kind": "admit"},
            {"t": 1.5, "kind": "segment", "steps": 5},
            {"t": 2.5, "kind": "segment", "steps": 10},
            {"t": 3.0, "kind": "done"},
        ], "t2": [
            {"t": 0.0, "kind": "enqueue"},
            {"t": 0.5, "kind": "dispatch"},
            {"t": 0.6, "kind": "redispatch"},
            {"t": 2.0, "kind": "admit"},
            {"t": 4.0, "kind": "timeout"},
        ], None: [{"t": 0.0, "kind": "noise"}]}

    def test_stage_percentiles(self):
        from tpudist.obs.timeline import summarize_timelines

        s = summarize_timelines(self._timelines())
        assert s["traces"] == 2
        assert s["enqueue_to_admit"]["n"] == 2
        assert s["enqueue_to_admit"]["max"] == pytest.approx(2.0)
        assert s["admit_to_first_token"]["n"] == 1
        assert s["admit_to_first_token"]["p50"] == pytest.approx(0.5)
        # one gap of 1.0s over the later segment's 10 steps
        assert s["inter_token"]["n"] == 1
        assert s["inter_token"]["p50"] == pytest.approx(0.1)
        assert s["enqueue_to_terminal"]["max"] == pytest.approx(4.0)
        assert s["redispatches"] == {0: 1, 1: 1}

    def test_render_handles_empty_stages(self):
        from tpudist.obs.timeline import (
            render_summary, summarize_timelines)

        s = summarize_timelines({"t": [{"t": 0.0, "kind": "enqueue"}]})
        lines = render_summary(s)
        assert any("no samples" in line for line in lines)

    def test_cli_summary_flag(self, tmp_path, capsys):
        from tpudist.obs import timeline as tl

        path = tmp_path / "events.json"
        path.write_text(json.dumps({
            "schema": "tpudist.events/1",
            "events": [dict(e, trace="t1", i=i) for i, e in
                       enumerate(self._timelines()["t1"])]}))
        assert tl.main([str(path), "--summary"]) == 0
        out = capsys.readouterr().out
        assert "per-stage latency percentiles" in out
        assert "enqueue_to_admit" in out


class TestSimCLI:
    def test_spec_file_run_emits_scenario_row(self, tmp_path, capsys):
        from tpudist.sim.__main__ import main as sim_main
        from tpudist.sim.envelope import main as env_main

        spec = {"name": "cli-tiny", "duration_s": 3.0,
                "arrival": {"kind": "constant", "rate": 5.0},
                "max_new": {"kind": "const", "value": 8}, "seed": 31,
                "envelope": {"max_lost": 0}}
        spath = tmp_path / "spec.json"
        spath.write_text(json.dumps(spec))
        jpath = tmp_path / "rows.jsonl"
        assert sim_main(["--spec", str(spath), "--check",
                         "--jsonl", str(jpath)]) == 0
        row = json.loads(capsys.readouterr().out.strip())
        assert row["metric"] == "scenario/cli-tiny"
        assert row["envelope_ok"] is True
        # the written JSONL gates through the shared checker (the
        # builtin-matrix demand relaxed: this is a one-off spec)
        assert env_main([str(jpath), "--min-scenarios", "1",
                         "--no-require-builtin"]) == 0

    def test_check_exit_code_on_violation(self, tmp_path, capsys):
        from tpudist.sim.__main__ import main as sim_main

        spec = {"name": "cli-bad", "duration_s": 3.0,
                "arrival": {"kind": "constant", "rate": 5.0},
                "max_new": {"kind": "const", "value": 8}, "seed": 32,
                "envelope": {"min_scale_ups": 5}}   # cannot happen
        spath = tmp_path / "spec.json"
        spath.write_text(json.dumps(spec))
        assert sim_main(["--spec", str(spath), "--check"]) == 1
        assert "envelope VIOLATED" in capsys.readouterr().err


class TestPrioritySaturation:
    """ISSUE 19's ``priority_saturation`` builtin end-to-end: an
    oversaturated single replica in migrate mode must preempt
    best-effort decodes so priority traffic meets its queue-wait
    ceiling — and the SAME workload with the preemption knob off must
    fail exactly those envelope gates (proof the gate is real, not
    vacuously green)."""

    def test_builtin_envelope_passes_with_preemption(self):
        from tpudist.sim.simulator import FleetSim

        row = FleetSim(builtin("priority_saturation")).run()
        assert row["envelope_ok"], row["violations"]
        assert row["preemptions"] >= 5
        assert row["preempt_resumes"] >= 1
        assert row["lost_requests"] == 0
        assert row["p99_priority_wait_s"] <= 0.5

    def test_degrade_baseline_fails_the_priority_gates(self):
        from tpudist.sim.simulator import FleetSim

        raw = dict(BUILTIN["priority_saturation"])
        raw["fleet"] = dict(raw["fleet"], preempt="degrade")
        row = FleetSim(ScenarioSpec.from_dict(raw)).run()
        assert not row["envelope_ok"]
        viol = " ".join(row["violations"])
        assert "p99_priority_wait_s" in viol
        assert "preemptions" in viol
