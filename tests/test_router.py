"""Fault-tolerant serve fleet (ISSUE 6) and its elastic extension
(ISSUE 7): least-loaded pick, wire round-trip, the no-hang bound,
redispatch-cap exhaustion, SLO admission, live join, hot-swap steering —
and the acceptance end-to-ends: SIGKILL a replica mid-decode (every
admitted request still returns a greedy exact-match Completion with no
orphaned KV blocks), then join a fresh replica and roll a weight
hot-swap through the survivors with zero lost requests."""

import json
import time

import numpy as np
import pytest

from tpudist.runtime import wire
from tpudist.runtime.router import (
    Router, _decode_request, _encode_completion, _encode_request,
    build_tiny_lm, exit_reports, launch_local_fleet, roll_weights,
    scale_fleet, stop_fleet, wait_live, wait_swapped)


def _coord_pair():
    try:
        from tpudist.runtime.coord import CoordClient, CoordServer

        server = CoordServer(0)
    except Exception as e:  # NativeUnavailable or build failure
        pytest.skip(f"native coord store unavailable: {e}")
    return server, CoordClient("127.0.0.1", server.port)


def _requests(n):
    """The fleet workload: varied prompt lengths and budgets, seeded so
    the uninterrupted reference run is reproducible."""
    from tpudist.models.serving import Request

    rng = np.random.default_rng(0)
    return [Request(rng.integers(0, 64, size=4 + i).astype(np.int32),
                    20 + 2 * i, rid=f"q{i}") for i in range(n)]


class TestPick:
    def _router(self):
        return Router(None, use_health=False)

    def test_prefers_fewest_outstanding(self):
        r = self._router()
        loads = {"a": {"queue_depth": 0.0, "queue_wait_mean": 0.0,
                       "kv_blocks_free": 10.0, "rejected": 0.0},
                 "b": {"queue_depth": 0.0, "queue_wait_mean": 0.0,
                       "kv_blocks_free": 10.0, "rejected": 0.0}}
        assert r._pick(["a", "b"], loads, {"a": 2, "b": 1}) == "b"
        # published queue depth counts the same as own assignments
        loads["b"]["queue_depth"] = 3.0
        assert r._pick(["a", "b"], loads, {"a": 2}) == "a"

    def test_tiebreak_queue_wait_then_free_blocks(self):
        r = self._router()
        loads = {"a": {"queue_depth": 0.0, "queue_wait_mean": 0.5,
                       "kv_blocks_free": 50.0},
                 "b": {"queue_depth": 0.0, "queue_wait_mean": 0.1,
                       "kv_blocks_free": 2.0}}
        assert r._pick(["a", "b"], loads, {}) == "b"
        loads["b"]["queue_wait_mean"] = 0.5
        assert r._pick(["a", "b"], loads, {}) == "a"

    def test_dense_replica_sorts_as_infinite_blocks(self):
        r = self._router()
        loads = {"paged": {"queue_depth": 0.0, "queue_wait_mean": 0.0,
                           "kv_blocks_free": 100.0},
                 "dense": {"queue_depth": 0.0, "queue_wait_mean": 0.0,
                           "kv_blocks_free": None}}
        assert r._pick(["paged", "dense"], loads, {}) == "dense"

    def test_no_candidates(self):
        assert self._router()._pick([], {}, {}) is None


class TestWireFormat:
    def test_request_roundtrip(self):
        from tpudist.models.serving import Completion, Request

        req = Request(np.array([3, 1, 4], np.int32), 9, rid="caller-id",
                      deadline_s=123.5)
        got = _decode_request(_encode_request("00000007", req))
        np.testing.assert_array_equal(got.prompt, req.prompt)
        assert got.max_new_tokens == 9
        assert got.rid == "00000007"  # router key, not caller rid
        assert got.deadline_s == 123.5
        assert got.trace is None   # traceless stays traceless

        comp = Completion(rid="00000007", prompt=req.prompt,
                          tokens=np.array([5, 6], np.int32),
                          reason="length")
        from tpudist.runtime import wire

        d = wire.decode_record(_encode_completion("r1", comp),
                               expect="completion")
        assert d == {"key": "00000007", "tokens": [5, 6],
                     "reason": "length", "replica": "r1"}

    def test_request_roundtrip_preserves_trace(self):
        from tpudist.models.serving import Request
        from tpudist.obs.events import TraceContext

        tc = TraceContext.mint("00000003", parent="span-9")
        req = Request(np.array([2, 7], np.int32), 5, rid="caller",
                      trace=tc)
        got = _decode_request(_encode_request("00000003", req))
        assert got.trace is not None
        assert got.trace.trace_id == tc.trace_id
        assert got.trace.parent == "span-9"
        assert got.trace.enqueued_at == tc.enqueued_at


def test_spawn_replica_pins_child_platform(monkeypatch):
    """A chip belongs to one process: the replica child gets the platform
    the CALLER asked for, even when the parent's environment names a
    chip platform (setdefault used to let the parent's value through)."""
    from tpudist.runtime import router

    seen = {}
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(router.subprocess, "Popen",
                        lambda argv, env: seen.update(argv=argv, env=env))
    router._spawn_replica("127.0.0.1:1", 3, namespace="ns", platform="cpu",
                          env_extra={"TPUDIST_X": 1})
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"
    assert seen["env"]["TPUDIST_X"] == "1"
    assert "r3" in seen["argv"]


class TestNoHang:
    def test_timeout_instead_of_hang_with_no_fleet(self):
        server, client = _coord_pair()
        router = Router(client, namespace="empty-fleet", use_health=False,
                        poll_s=0.01)
        t0 = time.monotonic()
        with pytest.raises(TimeoutError, match="1 of 1"):
            router.run(_requests(1), timeout_s=0.5)
        assert time.monotonic() - t0 < 5.0


# -- elastic membership: unit layer over an in-memory coord double ---------

class FakeCoord:
    """In-memory stand-in for CoordClient — just the verbs Router and
    HealthMonitor reach for (keys/get/set/delete/live), plus an
    ``on_set`` hook so a test can inject a fleet-wide event (every
    replica dying at once) at an exact point in the dispatch
    sequence."""

    def __init__(self):
        self.kv: dict[str, bytes] = {}
        self.live_set: set[str] = set()
        self.on_set = None

    def keys(self, prefix=""):
        return [k for k in list(self.kv) if k.startswith(prefix)]

    def get(self, key):
        return self.kv.get(key)

    def set(self, key, value):
        self.kv[key] = value
        if self.on_set is not None:
            self.on_set(key, value)

    def delete(self, key):
        self.kv.pop(key, None)

    def add(self, key, delta):
        self.counters = getattr(self, "counters", {})
        self.counters[key] = self.counters.get(key, 0) + int(delta)
        return self.counters[key]

    def live(self):
        return set(self.live_set)


def _register(fc, ns, rid, rank):
    fc.kv[f"{ns}/replica/{rid}"] = json.dumps(
        {"replica_id": rid, "rank": rank}).encode()
    fc.live_set.add(f"{ns}:{rid}")


def _publish(fc, ns, rank, *, gauges=None, hist_wait=None, age_s=0.0):
    """One published metrics snapshot, exactly the MetricsPublisher
    shape ``collect`` parses; ``age_s`` backdates ``published_at``."""
    snap = {"rank": rank, "published_at": time.time() - age_s,
            "gauges": {name: {"value": v}
                       for name, v in (gauges or {}).items()},
            "counters": {}, "histograms": {}}
    if hist_wait is not None:
        snap["histograms"]["serve/queue_wait_s"] = hist_wait
    fc.kv[f"{ns}/metrics/{rank}"] = json.dumps(snap).encode()


def _fat_wait_hist(idx=6, count=100):
    """Every queue-wait observation in one bucket at ``2**idx`` seconds
    — a power of the growth factor, so EVERY quantile is exactly
    ``2**idx`` (hist_quantile returns bucket lower bounds)."""
    v = float(2.0 ** idx)
    return {"growth": 2.0, "count": count, "sum": v * count, "zero": 0,
            "min": v, "max": v, "buckets": {str(idx): count}}


def _counter(name):
    from tpudist import obs

    return obs.snapshot()["counters"].get(name, {}).get("value", 0)


def _entry(req, attempts=0):
    return {"req": req, "assigned": None, "attempts": attempts}


class TestElasticUnit:
    def test_simultaneous_two_death_hits_redispatch_cap(self):
        """BOTH replicas die at once with ``max_redispatch=0``: every
        outstanding request must surface ``reason="failed"`` immediately
        (no hang, no silent drop), with both deaths and all four
        redispatch attempts counted."""
        fc = FakeCoord()
        ns = "cap"
        _register(fc, ns, "a", 0)
        _register(fc, ns, "b", 1)
        inbox_writes = []

        def on_set(key, value):
            if key.startswith(f"{ns}/inbox/"):
                inbox_writes.append(key)
                if len(inbox_writes) == 4:   # whole fleet dies at once
                    fc.live_set.clear()

        fc.on_set = on_set
        router = Router(fc, namespace=ns, use_health=False,
                        max_redispatch=0, poll_s=0.001)
        d0 = _counter("router/replica_deaths")
        r0 = _counter("router/redispatched")
        comps = router.run(_requests(4), timeout_s=10.0)
        assert [c.reason for c in comps] == ["failed"] * 4
        assert sorted(c.rid for c in comps) == [f"q{i}" for i in range(4)]
        assert all(c.tokens.size == 0 for c in comps)
        assert _counter("router/replica_deaths") - d0 == 2
        assert _counter("router/redispatched") - r0 == 4

    def test_slo_shed_predicted_miss(self):
        """The best candidate's published p99 queue wait already blows
        the deadline: the request is shed AT THE ROUTER (reason="shed")
        before any replica pays a prefill."""
        from tpudist.models.serving import Request

        fc = FakeCoord()
        ns = "slo"
        _register(fc, ns, "a", 0)
        _publish(fc, ns, 0, hist_wait=_fat_wait_hist(idx=6))  # p99 = 64s
        router = Router(fc, namespace=ns, use_health=False, poll_s=0.001)
        s0 = _counter("router/slo_shed")
        req = Request(np.arange(4, dtype=np.int32), 8, rid="doomed",
                      deadline_s=time.time() + 5.0)
        comps = router.run([req], timeout_s=10.0)
        assert comps[0].reason == "shed" and comps[0].rid == "doomed"
        assert comps[0].tokens.size == 0
        assert _counter("router/slo_shed") - s0 == 1
        assert fc.keys(f"{ns}/inbox/") == []   # never cost a prefill

    def test_slo_admission_scope(self):
        """Shed is ONLY for first-dispatch deadline requests whose miss
        is predicted: no-deadline and far-deadline requests dispatch
        normally, and an already-redispatched request (sunk prefill
        cost) races its deadline instead of being shed."""
        from tpudist.models.serving import Request

        fc = FakeCoord()
        ns = "slo2"
        _register(fc, ns, "a", 0)
        _publish(fc, ns, 0, hist_wait=_fat_wait_hist(idx=6))  # p99 = 64s
        router = Router(fc, namespace=ns, use_health=False)
        prompt = np.arange(4, dtype=np.int32)
        entries = {
            "00000000": _entry(Request(prompt, 8, rid="no-deadline")),
            "00000001": _entry(Request(prompt, 8, rid="far",
                                       deadline_s=time.time() + 1e4)),
            "00000002": _entry(Request(prompt, 8, rid="retry",
                                       deadline_s=time.time() + 5.0),
                               attempts=1),
        }
        done = {}
        router._poll(entries, done, lambda k, c: done.__setitem__(k, c))
        assert done == {}                              # nothing shed
        assert all(e["assigned"] == "a" for e in entries.values())
        assert len(fc.keys(f"{ns}/inbox/a/")) == 3

    def test_late_registration_counts_as_join(self):
        """Membership is re-read every poll: the first poll's live set
        is the baseline fleet, every later appearance is a JOIN —
        counted once, then known."""
        fc = FakeCoord()
        ns = "join"
        _register(fc, ns, "a", 0)
        router = Router(fc, namespace=ns, use_health=False)
        j0 = _counter("router/joins")
        router._poll({}, {}, None)
        assert _counter("router/joins") - j0 == 0   # baseline, not a join
        _register(fc, ns, "b", 1)
        router._poll({}, {}, None)
        assert _counter("router/joins") - j0 == 1
        router._poll({}, {}, None)                  # no double count
        assert _counter("router/joins") - j0 == 1
        assert router._known == {"a", "b"}

    def test_swapping_replica_is_steered_around(self):
        """A replica advertising ``serve/swapping`` has paused admission
        to drain for a weight rebind: the router must route around it —
        and when EVERY candidate is mid-swap, requests wait rather than
        fail."""
        from tpudist.models.serving import Request

        fc = FakeCoord()
        ns = "steer"
        _register(fc, ns, "a", 0)
        _register(fc, ns, "b", 1)
        # a is otherwise the obvious pick (idle) but is mid-hot-swap
        _publish(fc, ns, 0, gauges={"serve/swapping": 1.0,
                                    "serve/queue_depth": 0.0})
        _publish(fc, ns, 1, gauges={"serve/swapping": 0.0,
                                    "serve/queue_depth": 5.0})
        router = Router(fc, namespace=ns, use_health=False)
        prompt = np.arange(4, dtype=np.int32)
        entries = {"00000000": _entry(Request(prompt, 8, rid="x"))}
        router._poll(entries, {}, None)
        assert entries["00000000"]["assigned"] == "b"
        _publish(fc, ns, 1, gauges={"serve/swapping": 1.0})
        entries2 = {"00000001": _entry(Request(prompt, 8, rid="y"))}
        done = {}
        router._poll(entries2, done,
                     lambda k, c: done.__setitem__(k, c))
        assert entries2["00000001"]["assigned"] is None and done == {}

    def test_stale_publisher_steers_routing_without_a_death(self):
        """A replica that published then went quiet (the PUBLISH_DROP
        shape) goes ``stale`` in the health verdict: the router stops
        admitting to it but must NOT declare it dead — its heartbeat is
        still flowing and its in-flight work will land."""
        from tpudist.models.serving import Request

        fc = FakeCoord()
        ns = "quiet"
        _register(fc, ns, "a", 0)
        _register(fc, ns, "b", 1)
        _publish(fc, ns, 0, age_s=0.0)
        _publish(fc, ns, 1, age_s=10.0)   # published, then went quiet
        router = Router(fc, namespace=ns, stale_after_s=3.0,
                        lost_after_s=1e6, use_health=True)
        d0 = _counter("router/replica_deaths")
        prompt = np.arange(4, dtype=np.int32)
        entries = {"00000000": _entry(Request(prompt, 8, rid="x"))}
        router._poll(entries, {}, None)
        assert router._health.verdict()["stale"] == ["1"]
        assert entries["00000000"]["assigned"] == "a"
        assert _counter("router/replica_deaths") - d0 == 0
        assert "b" not in router._dead


class TestControlPlaneUnit:
    """PR 9's router-side control-plane mechanisms, driven against
    FakeCoord: join grace, drain accounting/steering, pool pinning,
    fleet-wide degradation clamp, and the replica-index add-chain."""

    def _reg_only(self, fc, ns, rid, rank, pool=None):
        """A registration WITHOUT a heartbeat lease — the coord-store
        state of a joiner that registered and is still compiling."""
        info = {"replica_id": rid, "rank": rank}
        if pool is not None:
            info["pool"] = pool
        fc.kv[f"{ns}/replica/{rid}"] = json.dumps(info).encode()

    def test_join_grace_forgives_never_live_registration(self):
        """A registered joiner with no heartbeat yet must NOT be swept
        as dead inside the grace window — sweeping it deletes the
        registration out from under the warming process (the PR 7
        false-positive-death shape)."""
        fc = FakeCoord()
        ns = "grace"
        _register(fc, ns, "a", 0)
        router = Router(fc, namespace=ns, use_health=False,
                        join_grace_s=30.0)
        router._poll({}, {}, None)              # baseline fleet
        self._reg_only(fc, ns, "slow", 1)       # mid-warmup joiner
        d0 = _counter("router/replica_deaths")
        router._poll({}, {}, None)
        router._poll({}, {}, None)
        assert "slow" not in router._dead
        assert _counter("router/replica_deaths") - d0 == 0
        assert f"{ns}/replica/slow" in fc.kv    # registration survives

    def test_join_grace_expiry_sweeps_dead_joiner(self):
        """Past the grace window a never-live registration IS swept: a
        joiner that died during warmup must not pin its registration
        (and the coordination residue around it) forever."""
        fc = FakeCoord()
        ns = "grace2"
        _register(fc, ns, "a", 0)
        router = Router(fc, namespace=ns, use_health=False,
                        join_grace_s=0.0)
        router._poll({}, {}, None)
        self._reg_only(fc, ns, "stillborn", 1)
        d0 = _counter("router/replica_deaths")
        router._poll({}, {}, None)
        assert "stillborn" in router._dead
        assert _counter("router/replica_deaths") - d0 == 1
        assert f"{ns}/replica/stillborn" not in fc.kv

    def test_ever_live_member_gets_no_grace(self):
        """Grace shields only NEVER-live joiners: once a replica has
        heartbeated, a lapsed lease means death NOW — stretching kill
        detection by the grace window would stall redispatch."""
        fc = FakeCoord()
        ns = "grace3"
        _register(fc, ns, "a", 0)
        _register(fc, ns, "b", 1)
        router = Router(fc, namespace=ns, use_health=False,
                        join_grace_s=1e6)
        router._poll({}, {}, None)
        fc.live_set.discard(f"{ns}:b")          # lease lapses
        d0 = _counter("router/replica_deaths")
        router._poll({}, {}, None)
        assert "b" in router._dead
        assert _counter("router/replica_deaths") - d0 == 1

    def test_draining_departure_is_a_drain_not_a_death(self):
        """A replica marked draining is steered around immediately, and
        its eventual departure ticks ``router/drains`` — not the death
        counter that pages an operator."""
        from tpudist.models.serving import Request

        fc = FakeCoord()
        ns = "drainacct"
        _register(fc, ns, "a", 0)
        _register(fc, ns, "b", 1)
        router = Router(fc, namespace=ns, use_health=False)
        fc.kv[f"{ns}/draining/a"] = b"1"
        prompt = np.arange(4, dtype=np.int32)
        entries = {"00000000": _entry(Request(prompt, 8, rid="x"))}
        router._poll(entries, {}, None)
        assert entries["00000000"]["assigned"] == "b"   # steered away
        fc.live_set.discard(f"{ns}:a")          # drain completes
        d0 = _counter("router/replica_deaths")
        g0 = _counter("router/drains")
        router._poll({}, {}, None)
        assert _counter("router/drains") - g0 == 1
        assert _counter("router/replica_deaths") - d0 == 0
        assert f"{ns}/draining/a" not in fc.kv  # residue swept

    def test_pool_pin_filters_candidates(self):
        """The ``{ns}/pool`` key pins traffic to one pool tag; absent,
        every pool serves (pre-blue-green fleets keep working)."""
        from tpudist.models.serving import Request

        fc = FakeCoord()
        ns = "pool"
        self._reg_only(fc, ns, "a", 0, pool="blue")
        self._reg_only(fc, ns, "b", 1, pool="green")
        fc.live_set |= {f"{ns}:a", f"{ns}:b"}
        router = Router(fc, namespace=ns, use_health=False)
        prompt = np.arange(4, dtype=np.int32)
        fc.kv[f"{ns}/pool"] = b"green"
        e1 = {"00000000": _entry(Request(prompt, 8, rid="x"))}
        router._poll(e1, {}, None)
        assert e1["00000000"]["assigned"] == "b"
        fc.kv[f"{ns}/pool"] = b"blue"
        e2 = {"00000001": _entry(Request(prompt, 8, rid="y"))}
        router._poll(e2, {}, None)
        assert e2["00000001"]["assigned"] == "a"

    def test_degraded_fleet_clamps_best_effort_at_router(self):
        """When a candidate advertises ``serve/degraded``, the router
        clamps best-effort (priority <= 0) budgets at dispatch so the
        overload tier shrinks work before the replica must shed it —
        priority traffic keeps its full budget."""
        from tpudist.models.serving import Request

        fc = FakeCoord()
        ns = "degr"
        _register(fc, ns, "a", 0)
        _publish(fc, ns, 0, gauges={"serve/degraded": 1.0})
        router = Router(fc, namespace=ns, use_health=False,
                        degrade_max_new=4)
        prompt = np.arange(4, dtype=np.int32)
        c0 = _counter("router/degrade_clamped")
        entries = {
            "00000000": _entry(Request(prompt, 16, rid="cheap")),
            "00000001": _entry(Request(prompt, 16, rid="vip",
                                       priority=1)),
        }
        router._poll(entries, {}, None)
        sent = {wire.decode_record(fc.kv[k])["key"]:
                wire.decode_record(fc.kv[k])["max_new_tokens"]
                for k in fc.keys(f"{ns}/inbox/a/")}
        assert sent == {"00000000": 4, "00000001": 16}
        assert _counter("router/degrade_clamped") - c0 == 1
        from tpudist import obs
        assert obs.snapshot()["gauges"]["router/degraded"]["value"] == 1.0

    @pytest.mark.parametrize("canary_ok", [False, True],
                             ids=["corrupt-canary", "clean-canary"])
    def test_roll_structural_canary_gates_the_commit(self, canary_ok):
        """Blue-green rollout: a warmed, heartbeating green pool that
        answers its canary WRONG rolls back with blue untouched (pool
        key never shifted, no blue replica stopped); an exact canary
        commits — the pool key shifts to green and blue drains."""
        from tpudist.models.serving import Completion, Request

        fc = FakeCoord()
        ns = "bluegreen"
        self._reg_only(fc, ns, "b0", 0)
        fc.live_set.add(f"{ns}:b0")
        expect = np.array([5, 6, 7], np.int32)

        class Proc:                       # a green worker's Popen
            returncode = None

            def poll(self):
                return self.returncode

            def wait(self, timeout=None):
                return self.returncode

        proc = Proc()

        def spawn():
            self._reg_only(fc, ns, "g0", 1, pool="green")
            fc.live_set.add(f"{ns}:g0")
            return [proc]

        def on_set(key, value):
            if key.startswith(f"{ns}/inbox/g0/canary-"):
                k = key.rsplit("/", 1)[1]
                tokens = expect if canary_ok else expect + 1
                fc.kv[f"{ns}/done/{k}"] = _encode_completion(
                    "g0", Completion(rid=k, prompt=np.zeros(1, np.int32),
                                     tokens=tokens, reason="length"))
            elif key.startswith(f"{ns}/stop/"):
                # a stopped replica exits and its lease lapses
                fc.live_set.discard(f"{ns}:{key.rsplit('/', 1)[1]}")
                proc.returncode = 0

        fc.on_set = on_set
        router = Router(fc, namespace=ns, use_health=False)
        rb0 = _counter("router/rollbacks")
        rolls0 = _counter("router/structural_rolls")
        out = router.roll_structural(
            spawn, 1, canary=Request(np.arange(5, dtype=np.int32), 3,
                                     rid="probe"),
            expect_tokens=expect, warmup_timeout_s=5.0,
            canary_timeout_s=5.0, drain_timeout_s=5.0)
        assert out["blue"] == ["b0"] and out["procs"] == [proc]
        if canary_ok:
            assert out["ok"] is True and out["stage"] == "done"
            assert out["green"] == ["g0"]
            assert out["blue_drained"] is True
            assert fc.kv[f"{ns}/pool"] == b"green"
            assert f"{ns}:b0" not in fc.live_set       # blue stopped
            assert f"{ns}:g0" in fc.live_set
            assert _counter("router/structural_rolls") - rolls0 == 1
            assert _counter("router/rollbacks") - rb0 == 0
        else:
            assert out["ok"] is False and out["stage"] == "canary"
            assert "mismatch" in out["reason"]
            assert f"{ns}/pool" not in fc.kv            # traffic never shifted
            assert f"{ns}:b0" in fc.live_set            # blue untouched
            assert f"{ns}/replica/g0" not in fc.kv      # green swept
            assert _counter("router/rollbacks") - rb0 == 1
            assert _counter("router/structural_rolls") - rolls0 == 0

    def test_alloc_replica_indices_chain(self):
        """Concurrent scale-ups must never collide on replica indices:
        allocation is an atomic add-chain, and seeding only advances
        the chain when it is behind."""
        from tpudist.runtime.router import (_seed_replica_index,
                                            alloc_replica_indices)

        fc = FakeCoord()
        ns = "chain"
        assert alloc_replica_indices(fc, 3, namespace=ns) == [0, 1, 2]
        assert alloc_replica_indices(fc, 2, namespace=ns) == [3, 4]
        _seed_replica_index(fc, 2, namespace=ns)    # behind: no-op
        assert alloc_replica_indices(fc, 1, namespace=ns) == [5]
        fc2 = FakeCoord()
        _seed_replica_index(fc2, 4, namespace=ns)   # fresh chain
        assert alloc_replica_indices(fc2, 1, namespace=ns) == [4]


class TestTracingUnit:
    def test_redispatch_preserves_trace_id(self):
        """A request redispatched off a dead replica carries the SAME
        trace context to the survivor: both inbox payloads decode to
        one trace id, and the local ring shows enqueue -> dispatch ->
        redispatch -> dispatch -> done under that id."""
        from tpudist import obs
        from tpudist.obs.events import group_timelines, is_complete

        fc = FakeCoord()
        ns = "trace-redis"
        _register(fc, ns, "a", 0)
        sent = []   # (replica, decoded request) in inbox-write order

        def on_set(key, value):
            if not key.startswith(f"{ns}/inbox/"):
                return
            sent.append((key.split("/")[2], _decode_request(value)))
            if len(sent) == 1:   # first dispatch landed on a: kill it
                fc.live_set.discard(f"{ns}:a")
                _register(fc, ns, "b", 1)
            else:                # survivor b serves the redispatch
                req = sent[-1][1]
                fc.kv[f"{ns}/done/{req.rid}"] = json.dumps(
                    {"key": req.rid, "tokens": [1, 2],
                     "reason": "length", "replica": "b"}).encode()

        fc.on_set = on_set
        obs.events.clear()
        router = Router(fc, namespace=ns, use_health=False, poll_s=0.001)
        comps = router.run(_requests(1), timeout_s=10.0)
        assert [c.reason for c in comps] == ["length"]
        assert [rid for rid, _ in sent] == ["a", "b"]
        traces = [r.trace for _, r in sent]
        assert all(t is not None for t in traces)
        assert traces[0].trace_id == traces[1].trace_id
        tl = group_timelines(obs.events.events())[traces[0].trace_id]
        kinds = [e["kind"] for e in tl]
        assert kinds[0] == "enqueue" and kinds[-1] == "done"
        assert kinds.count("dispatch") == 2 and "redispatch" in kinds
        assert is_complete(tl)

    def test_decision_counters_per_reason(self):
        """router/decisions/{reason} splits terminal outcomes: the
        redispatch-cap scenario resolves every request as `failed`, and
        decisions() surfaces the per-reason counts."""
        fc = FakeCoord()
        ns = "decide"
        _register(fc, ns, "a", 0)

        def on_set(key, value):
            if key.startswith(f"{ns}/inbox/"):
                fc.live_set.clear()   # the whole fleet dies immediately

        fc.on_set = on_set
        router = Router(fc, namespace=ns, use_health=False,
                        max_redispatch=0, poll_s=0.001)
        f0 = _counter("router/decisions/failed")
        comps = router.run(_requests(2), timeout_s=10.0)
        assert [c.reason for c in comps] == ["failed"] * 2
        assert _counter("router/decisions/failed") - f0 == 2
        assert set(router.decisions()) == {
            "completed", "shed", "rejected", "failed", "timeout"}


class TestFleetE2E:
    def _route(self, client, procs, n_requests, *, namespace,
               lost_after_s=5.0):
        try:
            wait_live(client, len(procs), namespace=namespace,
                      timeout_s=90.0)
            router = Router(client, namespace=namespace,
                            lost_after_s=lost_after_s)
            comps = router.run(_requests(n_requests), timeout_s=120.0)
        finally:
            stop_fleet(client, procs, namespace=namespace)
        return comps

    def _reference(self, n_requests, seed=0):
        """The uninterrupted run: one local ServeLoop, identical seed
        and layout to the fleet replicas."""
        from tpudist.models.serving import ServeLoop

        cfg, params = build_tiny_lm(seed=seed)
        loop = ServeLoop(cfg, params, num_slots=2, steps_per_sync=4,
                         prefill_chunk=8, cache_layout="paged",
                         kv_block_size=16)
        return {c.rid: tuple(c.tokens.tolist())
                for c in loop.run(_requests(n_requests))}

    def test_kill_mid_decode_every_request_completes_exact(
            self, tmp_path):
        """THE acceptance E2E: 2 replicas, replica r1 SIGKILLs itself
        after 4 dispatched segments (uncatchable, mid-decode).  Every
        admitted request must still return a Completion, redispatched
        greedy output must be token-identical to an uninterrupted run,
        the survivor's pool must drain fully free, and the whole run
        must finish inside the TTL + redispatch bound (timeout_s=120
        would raise TimeoutError — not hitting it IS the bound check).
        ISSUE 10 rides along: merging the router's local event ring
        with the replicas' published rings must yield ONE complete
        timeline per request — enqueue, dispatch, (redispatch,) done
        under a single trace id, reconstructable by the timeline
        tool across the SIGKILL."""
        from tpudist import obs

        server, client = _coord_pair()
        ns = "kill-fleet"
        obs.events.clear()   # this process's ring: router-side events
        obs.slo.clear()      # and its SLO windows
        procs = launch_local_fleet(
            f"127.0.0.1:{server.port}", 2, namespace=ns,
            replica_args=["--cache-layout", "paged",
                          "--kv-block-size", "16", "--ttl", "1.0"],
            env_overrides={1: {"TPUDIST_FAULT_KILL_AFTER_SEGMENTS": "4"}})
        before = obs.snapshot()["counters"]
        comps = self._route(client, procs, 6, namespace=ns)

        # every admitted request returned exactly one Completion
        assert sorted(c.rid for c in comps) == [f"q{i}" for i in range(6)]
        assert all(c.reason == "length" for c in comps)
        # the kill actually happened and forced redispatch
        after = obs.snapshot()["counters"]
        deaths = (after["router/replica_deaths"]["value"]
                  - before.get("router/replica_deaths",
                               {}).get("value", 0))
        redispatched = (after["router/redispatched"]["value"]
                        - before.get("router/redispatched",
                                     {}).get("value", 0))
        assert deaths >= 1 and redispatched >= 1
        assert procs[1].returncode == -9  # SIGKILL, not a clean exit
        # one terminal decision a request, each fed to the SLO windows
        # the burn rate is read from
        assert (after["router/decisions/completed"]["value"]
                - before.get("router/decisions/completed",
                             {}).get("value", 0)) == 6
        assert sum(obs.slo.counts(max(obs.slo.windows))) == 6
        # redispatched greedy output is token-identical to an
        # uninterrupted single-loop run over the same weights
        want = self._reference(6)
        for c in comps:
            np.testing.assert_array_equal(
                c.tokens, np.asarray(want[c.rid], np.int32),
                err_msg=f"request {c.rid} diverged after redispatch")
        # no orphaned KV blocks: the survivor drained its pool; the
        # killed replica leaves NO exit report (it vanished)
        reports = exit_reports(client, namespace=ns)
        assert set(reports) == {"r0"}
        assert reports["r0"]["pool_drained"] is True
        assert reports["r0"]["clean"] is True

        # -- ISSUE 10: one complete merged timeline per request --------
        from tpudist.obs import timeline as timeline_tool
        from tpudist.obs.events import (group_timelines, is_complete,
                                        timeline_for_rid)

        doc = obs.merge_events(
            collected=obs.collect_events(client, f"{ns}/events"),
            router=obs.events.snapshot())
        timelines = group_timelines(doc["events"])
        redispatched_traces = 0
        for i in range(6):
            tl = timeline_for_rid(timelines, f"q{i}")
            assert tl is not None, f"q{i}: no timeline"
            kinds = [e["kind"] for e in tl]
            assert kinds[0] == "enqueue" and kinds[-1] == "done", kinds
            assert is_complete(tl), (f"q{i}", kinds)
            if "redispatch" in kinds:
                redispatched_traces += 1
                # the redispatch healed: one more dispatch than deaths
                assert kinds.count("dispatch") == \
                    kinds.count("redispatch") + 1, kinds
        assert redispatched_traces >= 1
        # the survivor's final publish carries replica-side events
        # (admit/segment/done_commit) into the merged view
        assert any(e["kind"] == "done_commit" for e in doc["events"])
        # the timeline tool reconstructs the same story from disk
        path = tmp_path / "events.json"
        chrome = tmp_path / "chrome.json"
        obs.atomic_write_json(str(path), doc)
        rc = timeline_tool.main([str(path), "--rid", "q0",
                                 "--chrome", str(chrome),
                                 "--require-complete"])
        assert rc == 0
        assert json.load(open(chrome))["traceEvents"]

    def test_bit_flipping_replica_quarantined_exact_output(self):
        """ISSUE 13's acceptance E2E: replica r1 flips one bit in each
        of its first two committed completion payloads (past the frame
        header, so only the wire CHECKSUM can catch it).  The router
        must reject both payloads before delivery, strike r1 into
        quarantine, redispatch the work, and still return a greedy
        exact-match Completion for every request — then, because the
        injection self-stops, reinstate r1 after consecutive clean
        golden probes.  Nothing dies: quarantine is exclusion, not
        execution."""
        from tpudist import obs
        from tpudist.models.serving import Request, ServeLoop
        from tpudist.runtime.router import GoldenProbe, QuarantineConfig

        server, client = _coord_pair()
        ns = "flip-fleet"
        # one uninterrupted reference run yields BOTH the exact-match
        # oracle and the golden probe's known answer (greedy output is
        # per-request deterministic regardless of batching)
        probe_prompt = np.array([3, 1, 4, 1, 5], np.int32)
        cfg, params = build_tiny_lm(seed=0)
        ref = ServeLoop(cfg, params, num_slots=2, steps_per_sync=4,
                        prefill_chunk=8, cache_layout="paged",
                        kv_block_size=16)
        ref_out = {c.rid: c for c in ref.run(
            _requests(6) + [Request(probe_prompt, 12, rid="golden")])}
        golden = GoldenProbe(
            prompt=tuple(int(t) for t in probe_prompt),
            expect=tuple(ref_out["golden"].tokens.tolist()),
            max_new_tokens=12)

        procs = launch_local_fleet(
            f"127.0.0.1:{server.port}", 2, namespace=ns,
            replica_args=["--cache-layout", "paged",
                          "--kv-block-size", "16", "--ttl", "1.0"],
            env_overrides={1: {"TPUDIST_FAULT_FLIP_WIRE_BITS": "1:2"}})
        before = obs.snapshot()["counters"]
        try:
            wait_live(client, 2, namespace=ns, timeout_s=90.0)
            router = Router(
                client, namespace=ns, lost_after_s=5.0,
                golden_probe=golden,
                quarantine_config=QuarantineConfig(
                    strike_threshold=2, strike_window_s=60.0,
                    probe_interval_s=0.25, probe_timeout_s=30.0,
                    reinstate_after=2, retire_after_fails=50))
            comps = router.run(_requests(6), timeout_s=120.0)
            # the run may outlive the quarantine (in-poll probe ticks
            # can reinstate r1 before the last request drains); if
            # not, keep driving the probe cycle until r1 earns its
            # way back in
            deadline = time.monotonic() + 60.0
            while (router.quarantine.quarantined()
                   and time.monotonic() < deadline):
                router.quarantine.tick()
                time.sleep(0.05)
            assert router.quarantine.quarantined() == set()
        finally:
            stop_fleet(client, procs, namespace=ns)
        after = obs.snapshot()["counters"]

        def delta(name):
            return (after.get(name, {}).get("value", 0)
                    - before.get(name, {}).get("value", 0))

        # zero lost, zero corrupted tokens delivered: every request
        # exact-matches the uninterrupted reference
        assert sorted(c.rid for c in comps) == [f"q{i}" for i in range(6)]
        assert all(c.reason == "length" for c in comps)
        for c in comps:
            np.testing.assert_array_equal(
                c.tokens, np.asarray(ref_out[c.rid].tokens, np.int32),
                err_msg=f"request {c.rid} diverged past the bit flips")
        # both flips were caught at the wire and struck r1 into
        # quarantine; clean probes brought it back; nobody was killed
        assert delta("integrity/checksum_mismatch") >= 2
        assert delta("router/quarantines") >= 1
        assert delta("router/reinstated") >= 1
        assert delta("router/retired") == 0
        assert delta("probe/pass") >= 2
        assert delta("router/replica_deaths") == 0
        # r1 survived its quarantine: it exits CLEANLY at stop_fleet
        reports = exit_reports(client, namespace=ns)
        assert set(reports) == {"r0", "r1"}
        assert all(r["clean"] for r in reports.values())

    def test_two_replicas_share_load_no_faults(self):
        """Happy path: both replicas serve, output exact-matches the
        local reference, both exit cleanly with drained pools."""
        from tpudist import obs
        from tpudist.obs.aggregate import collect, merge_snapshots
        from tpudist.obs.registry import hist_quantile

        server, client = _coord_pair()
        ns = "happy-fleet"
        procs = launch_local_fleet(
            f"127.0.0.1:{server.port}", 2, namespace=ns,
            replica_args=["--cache-layout", "paged",
                          "--kv-block-size", "16", "--ttl", "1.0"])
        before = {n: _counter(n) for n in
                  ("router/replica_deaths", "router/redispatched",
                   "router/handoffs")}
        comps = self._route(client, procs, 4, namespace=ns)
        # a calm unified fleet: nobody died, nothing moved
        assert {n: _counter(n) - v for n, v in before.items()} == {
            "router/replica_deaths": 0, "router/redispatched": 0,
            "router/handoffs": 0}
        # the fleet-merged queue-wait histogram the router's SLO
        # admission reads outlives the replicas' exit
        wait_h = merge_snapshots(collect(client, f"{ns}/metrics"))[
            "histograms"]["serve/queue_wait_s"]
        assert wait_h["count"] >= 4
        assert (hist_quantile(wait_h, 0.99)
                >= hist_quantile(wait_h, 0.5) >= 0)
        assert sorted(c.rid for c in comps) == [f"q{i}" for i in range(4)]
        want = self._reference(4)
        for c in comps:
            np.testing.assert_array_equal(
                c.tokens, np.asarray(want[c.rid], np.int32))
        reports = exit_reports(client, namespace=ns)
        assert set(reports) == {"r0", "r1"}
        served = {rid: r["served"] for rid, r in reports.items()}
        assert sum(served.values()) == 4
        assert all(r["pool_drained"] and r["clean"]
                   for r in reports.values())
        # least-loaded admission actually spread the work
        assert all(v >= 1 for v in served.values()), served

    def test_elastic_join_kill_and_rolling_hot_swap(self, tmp_path):
        """ISSUE 7's acceptance E2E: 2 replicas serve; r1 SIGKILLs
        itself mid-decode while a fresh replica r2 joins the RUNNING
        fleet (restoring the fleet snapshot, so its greedy output
        exact-matches the incumbents); then a rolling hot-swap to new
        weights — with a GHOST ticket pre-claimed on the chain, so the
        dead-ticket-holder turn-timeout path runs for real — and a
        second batch decodes exact-match on the NEW weights.  Zero lost
        requests across the whole scenario."""
        from tpudist import obs

        server, client = _coord_pair()
        ns = "elastic-fleet"
        snap_dir = tmp_path / "weights"
        _, params_v1 = build_tiny_lm(seed=0)
        _, params_v2 = build_tiny_lm(seed=1)
        # v1 on disk BEFORE launch: every member (and the joiner)
        # restores the same committed bytes
        roll_weights(client, snap_dir, params_v1, version=1,
                     namespace=ns)
        args = ["--cache-layout", "paged", "--kv-block-size", "16",
                "--ttl", "1.0", "--snapshot-dir", str(snap_dir),
                "--swap-turn-timeout", "2.0"]
        procs = launch_local_fleet(
            f"127.0.0.1:{server.port}", 2, namespace=ns,
            replica_args=args,
            env_overrides={1: {"TPUDIST_FAULT_KILL_AFTER_SEGMENTS": "4"}})
        before = obs.snapshot()["counters"]
        try:
            wait_live(client, 2, namespace=ns, timeout_s=90.0,
                      procs=procs)
            router = Router(client, namespace=ns, lost_after_s=5.0)
            router._poll({}, {}, None)   # membership baseline: {r0, r1}
            # the joiner RACES r1's kill: spawned now, admitted whenever
            # its registration lands (typically mid-run)
            procs += scale_fleet(f"127.0.0.1:{server.port}", 1,
                                 start_index=2, namespace=ns,
                                 replica_args=args)
            comps = router.run(_requests(6), timeout_s=120.0)
            assert sorted(c.rid for c in comps) == [f"q{i}"
                                                    for i in range(6)]
            assert all(c.reason == "length" for c in comps)  # zero lost
            want = self._reference(6, seed=0)
            for c in comps:
                np.testing.assert_array_equal(
                    c.tokens, np.asarray(want[c.rid], np.int32),
                    err_msg=f"request {c.rid} diverged (pre-swap)")
            # the kill really happened (reap: SIGKILL already landed)
            assert procs[1].wait(timeout=30) == -9
            # survivors: r0 + the joiner (NOT passing procs — r1's death
            # is expected here, not a launch failure)
            wait_live(client, 2, namespace=ns, timeout_s=90.0)
            # GHOST ticket: a chain member that "died" holding ticket 1
            # — the survivors must time out its turn, not stall forever
            client.add(f"{ns}/weights/ticket/2", 1)
            roll_weights(client, snap_dir, params_v2, version=2,
                         namespace=ns)
            assert wait_swapped(client, 2, 2, namespace=ns,
                                timeout_s=90.0) == {0, 2}
            comps2 = router.run(_requests(4), timeout_s=120.0)
            assert sorted(c.rid for c in comps2) == [f"q{i}"
                                                     for i in range(4)]
            # zero swap-downtime losses: every post-roll request served
            assert all(c.reason == "length" for c in comps2)
            want2 = self._reference(4, seed=1)
            for c in comps2:
                np.testing.assert_array_equal(
                    c.tokens, np.asarray(want2[c.rid], np.int32),
                    err_msg=f"request {c.rid} diverged (post-swap)")
        finally:
            stop_fleet(client, procs, namespace=ns)
        after = obs.snapshot()["counters"]

        def delta(name):
            return (after.get(name, {}).get("value", 0)
                    - before.get(name, {}).get("value", 0))

        assert delta("router/joins") >= 1           # r2 joined mid-run
        assert delta("router/replica_deaths") >= 1  # r1's death was seen
        reports = exit_reports(client, namespace=ns)
        assert set(reports) == {"r0", "r2"}  # SIGKILLed r1 left none
        for rid, rep in reports.items():
            assert rep["clean"] and rep["pool_drained"], (rid, rep)
            assert rep["weights_version"] == 2, (rid, rep)

    @pytest.mark.slow
    def test_publish_drop_replica_stays_alive_and_serves(self):
        """TPUDIST_FAULT_PUBLISH_DROP starves r1's obs plane from
        birth: it never publishes a snapshot, but its heartbeat flows —
        the router must treat it as a live (if unknown-load) member,
        NOT a death.  Every request completes exact-match and r1 exits
        clean with a drained pool."""
        from tpudist import obs
        from tpudist.obs.aggregate import collect

        server, client = _coord_pair()
        ns = "quiet-fleet"
        procs = launch_local_fleet(
            f"127.0.0.1:{server.port}", 2, namespace=ns,
            replica_args=["--cache-layout", "paged",
                          "--kv-block-size", "16", "--ttl", "1.0"],
            env_overrides={1: {"TPUDIST_FAULT_PUBLISH_DROP": "0"}})
        before = obs.snapshot()["counters"]
        comps = self._route(client, procs, 4, namespace=ns)
        assert sorted(c.rid for c in comps) == [f"q{i}" for i in range(4)]
        assert all(c.reason == "length" for c in comps)
        want = self._reference(4)
        for c in comps:
            np.testing.assert_array_equal(
                c.tokens, np.asarray(want[c.rid], np.int32))
        # the drop was really active end-to-end: not even the final
        # publish on shutdown landed for rank 1
        assert 1 not in collect(client, namespace=f"{ns}/metrics")
        after = obs.snapshot()["counters"]
        deaths = (after.get("router/replica_deaths",
                            {}).get("value", 0)
                  - before.get("router/replica_deaths",
                               {}).get("value", 0))
        assert deaths == 0                  # starved obs plane != death
        reports = exit_reports(client, namespace=ns)
        assert set(reports) == {"r0", "r1"}
        assert all(r["clean"] and r["pool_drained"]
                   for r in reports.values())

    @pytest.mark.slow
    def test_delayed_heartbeat_joiner_survives_grace_window(self):
        """Satellite regression for the joiner false-positive death:
        TPUDIST_FAULT_HEARTBEAT_DELAY_S swallows r1's heartbeats for
        its first 10 s, so the router polls a REGISTERED rid with no
        lease — exactly a slow-warming joiner.  The grace window must
        forgive it (no death, registration intact); its lease then
        lands and it finishes as a normal member with a clean exit."""
        from tpudist import obs

        server, client = _coord_pair()
        ns = "slow-joiner"
        procs = launch_local_fleet(
            f"127.0.0.1:{server.port}", 2, namespace=ns,
            replica_args=["--cache-layout", "paged",
                          "--kv-block-size", "16", "--ttl", "1.0"],
            env_overrides={
                1: {"TPUDIST_FAULT_HEARTBEAT_DELAY_S": "10"}})
        before = obs.snapshot()["counters"]
        try:
            wait_live(client, 1, namespace=ns, timeout_s=90.0)
            router = Router(client, namespace=ns, lost_after_s=1e6)
            comps = router.run(_requests(6), timeout_s=120.0)
            assert sorted(c.rid for c in comps) \
                == [f"q{i}" for i in range(6)]
            assert all(c.reason == "length" for c in comps)
            # the joiner was never swept: not dead, registration kept
            assert "r1" not in router._dead
            assert client.get(f"{ns}/replica/r1") is not None
            # ... and its delayed lease does land
            wait_live(client, 2, namespace=ns, timeout_s=60.0)
        finally:
            stop_fleet(client, procs, namespace=ns)
        after = obs.snapshot()["counters"]
        deaths = (after.get("router/replica_deaths", {}).get("value", 0)
                  - before.get("router/replica_deaths",
                               {}).get("value", 0))
        assert deaths == 0
        want = self._reference(6)
        for c in comps:
            np.testing.assert_array_equal(
                c.tokens, np.asarray(want[c.rid], np.int32))
        reports = exit_reports(client, namespace=ns)
        assert set(reports) == {"r0", "r1"}
        assert all(r["clean"] and r["pool_drained"]
                   for r in reports.values())


class TestRebalanceUnit:
    """ISSUE 19 hot/cold rebalancing: the skew detector and the victim
    picker are pure static helpers — unit-tested on synthetic loads."""

    def test_depth_gap_flags_hot_and_cold(self):
        loads = {"r0": {"queue_depth": 3}, "r1": {"queue_depth": 0}}
        got = Router.rebalance_hot_cold(loads, ["r0", "r1"],
                                        {"r0": 1, "r1": 0})
        assert got == ("r0", "r1")

    def test_gap_below_min_gap_is_noise(self):
        loads = {"r0": {"queue_depth": 1}, "r1": {"queue_depth": 0}}
        assert Router.rebalance_hot_cold(
            loads, ["r0", "r1"], {}) is None

    def test_assigned_counts_toward_depth(self):
        # no published queue depth at all: router-side assignment
        # counts alone can flag the skew
        got = Router.rebalance_hot_cold({}, ["r0", "r1"],
                                        {"r0": 4, "r1": 1})
        assert got == ("r0", "r1")

    def test_wait_percentile_skew_flags_below_depth_gap(self):
        # depth gap below min_gap, but the hot replica's queue-wait
        # quantile is 2x the coolest's non-zero one
        loads = {"r0": {"queue_depth": 2, "queue_wait_q": 0.9},
                 "r1": {"queue_depth": 1, "queue_wait_q": 0.3}}
        assert Router.rebalance_hot_cold(
            loads, ["r0", "r1"], {}) == ("r0", "r1")

    def test_zero_cold_wait_never_divides_into_a_signal(self):
        loads = {"r0": {"queue_depth": 2, "queue_wait_q": 5.0},
                 "r1": {"queue_depth": 1, "queue_wait_q": 0.0}}
        assert Router.rebalance_hot_cold(
            loads, ["r0", "r1"], {}) is None

    def test_single_candidate_is_never_skewed(self):
        assert Router.rebalance_hot_cold(
            {"r0": {"queue_depth": 9}}, ["r0"], {}) is None

    def test_min_gap_is_tunable(self):
        loads = {"r0": {"queue_depth": 1}, "r1": {"queue_depth": 0}}
        assert Router.rebalance_hot_cold(
            loads, ["r0", "r1"], {}, min_gap=1) == ("r0", "r1")

    def test_victim_is_oldest_outstanding_on_hot(self):
        entries = {"k2": {"assigned": "r0"},
                   "k1": {"assigned": "r0"},
                   "k0": {"assigned": "r1"}}
        assert Router.rebalance_victim(entries, {}, "r0") == "k1"

    def test_victim_skips_done_migrating_and_pull(self):
        entries = {"k1": {"assigned": "r0"},
                   "k2": {"assigned": "r0"},
                   "k3": {"assigned": "r0", "stage": "pull"},
                   "k4": {"assigned": "r0"}}
        got = Router.rebalance_victim(entries, {"k1": object()}, "r0",
                                      migrating=("k2",))
        assert got == "k4"

    def test_no_eligible_victim_returns_none(self):
        entries = {"k1": {"assigned": "r1"}}
        assert Router.rebalance_victim(entries, {}, "r0") is None
