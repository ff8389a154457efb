"""Control-plane crash recovery (ISSUE 12): the router's request-
lifecycle journal, the ``recover()`` failover path (re-adopt, replay,
redispatch, orphan sweep, duplicate-terminal dedup), coord-brownout
degradation in the event loop, and the compaction property under
repeated random crash/recover cycles."""

import json

import numpy as np
import pytest

from tpudist import obs
from tpudist.runtime import faults, wire
from tpudist.runtime.faults import FaultPlan, RouterKilled
from tpudist.runtime.router import (
    JOURNAL_SCHEMA, Router, _decode_request, _encode_request)


@pytest.fixture(autouse=True)
def _clean_plan():
    faults.reset()
    yield
    faults.reset()


class FakeCoord:
    """In-memory CoordClient double (the test_router idiom) with an
    ``on_set`` hook so a test can play replica at exact points in the
    dispatch sequence."""

    def __init__(self):
        self.kv: dict[str, bytes] = {}
        self.live_set: set[str] = set()
        self.counters: dict[str, int] = {}
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
        self.counters[key] = self.counters.get(key, 0) + int(delta)
        return self.counters[key]

    def live(self):
        return set(self.live_set)


def _register(fc, ns, rid, rank):
    fc.kv[f"{ns}/replica/{rid}"] = json.dumps(
        {"replica_id": rid, "rank": rank}).encode()
    fc.live_set.add(f"{ns}:{rid}")


def _requests(n):
    from tpudist.models.serving import Request

    rng = np.random.default_rng(0)
    return [Request(rng.integers(0, 64, size=4 + i).astype(np.int32),
                    8 + i, rid=f"q{i}") for i in range(n)]


def _counter(name):
    return obs.snapshot()["counters"].get(name, {}).get("value", 0)


def _instant_replica(fc, ns, rid="a"):
    """Play a replica that consumes its inbox and commits the done key
    the instant a dispatch lands (greedy-deterministic: tokens are a
    pure function of the prompt, so a double-serve is identical)."""

    def on_set(key, value):
        if not key.startswith(f"{ns}/inbox/"):
            return
        req = _decode_request(value)
        fc.kv.pop(key, None)   # consumed
        fc.kv[f"{ns}/done/{req.rid}"] = json.dumps(
            {"key": req.rid,
             "tokens": [int(req.prompt[0]), int(req.prompt.size)],
             "reason": "length", "replica": rid}).encode()

    fc.on_set = on_set


def _router(fc, ns, **kw):
    kw.setdefault("use_health", False)
    kw.setdefault("poll_s", 0.001)
    kw.setdefault("join_grace_s", 0.0)
    return Router(fc, namespace=ns, **kw)


class TestJournalLifecycle:
    def test_submit_record_lands_before_dispatch(self):
        fc = FakeCoord()
        ns = "jl1"
        _register(fc, ns, "a", 0)
        seen = []

        def on_set(key, value):
            if key.startswith(f"{ns}/inbox/"):
                req = _decode_request(value)
                raw = fc.kv.get(f"{ns}/journal/{req.rid}")
                seen.append(None if raw is None
                            else wire.decode_record(raw))
                fc.kv.pop(key, None)
                fc.kv[f"{ns}/done/{req.rid}"] = json.dumps(
                    {"key": req.rid, "tokens": [7],
                     "reason": "length", "replica": "a"}).encode()

        fc.on_set = on_set
        comps = _router(fc, ns).run(_requests(2), timeout_s=10.0)
        assert [c.reason for c in comps] == ["length"] * 2
        # at each dispatch, the submit-time journal record was already
        # durable: schema-stamped, caller rid preserved, still open
        assert len(seen) == 2
        for doc in seen:
            assert doc is not None
            assert doc["schema"] == JOURNAL_SCHEMA
            assert doc["terminal"] is None
            assert doc["rid"].startswith("q")
        # ...and the run's end compacted the journal to empty
        assert fc.keys(f"{ns}/journal/") == []
        assert fc.keys(f"{ns}/done/") == []

    def test_journal_off_writes_nothing(self):
        fc = FakeCoord()
        ns = "jl2"
        _register(fc, ns, "a", 0)
        writes = []
        _instant_replica(fc, ns)
        inner = fc.on_set

        def on_set(key, value):
            if key.startswith(f"{ns}/journal/"):
                writes.append(key)
            inner(key, value)

        fc.on_set = on_set
        comps = _router(fc, ns, journal=False).run(
            _requests(2), timeout_s=10.0)
        assert [c.reason for c in comps] == ["length"] * 2
        assert writes == []

    def test_terminal_journaled_before_done_key_destroyed(self):
        """The commit-point ordering: when the done key disappears, the
        journal record must ALREADY hold the terminal + tokens — a crash
        between the two replays instead of losing the outcome."""
        fc = FakeCoord()
        ns = "jl3"
        _register(fc, ns, "a", 0)
        _instant_replica(fc, ns)
        at_delete = {}
        orig_delete = fc.delete

        def delete(key):
            if key.startswith(f"{ns}/done/") and key not in at_delete:
                k = key[len(f"{ns}/done/"):]
                raw = fc.kv.get(f"{ns}/journal/{k}")
                at_delete[key] = (None if raw is None
                                  else wire.decode_record(raw))
            orig_delete(key)

        fc.delete = delete
        _router(fc, ns).run(_requests(1), timeout_s=10.0)
        (doc,) = at_delete.values()
        assert doc is not None and doc["terminal"] == "length"
        assert doc["tokens"]   # the replay payload rode along


class TestRecover:
    def _journal(self, fc, ns, k, *, rid, assigned=None, attempts=0,
                 terminal=None, tokens=()):
        req = _requests(1)[0]
        doc = {"schema": JOURNAL_SCHEMA,
               "req": wire.decode_record(_encode_request(k, req)),
               "rid": rid, "assigned": assigned, "attempts": attempts,
               "at": 0.0, "terminal": terminal,
               "tokens": list(tokens)}
        fc.kv[f"{ns}/journal/{k}"] = json.dumps(doc).encode()

    def test_failover_replays_readopts_redispatches_and_sweeps(self):
        fc = FakeCoord()
        ns = "rec1"
        _register(fc, ns, "a", 0)
        # the crashed router left behind:
        #  k0: terminal journaled + lingering duplicate done key
        self._journal(fc, ns, "00000000", rid="qa", terminal="length",
                      tokens=[9, 9])
        fc.kv[f"{ns}/done/00000000"] = json.dumps(
            {"key": "00000000", "tokens": [9, 9], "reason": "length",
             "replica": "a"}).encode()
        #  k1: terminal journaled AND already delivered by the old router
        self._journal(fc, ns, "00000001", rid="qb", terminal="length",
                      tokens=[1])
        #  k2: open, assigned to a replica that is gone
        self._journal(fc, ns, "00000002", rid="qc", assigned="ghost",
                      attempts=1)
        #  k3: open, assigned to live 'a', which already committed
        self._journal(fc, ns, "00000003", rid="qd", assigned="a")
        fc.kv[f"{ns}/done/00000003"] = json.dumps(
            {"key": "00000003", "tokens": [4], "reason": "length",
             "replica": "a"}).encode()
        #  orphaned inbox residue of k0 (terminal) on a's inbox
        fc.kv[f"{ns}/inbox/a/00000000"] = _encode_request(
            "00000000", _requests(1)[0])
        _instant_replica(fc, ns)

        d0 = _counter("router/dup_terminals")
        o0 = _counter("router/orphans_swept")
        r0 = _counter("router/recoveries")
        router = _router(fc, ns)
        comps = router.recover(timeout_s=10.0, delivered=["qb"])
        assert sorted(c.rid for c in comps) == ["qa", "qc", "qd"]
        by_rid = {c.rid: c for c in comps}
        # qa replayed from the journal's stored tokens
        assert by_rid["qa"].tokens.tolist() == [9, 9]
        # qd re-adopted: the live replica's commit consumed normally
        assert by_rid["qd"].tokens.tolist() == [4]
        assert _counter("router/dup_terminals") - d0 == 1
        assert _counter("router/orphans_swept") - o0 >= 1
        assert _counter("router/recoveries") - r0 == 1
        # the next minted key must not collide with journaled ones
        assert router._seq >= 4
        # everything delivered: journal and done keys swept clean
        assert fc.keys(f"{ns}/journal/") == []
        assert fc.keys(f"{ns}/done/") == []

    def test_recover_empty_journal_is_a_noop(self):
        fc = FakeCoord()
        ns = "rec2"
        _register(fc, ns, "a", 0)
        assert _router(fc, ns).recover(timeout_s=1.0) == []


class TestCrashRecoverProperty:
    def test_random_kill_cycles_deliver_exactly_once(self):
        """N requests through a router that is repeatedly crashed at
        random poll counts and recovered: every caller rid is delivered
        EXACTLY once, the journal compacts to empty, no done-key
        residue, and the recovery counter matches the crash count."""
        rng = np.random.default_rng(5)
        fc = FakeCoord()
        ns = "prop"
        _register(fc, ns, "a", 0)
        _instant_replica(fc, ns)
        n = 12
        delivered = []

        def deliver(key, comp):
            delivered.append(comp)

        r0 = _counter("router/recoveries")
        kills = 0
        # the first crash lands at poll 2: everything dispatched (and
        # committed by the instant replica) but nothing consumed — the
        # widest window for double-delivery bugs
        faults.install(FaultPlan(
            router_kill_after_polls=2, router_kill_raise=True))
        router = _router(fc, ns, compact_every=3)
        try:
            router.run(_requests(n), timeout_s=30.0,
                       on_complete=deliver)
        except RouterKilled:
            while True:
                kills += 1
                faults.install(FaultPlan(
                    router_kill_after_polls=int(rng.integers(1, 6)),
                    router_kill_raise=True))
                router = _router(fc, ns, compact_every=3)
                try:
                    router.recover(
                        timeout_s=30.0,
                        delivered=[c.rid for c in delivered],
                        on_complete=deliver)
                    break
                except RouterKilled:
                    continue
        assert kills >= 1
        rids = sorted(c.rid for c in delivered)
        assert rids == sorted(f"q{i}" for i in range(n))
        assert fc.keys(f"{ns}/journal/") == []
        assert fc.keys(f"{ns}/done/") == []
        assert fc.keys(f"{ns}/inbox/") == []
        assert _counter("router/recoveries") - r0 == kills


class TestRouteModeFailover:
    def test_crashed_route_then_recover_writes_each_result_once(
            self, tmp_path, monkeypatch):
        """The router's own command line (``--route --requests F
        --results OUT`` and then ``--route --recover --results OUT``):
        the first pass dies mid-run, the second rebuilds from the
        journal and from what OUT already holds — every request ends
        with exactly one result line (none lost, none twice), tokens as
        the replica produced them, journal compacted."""
        import argparse

        from tpudist.runtime import router as router_mod

        fc = FakeCoord()
        ns = "routecli"
        _register(fc, ns, "a", 0)
        _instant_replica(fc, ns)
        monkeypatch.setattr(router_mod, "CoordClient",
                            lambda host, port: fc)
        reqs = _requests(8)
        req_file = tmp_path / "requests.json"
        req_file.write_text(json.dumps(
            [{"prompt": r.prompt.astype(int).tolist(),
              "max_new_tokens": int(r.max_new_tokens), "rid": r.rid}
             for r in reqs]))
        results = tmp_path / "results.jsonl"

        def args(**kw):
            return argparse.Namespace(
                coord="127.0.0.1:1", namespace=ns, poll_s=0.001,
                lost_after=5.0, timeout=30.0, results=str(results),
                requests=str(req_file), recover=False, **kw)

        faults.install(FaultPlan(router_kill_after_polls=2,
                                 router_kill_raise=True))
        with pytest.raises(RouterKilled):
            router_mod._run_route_mode(args())
        assert fc.keys(f"{ns}/journal/")        # the crash left work open
        faults.reset()
        rec = args()
        rec.recover = True
        router_mod._run_route_mode(rec)

        lines = [json.loads(ln)
                 for ln in results.read_text().splitlines() if ln.strip()]
        assert sorted(d["rid"] for d in lines) == \
            sorted(r.rid for r in reqs)         # none lost, none twice
        want = {r.rid: [int(r.prompt[0]), int(r.prompt.size)]
                for r in reqs}
        assert all(d["tokens"] == want[d["rid"]] for d in lines)
        assert fc.keys(f"{ns}/journal/") == []
        assert fc.keys(f"{ns}/done/") == []


class _BrownoutCoord(FakeCoord):
    """FakeCoord that is unreachable while ``outage`` is set; the
    outage lifts itself after ``blind_max`` refused ops."""

    def __init__(self, blind_max=5):
        super().__init__()
        self.outage = False
        self.blind = 0
        self.blind_max = blind_max

    def _gate(self):
        if self.outage:
            self.blind += 1
            if self.blind >= self.blind_max:
                self.outage = False
            raise ConnectionError("store down")

    def keys(self, prefix=""):
        self._gate()
        return super().keys(prefix)

    def get(self, key):
        self._gate()
        return super().get(key)

    def set(self, key, value):
        self._gate()
        super().set(key, value)

    def delete(self, key):
        self._gate()
        super().delete(key)

    def live(self):
        self._gate()
        return super().live()


class TestRouterBrownout:
    def test_polls_blind_through_outage_no_death_verdicts(self):
        fc = _BrownoutCoord(blind_max=5)
        ns = "bo"
        _register(fc, ns, "a", 0)

        def on_set(key, value):
            if not key.startswith(f"{ns}/inbox/"):
                return
            req = _decode_request(value)
            fc.kv.pop(key, None)
            fc.kv[f"{ns}/done/{req.rid}"] = json.dumps(
                {"key": req.rid, "tokens": [3], "reason": "length",
                 "replica": "a"}).encode()
            fc.outage = True   # the store goes dark on the commit

        fc.on_set = on_set
        op0 = _counter("router/outage_polls")
        d0 = _counter("router/replica_deaths")
        comps = _router(fc, ns).run(_requests(1), timeout_s=10.0)
        # the outcome survived the brownout: polled blind, then
        # consumed the commit after reconnect — and the unreadable
        # live set produced no death verdicts
        assert [c.reason for c in comps] == ["length"]
        assert _counter("router/outage_polls") - op0 >= 1
        assert _counter("router/replica_deaths") - d0 == 0
