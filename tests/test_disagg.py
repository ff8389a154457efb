"""Disaggregated prefill/decode serving (ISSUE 15): the KV-migration
payload codec and transports, in-process split exactness against the
unified greedy reference (adopt, dropped-payload fallback, corrupt-chain
fallback), the router's two-stage journal ordering and mid-pipeline
recovery, and the acceptance E2Es: a real 1-prefill + 1-decode fleet
byte-identical through the handoff, and a prefill replica SIGKILLed at
the handoff seam (payload published, commit never lands) with zero lost
requests and exact output."""

import json

import numpy as np
import pytest

from tpudist import obs
from tpudist.runtime import faults, wire
from tpudist.runtime.disagg import (
    CoordKVTransport, IciKVTransport, decode_payload, encode_payload,
    make_transport, payload_nbytes)
from tpudist.runtime.faults import FaultPlan
from tpudist.runtime.router import (
    JOURNAL_SCHEMA, Router, _decode_request, _encode_request,
    build_tiny_lm, exit_reports, launch_local_fleet, scale_fleet,
    stop_fleet, wait_live)


@pytest.fixture(autouse=True)
def _clean_plan():
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


def _requests(n):
    from tpudist.models.serving import Request

    rng = np.random.default_rng(0)
    return [Request(rng.integers(0, 64, size=4 + i).astype(np.int32),
                    20 + 2 * i, rid=f"q{i}") for i in range(n)]


def _counter(name):
    return obs.snapshot()["counters"].get(name, {}).get("value", 0)


def _payload(key="k0", seed=3):
    """A structurally complete handoff payload with deterministic page
    arrays — enough to exercise every codec/transport path without a
    model in the loop."""
    rng = np.random.default_rng(seed)
    return {"key": key, "rid": "caller", "prompt": [3, 1, 4, 1, 5],
            "max_new_tokens": 7, "first": 42, "true_len": 5,
            "block_size": 8, "chain": [11, 22],
            "published_at": 0.0,
            "layers": [
                {"k": rng.standard_normal((2, 8, 4)).astype(np.float32),
                 "v": rng.standard_normal((2, 8, 4)).astype(np.float32)}
                for _ in range(2)]}


# -- payload codec ---------------------------------------------------------

class TestPayloadCodec:
    def test_roundtrip_bit_exact_through_json(self):
        p = _payload()
        doc = json.loads(json.dumps(encode_payload(p)))  # the wire trip
        got = decode_payload(doc)
        assert got["prompt"] == p["prompt"]
        assert got["chain"] == p["chain"]
        assert got["max_new_tokens"] == 7 and got["first"] == 42
        assert got["block_size"] == 8 and got["true_len"] == 5
        for gl, pl in zip(got["layers"], p["layers"]):
            assert gl["k"].dtype == np.float32
            np.testing.assert_array_equal(gl["k"], pl["k"])
            np.testing.assert_array_equal(gl["v"], pl["v"])

    def test_nbytes_counts_page_arrays(self):
        p = _payload()
        assert payload_nbytes(p) == 4 * (2 * 8 * 4) * 2 * 2
        assert payload_nbytes({"layers": []}) == 0

    def test_broken_document_raises(self):
        doc = encode_payload(_payload())
        del doc["layers"]
        with pytest.raises(KeyError):
            decode_payload(doc)
        doc2 = encode_payload(_payload())
        doc2["layers"][0]["k"]["dtype"] = "not-a-dtype"
        with pytest.raises((TypeError, ValueError)):
            decode_payload(doc2)


# -- transports over an in-memory store ------------------------------------

class _KV:
    """Just the coord verbs CoordKVTransport touches."""

    def __init__(self):
        self.kv = {}

    def get(self, key):
        return self.kv.get(key)

    def set(self, key, value):
        self.kv[key] = value

    def delete(self, key):
        self.kv.pop(key, None)


class TestTransports:
    def test_coord_roundtrip_and_metrics(self):
        t = CoordKVTransport(_KV(), namespace="tns")
        h0, b0 = _counter("serve/handoffs"), _counter("serve/handoff_bytes")
        p = _payload()
        ref, n = t.publish("k0", p)
        assert ref == "tns/kv/k0" and n > payload_nbytes(p)
        got = t.fetch(ref)
        assert got is not None
        np.testing.assert_array_equal(got["layers"][1]["v"],
                                      p["layers"][1]["v"])
        assert _counter("serve/handoffs") - h0 == 1
        assert _counter("serve/handoff_bytes") - b0 == n
        t.delete(ref)
        assert t.fetch(ref) is None
        t.delete(ref)   # idempotent on a missing ref

    def test_coord_fetch_corrupt_frame_is_none_and_swept(self):
        store = _KV()
        t = CoordKVTransport(store, namespace="tns")
        ref, _ = t.publish("k1", _payload())
        raw = bytearray(store.kv[ref])
        raw[len(raw) // 2] ^= 0x10   # flip one bit past the header
        store.kv[ref] = bytes(raw)
        m0 = _counter("integrity/checksum_mismatch")
        assert t.fetch(ref) is None   # never adopt unverified pages
        assert _counter("integrity/checksum_mismatch") - m0 == 1
        assert ref not in store.kv    # swept so retries miss cleanly

    def test_coord_handoff_drop_loses_payload_not_publish(self):
        store = _KV()
        t = CoordKVTransport(store, namespace="tns")
        faults.install(FaultPlan(handoff_drop=1))
        ref, _ = t.publish("k2", _payload())
        assert t.fetch(ref) is None       # first publish swallowed
        ref2, _ = t.publish("k3", _payload())
        assert t.fetch(ref2) is not None  # drop budget spent

    def test_ici_roundtrip(self):
        t = IciKVTransport()
        p = _payload()
        ref, n = t.publish("k4", p)
        assert ref == "ici://k4" and n == payload_nbytes(p)
        got = t.fetch(ref)
        assert got is not None
        np.testing.assert_array_equal(got["layers"][0]["k"],
                                      p["layers"][0]["k"])
        t.delete(ref)
        assert t.fetch(ref) is None
        t.delete(ref)

    def test_make_transport(self):
        assert isinstance(make_transport("ici"), IciKVTransport)
        assert isinstance(make_transport("coord", client=_KV()),
                          CoordKVTransport)
        with pytest.raises(ValueError, match="needs a CoordClient"):
            make_transport("coord")
        with pytest.raises(ValueError, match="unknown KV transport"):
            make_transport("dcn")


# -- in-process split exactness vs the unified reference -------------------

class TestSplitExactness:
    """The core correctness claim, no subprocesses: prefill-role loop ->
    wire codec -> decode-role loop is byte-identical to one unified
    loop, on the adopt path AND on every fallback path."""

    def _setup(self):
        import jax
        import jax.numpy as jnp

        from tpudist.models.serving import Request, ServeLoop
        from tpudist.models.transformer import (TransformerConfig,
                                                TransformerLM)

        cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                                num_kv_heads=2, embed_dim=64,
                                max_seq_len=96)
        params = TransformerLM(cfg).init(
            jax.random.key(0), jnp.zeros((1, 2), jnp.int32))["params"]
        kw = dict(num_slots=2, steps_per_sync=4, prefill_chunk=8,
                  decode_attention="flash", cache_layout="paged",
                  kv_block_size=8, chunked_prefill=True,
                  prefix_sharing=False)

        def prompt(seed, n):
            return np.asarray(jax.random.randint(
                jax.random.key(seed), (n,), 0, 64))

        # lengths straddle the block size: multi-block, sub-block, and
        # partial-tail prompts all cross the codec
        specs = [(100 + i, n, 9) for i, n in enumerate((40, 5, 23, 11))]

        def reqs(**extra):
            return [Request(prompt(s, n), m, rid=i, **extra)
                    for i, (s, n, m) in enumerate(specs)]

        return cfg, params, kw, reqs, ServeLoop, Request

    def test_adopt_and_fallbacks_byte_identical(self):
        cfg, params, kw, reqs, ServeLoop, Request = self._setup()
        ref = {c.rid: np.asarray(c.tokens)
               for c in ServeLoop(cfg, params, **kw).run(reqs())}

        # prefill half: every request terminates reason="handoff" with a
        # payload, zero generated tokens, and the pool drains at handoff
        pre = ServeLoop(cfg, params, role="prefill", **kw)
        handoffs = pre.run(reqs())
        assert sorted(c.rid for c in handoffs) == sorted(ref)
        assert all(c.reason == "handoff" and c.handoff is not None
                   for c in handoffs)
        assert pre.pool.free_blocks == pre.pool.num_blocks
        pre.pool.check()

        # decode half adopts codec-round-tripped payloads: exact, and
        # the adoptions counter proves no silent re-prefill happened
        payloads = {c.rid: decode_payload(encode_payload(c.handoff))
                    for c in handoffs}
        a0, f0 = _counter("serve/adoptions"), _counter(
            "serve/handoff_fallbacks")
        dec = ServeLoop(cfg, params, role="decode", **kw)
        out = {c.rid: np.asarray(c.tokens) for c in dec.run(
            [Request(np.asarray(p["prompt"], np.int32),
                     p["max_new_tokens"], rid=rid, kv_handoff=p)
             for rid, p in payloads.items()])}
        for rid in ref:
            np.testing.assert_array_equal(out[rid], ref[rid],
                                          err_msg=f"adopt rid={rid}")
        assert _counter("serve/adoptions") - a0 == len(ref)
        assert _counter("serve/handoff_fallbacks") - f0 == 0
        assert dec.pool.free_blocks == dec.pool.num_blocks
        dec.pool.check()

        # lost payload: a decode-role loop given no payload re-prefills
        # from the prompt — strictly slower, byte-identical
        dec2 = ServeLoop(cfg, params, role="decode", **kw)
        out2 = {c.rid: np.asarray(c.tokens) for c in dec2.run(
            [Request(np.asarray(p["prompt"], np.int32),
                     p["max_new_tokens"], rid=rid)
             for rid, p in payloads.items()])}
        for rid in ref:
            np.testing.assert_array_equal(out2[rid], ref[rid],
                                          err_msg=f"fallback rid={rid}")

        # corrupt chain: the adopter's hash-chain verification must
        # refuse the pages and fall back — still exact
        bad = dict(payloads[0])
        bad["chain"] = [1, 2, 3]
        f1 = _counter("serve/handoff_fallbacks")
        dec3 = ServeLoop(cfg, params, role="decode", **kw)
        [c] = dec3.run([Request(np.asarray(bad["prompt"], np.int32),
                                bad["max_new_tokens"], rid=0,
                                kv_handoff=bad)])
        np.testing.assert_array_equal(np.asarray(c.tokens), ref[0])
        assert _counter("serve/handoff_fallbacks") - f1 == 1

    def test_prefill_role_requires_chunked_paged_plain(self):
        cfg, params, kw, _, ServeLoop, _ = self._setup()
        with pytest.raises(ValueError, match="role"):
            ServeLoop(cfg, params, role="pre", **kw)
        with pytest.raises(ValueError, match="paged"):
            ServeLoop(cfg, params, num_slots=2, cache_layout="dense",
                      role="prefill")
        with pytest.raises(ValueError, match="paged"):
            ServeLoop(cfg, params, num_slots=2, cache_layout="dense",
                      role="decode")


# -- two-stage journal ordering over an in-memory coord double -------------

class FakeCoord:
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


def _register(fc, ns, rid, rank, role="both"):
    fc.kv[f"{ns}/replica/{rid}"] = json.dumps(
        {"replica_id": rid, "rank": rank, "role": role}).encode()
    fc.live_set.add(f"{ns}:{rid}")


def _router(fc, ns, **kw):
    kw.setdefault("use_health", False)
    kw.setdefault("poll_s", 0.001)
    kw.setdefault("join_grace_s", 0.0)
    return Router(fc, namespace=ns, **kw)


def _split_fleet(fc, ns):
    """Play a 1-prefill + 1-decode fleet: 'p' answers every dispatch
    with a reason="handoff" commit (ref only — the payload 'crossed'
    separately), 'd' asserts the ref rode the decode dispatch and
    commits the terminal."""
    _register(fc, ns, "p", 0, role="prefill")
    _register(fc, ns, "d", 1, role="decode")
    seen_refs = []

    def on_set(key, value):
        if key.startswith(f"{ns}/inbox/p/"):
            req = _decode_request(value)
            assert req.kv_handoff is None   # fresh = prefill stage
            fc.kv.pop(key, None)
            fc.kv[f"{ns}/done/{req.rid}"] = json.dumps(
                {"key": req.rid, "tokens": [], "reason": "handoff",
                 "replica": "p",
                 "handoff_ref": f"{ns}/kv/{req.rid}"}).encode()
        elif key.startswith(f"{ns}/inbox/d/"):
            req = _decode_request(value)
            assert req.kv_handoff == {
                "handoff_ref": f"{ns}/kv/{req.rid}"}
            seen_refs.append(req.kv_handoff["handoff_ref"])
            fc.kv.pop(key, None)
            fc.kv[f"{ns}/done/{req.rid}"] = json.dumps(
                {"key": req.rid,
                 "tokens": [int(req.prompt[0]), int(req.prompt.size)],
                 "reason": "length", "replica": "d"}).encode()

    fc.on_set = on_set
    return seen_refs


class TestTwoStageUnit:
    def test_handoff_journaled_before_done_key_destroyed(self):
        """The stage transition's commit-point ordering: when the
        prefill done key disappears, the journal record must ALREADY
        say stage=decode with the payload ref — a router crash between
        the two recovers mid-pipeline instead of re-prefilling blind or
        losing the request."""
        fc = FakeCoord()
        ns = "ds1"
        _split_fleet(fc, ns)
        at_delete = []
        orig_delete = fc.delete

        def delete(key):
            # record only real consumptions (the router also issues
            # idempotent sweep deletes of already-consumed keys)
            if key.startswith(f"{ns}/done/") and key in fc.kv:
                k = key[len(f"{ns}/done/"):]
                raw = fc.kv.get(f"{ns}/journal/{k}")
                at_delete.append(None if raw is None
                                 else wire.decode_record(raw))
            orig_delete(key)

        fc.delete = delete
        h0 = _counter("router/handoffs")
        comps = _router(fc, ns).run(_requests(1), timeout_s=10.0)
        assert [c.reason for c in comps] == ["length"]
        assert _counter("router/handoffs") - h0 == 1
        # first done-key delete is the handoff consumption: the journal
        # already holds the decode stage + ref, terminal still open;
        # the second is the terminal, journaled with its tokens
        handoff_doc, terminal_doc = at_delete
        assert handoff_doc is not None
        assert handoff_doc["schema"] == JOURNAL_SCHEMA
        assert handoff_doc["stage"] == "decode"
        assert handoff_doc["handoff_ref"] == f"{ns}/kv/00000000"
        assert handoff_doc["terminal"] is None
        assert terminal_doc["terminal"] == "length"
        # the run compacted the journal and deleted the payload ref
        assert fc.keys(f"{ns}/journal/") == []
        assert f"{ns}/kv/00000000" not in fc.kv

    def test_recover_resumes_decode_stage_with_ref(self):
        """A journaled handoff recovers MID-pipeline: the replacement
        router dispatches straight to the decode pool with the payload
        ref intact — no second prefill, no lost request."""
        fc = FakeCoord()
        ns = "ds2"
        seen_refs = _split_fleet(fc, ns)
        req = _requests(1)[0]
        doc = {"schema": JOURNAL_SCHEMA,
               "req": wire.decode_record(_encode_request("00000000", req)),
               "rid": "qa", "assigned": "ghost", "attempts": 1,
               "at": 0.0, "terminal": None,
               "stage": "decode", "handoff_ref": f"{ns}/kv/00000000"}
        fc.kv[f"{ns}/journal/00000000"] = json.dumps(doc).encode()
        comps = _router(fc, ns).recover(timeout_s=10.0)
        assert [c.rid for c in comps] == ["qa"]
        assert comps[0].reason == "length"
        assert seen_refs == [f"{ns}/kv/00000000"]

    @pytest.mark.parametrize("with_ref", [True, False],
                             ids=["pages-moved", "no-payload"])
    def test_migrate_commit_is_a_stage_not_a_terminal(self, with_ref):
        """Fast drain's router half: a draining replica answers with a
        reason="migrate" commit.  With a payload ref the request goes
        on to a peer as a decode-stage dispatch carrying the ref (one
        journaled migration, no fallback); without one it reverts to an
        ordinary prefill there and the fallback is counted.  Either way
        exactly one terminal, nothing left in the journal."""
        fc = FakeCoord()
        ns = "mig1"
        _register(fc, ns, "a", 0)
        _register(fc, ns, "b", 1)
        ref = f"{ns}/kv/00000000" if with_ref else None
        seen = []

        def on_set(key, value):
            if key.startswith(f"{ns}/inbox/a/"):
                req = _decode_request(value)
                fc.kv.pop(key, None)
                fc.kv[f"{ns}/draining/a"] = b"1"   # steered around from now
                doc = {"key": req.rid, "tokens": [], "reason": "migrate",
                       "replica": "a"}
                if ref:
                    doc["handoff_ref"] = ref
                fc.kv[f"{ns}/done/{req.rid}"] = json.dumps(doc).encode()
            elif key.startswith(f"{ns}/inbox/b/"):
                req = _decode_request(value)
                seen.append(req.kv_handoff)
                fc.kv.pop(key, None)
                fc.kv[f"{ns}/done/{req.rid}"] = json.dumps(
                    {"key": req.rid,
                     "tokens": [int(req.prompt[0]), int(req.prompt.size)],
                     "reason": "length", "replica": "b"}).encode()

        fc.on_set = on_set
        m0 = _counter("router/migrations")
        f0 = _counter("router/migration_fallbacks")
        req = _requests(1)[0]
        # 'a' is picked first: 'b' already carries load
        fc.kv[f"{ns}/inbox/b/zz-busy"] = b"x"
        comps = _router(fc, ns).run([req], timeout_s=10.0)
        assert [(c.rid, c.reason) for c in comps] == [(req.rid, "length")]
        assert comps[0].tokens.tolist() == [int(req.prompt[0]),
                                            int(req.prompt.size)]
        assert seen == [{"handoff_ref": ref} if with_ref else None]
        assert _counter("router/migrations") - m0 == 1
        assert _counter("router/migration_fallbacks") - f0 == \
            (0 if with_ref else 1)
        assert fc.keys(f"{ns}/journal/") == []

    def test_prefill_pool_empty_decode_stage_still_flows(self):
        """Stage pools are independent: with only a decode replica
        live, a fresh (prefill-stage) request waits un-dispatched
        rather than landing on a decode-only replica."""
        fc = FakeCoord()
        ns = "ds3"
        _register(fc, ns, "d", 0, role="decode")
        dispatched = []
        fc.on_set = lambda key, value: (
            dispatched.append(key) if key.startswith(f"{ns}/inbox/")
            else None)
        router = _router(fc, ns)
        with pytest.raises(TimeoutError):
            router.run(_requests(1), timeout_s=0.3)
        assert dispatched == []


# -- acceptance E2Es: real subprocess fleets -------------------------------

class TestDisaggFleetE2E:
    def _reference(self, n_requests):
        from tpudist.models.serving import ServeLoop

        cfg, params = build_tiny_lm(seed=0)
        loop = ServeLoop(cfg, params, num_slots=2, steps_per_sync=4,
                         prefill_chunk=8, cache_layout="paged",
                         kv_block_size=16)
        return {c.rid: tuple(c.tokens.tolist())
                for c in loop.run(_requests(n_requests))}

    def test_two_stage_fleet_byte_identical_to_unified(self):
        """THE acceptance E2E: 1 prefill + 1 decode replica behind the
        two-stage router.  Every request's greedy output must be
        byte-identical to one unified loop over the same weights, every
        request must cross the handoff seam exactly once, both pools
        must drain, and no KV payload may leak in the store."""
        server, client = _coord_pair()
        ns = "disagg-fleet"
        base = ["--cache-layout", "paged", "--kv-block-size", "16",
                "--ttl", "1.0"]
        n_req = 5
        procs = launch_local_fleet(
            f"127.0.0.1:{server.port}", 1, namespace=ns,
            replica_args=base + ["--role", "prefill"])
        procs += scale_fleet(
            f"127.0.0.1:{server.port}", 1, start_index=1, namespace=ns,
            replica_args=base + ["--role", "decode"])
        before = obs.snapshot()["counters"]
        try:
            wait_live(client, 2, namespace=ns, timeout_s=90.0)
            router = Router(client, namespace=ns)
            comps = router.run(_requests(n_req), timeout_s=120.0)
        finally:
            stop_fleet(client, procs, namespace=ns)

        assert sorted(c.rid for c in comps) == \
            [f"q{i}" for i in range(n_req)]
        assert all(c.reason == "length" for c in comps)
        want = self._reference(n_req)
        for c in comps:
            np.testing.assert_array_equal(
                c.tokens, np.asarray(want[c.rid], np.int32),
                err_msg=f"request {c.rid} diverged through handoff")
        after = obs.snapshot()["counters"]
        handoffs = (after.get("router/handoffs", {}).get("value", 0)
                    - before.get("router/handoffs", {}).get("value", 0))
        assert handoffs == n_req
        reports = exit_reports(client, namespace=ns)
        assert set(reports) == {"r0", "r1"}
        for rid, rep in reports.items():
            assert rep["pool_drained"] is True, (rid, rep)
            assert rep["clean"] is True, (rid, rep)
        assert client.keys(f"{ns}/kv/") == []   # no leaked payloads

    def test_kill_at_handoff_zero_lost_exact(self):
        """The exactly-once seam: prefill replica r0 SIGKILLs itself
        right after publishing its first KV payload, BEFORE committing
        the handoff done record.  The router must see a plain death —
        redispatch the request (and r0's queue) to the surviving
        prefill replica, deliver every request exactly once, and keep
        the output byte-identical."""
        server, client = _coord_pair()
        ns = "kill-handoff"
        base = ["--cache-layout", "paged", "--kv-block-size", "16",
                "--ttl", "1.0"]
        n_req = 6
        procs = launch_local_fleet(
            f"127.0.0.1:{server.port}", 2, namespace=ns,
            replica_args=base + ["--role", "prefill"],
            env_overrides={0: {"TPUDIST_FAULT_KILL_AT_HANDOFF": "1"}})
        procs += scale_fleet(
            f"127.0.0.1:{server.port}", 1, start_index=2, namespace=ns,
            replica_args=base + ["--role", "decode"])
        before = obs.snapshot()["counters"]
        try:
            wait_live(client, 3, namespace=ns, timeout_s=90.0)
            router = Router(client, namespace=ns, lost_after_s=5.0)
            comps = router.run(_requests(n_req), timeout_s=120.0)
        finally:
            stop_fleet(client, procs, namespace=ns)

        # every admitted request returned exactly one Completion
        assert sorted(c.rid for c in comps) == \
            [f"q{i}" for i in range(n_req)]
        assert all(c.reason == "length" for c in comps)
        # the kill happened at the seam and forced redispatch
        after = obs.snapshot()["counters"]

        def delta(name):
            return (after.get(name, {}).get("value", 0)
                    - before.get(name, {}).get("value", 0))

        assert procs[0].returncode == -9   # SIGKILL, not a clean exit
        assert delta("router/replica_deaths") >= 1
        assert delta("router/redispatched") >= 1
        assert delta("router/handoffs") == n_req
        # redispatched output is byte-identical to an uninterrupted run
        want = self._reference(n_req)
        for c in comps:
            np.testing.assert_array_equal(
                c.tokens, np.asarray(want[c.rid], np.int32),
                err_msg=f"request {c.rid} diverged after the kill")
        # the dead replica leaves no exit report; survivors drain clean
        reports = exit_reports(client, namespace=ns)
        assert set(reports) == {"r1", "r2"}
        for rid, rep in reports.items():
            assert rep["pool_drained"] is True, (rid, rep)
            assert rep["clean"] is True, (rid, rep)
        # the orphaned pre-commit payload was overwritten by the re-run
        # and consumed; nothing leaks
        assert client.keys(f"{ns}/kv/") == []
