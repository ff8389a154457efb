"""Tiered KV memory (ISSUE 16): HostTier unit semantics (budget,
leaf-first chain-suffix LRU eviction, weights-version stamps,
invariants), the serve-loop spill -> re-admit seam (byte-exact through
a full eviction round trip), the pull-mode export/install roundtrip
with its trust gates, the hot-swap invalidation regression (a post-swap
shared-prefix admission must re-prefill — never adopt pre-swap KV),
the router's pull orchestration over a fake coord (happy path, owner
death fallback, stale-summary TTL skip, direct owner affinity), and
the acceptance E2Es: a real 2-replica fleet where a cold local miss is
served by a peer KV pull byte-identical to re-prefill, and the owner
SIGKILLed mid-pull degrading to re-prefill with zero lost requests."""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist import obs
from tpudist.models.generate import greedy_generate
from tpudist.models.kv_pages import chain_hashes
from tpudist.models.kv_tier import (DEFAULT_TIER_BYTES, HostTier,
                                    tier_budget_from_env)
from tpudist.models.serving import Request, ServeLoop
from tpudist.models.transformer import TransformerConfig, TransformerLM
from tpudist.runtime import wire
from tpudist.runtime.router import (Router, _decode_request,
                                    build_tiny_lm, exit_reports,
                                    launch_local_fleet, scale_fleet,
                                    stop_fleet, wait_live)

CFG = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                        num_kv_heads=2, embed_dim=64, max_seq_len=96)
BS = 16
TIER_ENV = "TPUDIST_KV_HOST_TIER_BYTES"


@pytest.fixture(scope="module")
def params():
    return TransformerLM(CFG).init(
        jax.random.key(0), jnp.zeros((1, 2), jnp.int32))["params"]


@pytest.fixture(scope="module")
def params_v2():
    return TransformerLM(CFG).init(
        jax.random.key(1), jnp.zeros((1, 2), jnp.int32))["params"]


def _prompt(seed, n):
    return np.asarray(jax.random.randint(jax.random.key(seed), (n,), 0, 64))


def _want(params, prompt, n):
    out = greedy_generate(CFG, params, jnp.asarray(prompt)[None, :], n)
    return np.asarray(out)[0, len(prompt):]


def _counter(name):
    return obs.snapshot()["counters"].get(name, {}).get("value", 0)


def _tier_loop(params, tier_bytes, **kw):
    """ServeLoop with the host tier budget pinned via its env knob for
    the ctor only (the loop reads it once)."""
    old = os.environ.get(TIER_ENV)
    os.environ[TIER_ENV] = str(int(tier_bytes))
    try:
        kw.setdefault("num_slots", 2)
        kw.setdefault("steps_per_sync", 4)
        kw.setdefault("prefill_chunk", 8)
        kw.setdefault("cache_layout", "paged")
        kw.setdefault("kv_block_size", BS)
        return ServeLoop(CFG, params, **kw)
    finally:
        if old is None:
            os.environ.pop(TIER_ENV, None)
        else:
            os.environ[TIER_ENV] = old


# -- HostTier unit semantics ----------------------------------------------

def _layers(h, n_layers=1):
    base = np.full((8, 4), (int(h) % 97) / 3.0, np.float32)
    return [{"k": base + i, "v": base + i + 0.5} for i in range(n_layers)]


_ENTRY_BYTES = 2 * 8 * 4 * 4     # one _layers() entry: k + v float32


class TestHostTierUnit:
    def test_put_take_roundtrip_byte_exact(self):
        tier = HostTier(10 * _ENTRY_BYTES)
        assert tier.put(11, _layers(11), parent=None)
        assert 11 in tier and len(tier) == 1
        assert tier.nbytes == _ENTRY_BYTES
        got = tier.take(11)
        np.testing.assert_array_equal(got[0]["k"], _layers(11)[0]["k"])
        np.testing.assert_array_equal(got[0]["v"], _layers(11)[0]["v"])
        assert 11 not in tier and tier.nbytes == 0
        tier.check()

    def test_put_first_wins_keeps_original_bytes(self):
        tier = HostTier(10 * _ENTRY_BYTES)
        tier.put(5, _layers(5), parent=None)
        assert tier.put(5, _layers(99), parent=None)   # refresh, no clobber
        np.testing.assert_array_equal(
            tier.take(5)[0]["k"], _layers(5)[0]["k"])

    def test_budget_bound_and_oversize_rejected(self):
        tier = HostTier(3 * _ENTRY_BYTES)
        for h in (1, 2, 3, 4, 5):
            assert tier.put(h, _layers(h), parent=None)
            tier.check()
            assert tier.nbytes <= tier.budget_bytes
        assert len(tier) == 3                      # LRU evicted to fit
        big = [{"k": np.zeros((256, 256), np.float32),
                "v": np.zeros((256, 256), np.float32)}]
        assert not tier.put(7, big, parent=None)   # alone exceeds budget
        assert not HostTier(0).put(8, _layers(8), parent=None)  # disabled

    def test_eviction_is_leaf_first_chain_suffix(self):
        """Chain a<-b<-c plus loose d: LRU order is a,b,c,d, but a and
        b are mid-chain (tier-resident children) so eviction must trim
        the SUFFIX first — c before b before a, never a hole."""
        tier = HostTier(10 * _ENTRY_BYTES)
        tier.put(1, _layers(1), parent=None)
        tier.put(2, _layers(2), parent=1)
        tier.put(3, _layers(3), parent=2)
        tier.put(4, _layers(4), parent=None)
        assert tier.evict_one() and 3 not in tier    # deepest leaf first
        assert tier.evict_one() and 2 not in tier    # then its parent
        assert tier.evict_one() and 1 not in tier    # chain head last...
        assert 4 in tier                             # ...among its chain
        tier.check()

    def test_put_evicts_leaves_to_make_room(self):
        tier = HostTier(3 * _ENTRY_BYTES)
        tier.put(1, _layers(1), parent=None)
        tier.put(2, _layers(2), parent=1)
        tier.put(3, _layers(3), parent=2)
        assert tier.put(9, _layers(9), parent=None)
        # room came from the chain suffix, not the (mid-chain) head
        assert 1 in tier and 3 not in tier
        tier.check()

    def test_version_mismatch_reads_absent_and_take_drops(self):
        tier = HostTier(10 * _ENTRY_BYTES)
        tier.put(6, _layers(6), parent=None, version=0)
        assert tier.has(6, version=0)
        assert not tier.has(6, version=1)           # stale reads absent
        assert len(tier) == 1                       # has() never mutates
        assert tier.take(6, version=1) is None      # take DROPS stale
        assert len(tier) == 0 and tier.nbytes == 0
        tier.check()

    def test_match_chain_leading_run(self):
        tier = HostTier(10 * _ENTRY_BYTES)
        tier.put(1, _layers(1), parent=None)
        tier.put(3, _layers(3), parent=2)
        assert tier.match_chain([1, 2, 3]) == 1     # hole at 2 stops it
        assert tier.match_chain([1, 3]) == 2
        assert tier.match_chain([8]) == 0

    def test_discard_keeps_tier_child_reachable(self):
        tier = HostTier(10 * _ENTRY_BYTES)
        tier.put(1, _layers(1), parent=None)
        tier.put(2, _layers(2), parent=1)
        tier.discard(1)             # hash 1 became HBM-resident again
        assert 1 not in tier and 2 in tier
        tier.check(resident_hashes=[1])   # disjointness restored
        # the child still walks: its parent link is HBM-resident now
        assert tier.match_chain([2]) == 1

    def test_check_catches_cross_residency(self):
        tier = HostTier(10 * _ENTRY_BYTES)
        tier.put(7, _layers(7), parent=None)
        with pytest.raises(AssertionError, match="simultaneously"):
            tier.check(resident_hashes=[7])

    def test_budget_from_env(self, monkeypatch):
        monkeypatch.delenv(TIER_ENV, raising=False)
        assert tier_budget_from_env() == DEFAULT_TIER_BYTES
        monkeypatch.setenv(TIER_ENV, "1048576")
        assert tier_budget_from_env() == 1 << 20
        monkeypatch.setenv(TIER_ENV, "0")
        assert tier_budget_from_env() == 0
        monkeypatch.setenv(TIER_ENV, "lots")
        assert tier_budget_from_env() == 0          # unparsable disables


# -- serve-loop spill / re-admit seam -------------------------------------

class TestServeTier:
    def test_spill_then_readmit_byte_exact(self, params):
        """Three distinct 3-block prefixes through a 7-block pool: the
        third admission must evict the first tenant's cached pages into
        the tier, and the first tenant's return must re-admit from the
        tier — with every completion still bit-matching its dedicated
        greedy rollout."""
        loop = _tier_loop(params, 8 << 20, kv_num_blocks=7)
        assert loop._tier is not None
        spills0 = _counter("serve/tier_spills")
        readmits0 = _counter("serve/tier_readmits")
        prompts = [_prompt(100 + i, 52) for i in range(3)]
        comps = []
        for i, p in enumerate(prompts):
            comps += loop.run([Request(p, 8, rid=f"t{i}")])
        comps += loop.run([Request(prompts[0], 8, rid="t0-again")])
        for c in comps:
            np.testing.assert_array_equal(
                c.tokens, _want(params, c.prompt, 8),
                err_msg=f"request {c.rid} diverged through the tier")
        assert _counter("serve/tier_spills") - spills0 >= 3
        assert _counter("serve/tier_readmits") - readmits0 >= 1
        loop.flush_prefix_cache()
        loop.pool.check()
        assert loop.pool.free_blocks == loop.pool.num_blocks
        assert loop.tier_drained() is True

    def test_tier_multiplies_reusable_prefix_capacity(self, params):
        """Four tenants' 3-block prefixes asked round-robin through a
        7-block pool: each tenant's pages are evicted before its next
        ask.  With the host tier the evicted pages stay reusable — at
        least twice the cached prefix blocks for the same pool, and
        hits where the pool alone finds fewer — with the same tokens
        for every request, none lost, both levels drained."""
        prompts = [_prompt(300 + t, 52) for t in range(4)]
        reqs = [(f"r{rnd}t{t}", np.concatenate(
                    [prompts[t], _prompt(400 + 4 * rnd + t, 2 + rnd)]))
                for rnd in range(3) for t in range(4)]

        def arm(tier_bytes):
            loop = _tier_loop(params, tier_bytes, kv_num_blocks=7)
            got = {}
            for rid, prompt in reqs:
                for c in loop.run([Request(prompt, 6, rid=rid)]):
                    got[c.rid] = tuple(c.tokens.tolist())
            cached = len(loop._prefix_cache._entries) + (
                len(loop._tier) if loop._tier is not None else 0)
            hits = loop.prefix_stats["hits"]
            loop.flush_prefix_cache()
            loop.pool.check()
            assert loop.pool.free_blocks == loop.pool.num_blocks
            assert loop.tier_drained() in (None, True)
            return got, cached, hits

        plain, plain_cached, plain_hits = arm(0)
        tiered, tiered_cached, tiered_hits = arm(8 << 20)
        assert sorted(tiered) == sorted(rid for rid, _ in reqs)  # none lost
        assert tiered == plain
        assert tiered_cached >= 2 * plain_cached, (tiered_cached,
                                                   plain_cached)
        assert tiered_hits > plain_hits, (tiered_hits, plain_hits)

    def test_env_zero_disables_tier(self, params):
        loop = _tier_loop(params, 0, kv_num_blocks=7)
        assert loop._tier is None and loop.tier_drained() is None
        [c] = loop.run([Request(_prompt(1, 20), 6, rid="a")])
        np.testing.assert_array_equal(c.tokens, _want(params, c.prompt, 6))


# -- pull-mode export / install roundtrip ---------------------------------

class TestPrefixExportInstall:
    def _seeded_owner(self, params, prompt):
        owner = _tier_loop(params, 8 << 20, kv_num_blocks=12)
        [c] = owner.run([Request(prompt, 8, rid="seed")])
        np.testing.assert_array_equal(c.tokens, _want(params, prompt, 8))
        return owner

    def test_roundtrip_byte_exact(self, params):
        prompt = _prompt(7, 52)                 # 3 full blocks + tail
        owner = self._seeded_owner(params, prompt)
        chain = chain_hashes(prompt, BS)
        payload = owner.export_prefix(chain)
        assert payload is not None
        assert payload["chain"] == chain[:3]
        peer = _tier_loop(params, 8 << 20, kv_num_blocks=12)
        assert peer.install_prefix(prompt, payload) == 3
        hits0 = peer.prefix_stats["hits"]
        [c] = peer.run([Request(prompt, 8, rid="q")])
        np.testing.assert_array_equal(c.tokens, _want(params, prompt, 8))
        assert peer.prefix_stats["hits"] - hits0 == 1  # adopted, not re-prefilled
        for loop in (owner, peer):
            loop.flush_prefix_cache()
            assert loop.pool.used_blocks == 0
            assert loop.tier_drained() is True

    def test_export_continues_into_tier(self, params):
        """An owner whose pages spilled must still export them: the
        payload walk continues from HBM into the host tier."""
        loop = _tier_loop(params, 8 << 20, kv_num_blocks=7)
        prompts = [_prompt(200 + i, 52) for i in range(3)]
        for i, p in enumerate(prompts):
            loop.run([Request(p, 8, rid=f"t{i}")])
        chain = chain_hashes(prompts[0], BS)
        assert len(loop._tier) >= 1          # tenant 0 was spilled
        payload = loop.export_prefix(chain)
        assert payload is not None and len(payload["chain"]) >= 1
        peer = _tier_loop(params, 8 << 20, kv_num_blocks=12)
        assert peer.install_prefix(prompts[0], payload) >= 1
        [c] = peer.run([Request(prompts[0], 8, rid="q")])
        np.testing.assert_array_equal(
            c.tokens, _want(params, prompts[0], 8))

    def test_install_gates_reject_bad_payloads(self, params):
        prompt = _prompt(7, 52)
        owner = self._seeded_owner(params, prompt)
        payload = owner.export_prefix(chain_hashes(prompt, BS))
        peer = _tier_loop(params, 8 << 20, kv_num_blocks=12)
        stale = dict(payload, version=99)
        assert peer.install_prefix(prompt, stale) == 0
        wrong_bs = dict(payload, block_size=8)
        assert peer.install_prefix(prompt, wrong_bs) == 0
        other = dict(payload, chain=[h + 1 for h in payload["chain"]])
        assert peer.install_prefix(prompt, other) == 0
        assert peer.install_prefix(_prompt(9, 52), payload) == 0
        # nothing half-installed: the pool is untouched by rejections
        peer.pool.check()
        assert peer.pool.free_blocks == peer.pool.num_blocks


# -- hot-swap invalidation (satellite 1) ----------------------------------

class TestSwapInvalidation:
    def test_midstream_swap_shared_prefix_reprefills_exact(
            self, params, params_v2):
        """THE regression: weights hot-swap mid-stream, then a request
        sharing the pre-swap request's prefix.  Its admission must NOT
        adopt the cached/tiered pre-swap KV — output must bit-match a
        greedy rollout on the NEW weights (any stale adoption shows up
        as divergence), and the tier must be empty at the swap point."""
        loop = _tier_loop(params, 8 << 20, kv_num_blocks=12)
        pre = _prompt(50, 48)
        old = Request(np.concatenate([pre, _prompt(51, 4)]), 8, rid="old")
        new = Request(np.concatenate([pre, _prompt(52, 5)]), 8, rid="new")
        polls = {"n": 0}
        seen = []

        def source():
            polls["n"] += 1
            if polls["n"] == 1:
                return [old]
            if polls["n"] == 2:
                loop.request_swap(lambda: params_v2, version=5)
                return [new]
            return None if len(seen) == 2 else []

        comps = {c.rid: c for c in loop.run(
            source=source, sink=seen.append, idle_wait_s=0.0)}
        np.testing.assert_array_equal(
            comps["old"].tokens, _want(params, old.prompt, 8),
            err_msg="pre-swap request must decode on the OLD weights")
        np.testing.assert_array_equal(
            comps["new"].tokens,
            np.asarray(greedy_generate(
                CFG, params_v2,
                jnp.asarray(new.prompt)[None, :], 8))[0, len(new.prompt):],
            err_msg="post-swap shared-prefix request adopted stale KV")
        assert loop.weights_version == 5

    def test_install_rejects_pre_swap_export(self, params, params_v2):
        """Cross-replica half of the same rule: a payload exported
        under version 0 must not install on a peer already at a later
        weights version (the version gate, not just the swap flush)."""
        prompt = _prompt(7, 52)
        owner = _tier_loop(params, 8 << 20, kv_num_blocks=12)
        owner.run([Request(prompt, 8, rid="seed")])
        payload = owner.export_prefix(chain_hashes(prompt, BS))
        assert payload is not None and payload["version"] == 0
        peer = _tier_loop(params, 8 << 20, kv_num_blocks=12)
        peer.request_swap(lambda: params_v2, version=3)
        peer.run([Request(_prompt(1, 10), 2, rid="tick")])  # applies swap
        assert peer.weights_version == 3
        assert peer.install_prefix(prompt, payload) == 0
        [c] = peer.run([Request(prompt, 8, rid="q")])
        np.testing.assert_array_equal(
            c.tokens,
            np.asarray(greedy_generate(
                CFG, params_v2,
                jnp.asarray(prompt)[None, :], 8))[0, len(prompt):])


# -- router pull orchestration (fake coord) -------------------------------

class FakeCoord:
    def __init__(self):
        self.kv = {}
        self.live_set = set()
        self.counters = {}
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


def _pull_prompt():
    prompt = np.arange(20, dtype=np.int32) % 7
    return prompt, chain_hashes(prompt.tolist(), 8)


class TestRouterPull:
    def test_pull_happy_path(self):
        """Owner draining with the covering chain: the router must ask
        it to export (pullreq), stage the request through "pull", and
        dispatch to the cold peer WITH the payload ref — consuming the
        payload and leaving an empty journal."""
        fc = FakeCoord()
        ns = "pull1"
        _register(fc, ns, "a", 0)
        _register(fc, ns, "b", 1)
        fc.kv[f"{ns}/draining/a"] = b"1"
        prompt, chain = _pull_prompt()
        fc.kv[f"{ns}/prefix/a"] = wire.encode_record("prefix", {
            "replica": "a", "hashes": [], "chains": chain,
            "tiered": [chain[1]], "block_size": 8, "version": 0,
            "at": time.time()})
        events = []

        def on_set(key, value):
            if key.startswith(f"{ns}/pullreq/a/"):
                k = key.split("/")[-1]
                doc = wire.decode_record(value, expect="pullreq")
                assert doc["prompt"] == prompt.tolist()
                events.append("pullreq")
                fc.kv.pop(key, None)
                fc.kv[f"{ns}/kv/pull-{k}"] = b"payload-bytes"
                fc.kv[f"{ns}/pulldone/{k}"] = wire.encode_record(
                    "pulldone", {"key": k, "ref": f"{ns}/kv/pull-{k}",
                                 "owner": "a"})
            elif key.startswith(f"{ns}/inbox/b/"):
                req = _decode_request(value)
                assert req.prefix_ref == f"{ns}/kv/pull-{req.rid}"
                events.append("dispatch-b")
                fc.kv.pop(key, None)
                fc.kv[f"{ns}/done/{req.rid}"] = wire.encode_record(
                    "completion", {"key": req.rid, "tokens": [1, 2, 3],
                                   "reason": "length", "replica": "b"})
            elif key.startswith(f"{ns}/inbox/a/"):
                raise AssertionError("dispatched to draining owner")

        fc.on_set = on_set
        p0 = _counter("router/prefix_pulls")
        router = Router(fc, namespace=ns, use_health=False, poll_s=0.001,
                        join_grace_s=0.0)
        comps = router.run([Request(prompt, 4, rid="q0")], timeout_s=10.0)
        assert [c.reason for c in comps] == ["length"]
        assert events == ["pullreq", "dispatch-b"], events
        assert _counter("router/prefix_pulls") - p0 == 1
        assert f"{ns}/kv/pull-00000000" not in fc.kv, "payload leaked"
        assert fc.keys(f"{ns}/journal/") == []

    def test_owner_death_mid_pull_falls_back(self):
        """Owner answers nothing and leaves the live set after the
        pullreq lands: the router must revert the request to an
        ordinary prefill (prefix_ref=None) on the surviving replica,
        long before the pull timeout."""
        fc = FakeCoord()
        ns = "pull2"
        _register(fc, ns, "a", 0)
        _register(fc, ns, "b", 1)
        fc.kv[f"{ns}/draining/a"] = b"1"
        prompt, chain = _pull_prompt()
        fc.kv[f"{ns}/prefix/a"] = wire.encode_record("prefix", {
            "replica": "a", "hashes": [], "chains": chain, "tiered": [],
            "block_size": 8, "version": 0, "at": time.time()})
        events = []

        def on_set(key, value):
            if key.startswith(f"{ns}/pullreq/a/"):
                events.append("pullreq")
                fc.live_set.discard(f"{ns}:a")   # dies, never answers
            elif key.startswith(f"{ns}/inbox/b/"):
                req = _decode_request(value)
                assert req.prefix_ref is None
                events.append("dispatch-b")
                fc.kv.pop(key, None)
                fc.kv[f"{ns}/done/{req.rid}"] = wire.encode_record(
                    "completion", {"key": req.rid, "tokens": [9],
                                   "reason": "length", "replica": "b"})

        fc.on_set = on_set
        f0 = _counter("router/prefix_pull_fallbacks")
        router = Router(fc, namespace=ns, use_health=False, poll_s=0.001,
                        join_grace_s=0.0, pull_timeout_s=30.0)
        comps = router.run([Request(prompt, 4, rid="q0")], timeout_s=10.0)
        assert [c.reason for c in comps] == ["length"]
        assert events == ["pullreq", "dispatch-b"], events
        assert _counter("router/prefix_pull_fallbacks") - f0 == 1

    def test_stale_summary_skipped(self):
        """Prefix-affinity TTL (satellite 2): a summary older than the
        staleness bound must neither steer nor trigger a pull, and the
        skip is counted."""
        fc = FakeCoord()
        ns = "pull3"
        _register(fc, ns, "a", 0)
        _register(fc, ns, "b", 1)
        fc.kv[f"{ns}/draining/a"] = b"1"
        prompt, chain = _pull_prompt()
        fc.kv[f"{ns}/prefix/a"] = wire.encode_record("prefix", {
            "replica": "a", "hashes": [], "chains": chain, "tiered": [],
            "block_size": 8, "version": 0, "at": time.time() - 9999.0})
        events = []

        def on_set(key, value):
            if key.startswith(f"{ns}/pullreq/"):
                raise AssertionError("pulled from a stale owner")
            if key.startswith(f"{ns}/inbox/b/"):
                req = _decode_request(value)
                assert req.prefix_ref is None
                events.append("dispatch-b")
                fc.kv.pop(key, None)
                fc.kv[f"{ns}/done/{req.rid}"] = wire.encode_record(
                    "completion", {"key": req.rid, "tokens": [5],
                                   "reason": "length", "replica": "b"})

        fc.on_set = on_set
        s0 = _counter("router/prefix_stale_skips")
        router = Router(fc, namespace=ns, use_health=False, poll_s=0.001,
                        join_grace_s=0.0)
        comps = router.run([Request(prompt, 4, rid="q0")], timeout_s=10.0)
        assert [c.reason for c in comps] == ["length"]
        assert events == ["dispatch-b"]
        assert _counter("router/prefix_stale_skips") - s0 >= 1

    def test_dispatchable_owner_gets_affinity_not_pull(self):
        """When the covering owner can take the request itself, the
        pages are already where the request lands: direct content
        affinity, never a pull."""
        fc = FakeCoord()
        ns = "pull4"
        _register(fc, ns, "a", 0)
        _register(fc, ns, "b", 1)
        prompt, chain = _pull_prompt()
        fc.kv[f"{ns}/prefix/a"] = wire.encode_record("prefix", {
            "replica": "a", "hashes": [], "chains": chain, "tiered": [],
            "block_size": 8, "version": 0, "at": time.time()})
        events = []

        def on_set(key, value):
            if key.startswith(f"{ns}/pullreq/"):
                raise AssertionError("pulled when owner was dispatchable")
            if key.startswith(f"{ns}/inbox/"):
                rid = key.split("/")[2]
                req = _decode_request(value)
                events.append(f"dispatch-{rid}")
                fc.kv.pop(key, None)
                fc.kv[f"{ns}/done/{req.rid}"] = wire.encode_record(
                    "completion", {"key": req.rid, "tokens": [7],
                                   "reason": "length", "replica": rid})

        fc.on_set = on_set
        router = Router(fc, namespace=ns, use_health=False, poll_s=0.001,
                        join_grace_s=0.0)
        router.run([Request(prompt, 4, rid="q0")], timeout_s=10.0)
        assert events == ["dispatch-a"], events


# -- fleet E2E: cold miss -> peer pull ------------------------------------

def _coord_pair():
    try:
        from tpudist.runtime.coord import CoordClient, CoordServer

        server = CoordServer(0)
    except Exception as e:  # NativeUnavailable or build failure
        pytest.skip(f"native coord store unavailable: {e}")
    return server, CoordClient("127.0.0.1", server.port)


def _shared_prefix_requests():
    """Seed + follower sharing a 48-token (3 full block) prefix."""
    rng = np.random.default_rng(16)
    pre = rng.integers(0, 64, size=48).astype(np.int32)
    seed = Request(np.concatenate([pre, rng.integers(0, 64, size=4)
                                   .astype(np.int32)]), 8, rid="seed")
    q1 = Request(np.concatenate([pre, rng.integers(0, 64, size=5)
                                 .astype(np.int32)]), 8, rid="q1")
    return seed, q1


def _fleet_want(rid_reqs):
    cfg, params = build_tiny_lm(seed=0)
    out = {}
    for req in rid_reqs:
        got = greedy_generate(cfg, params,
                              jnp.asarray(req.prompt)[None, :], 8)
        out[req.rid] = np.asarray(got)[0, len(req.prompt):]
    return out


def _wait_owner_summary(client, ns, rid, timeout_s=60.0):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        raw = client.get(f"{ns}/prefix/{rid}")
        if raw is not None:
            doc = wire.decode_record(raw, expect="prefix")
            if doc.get("chains") or doc.get("tiered"):
                return doc
        time.sleep(0.05)
    raise AssertionError(f"no chain summary from {rid}")


TIER_FLEET_ENV = {TIER_ENV: str(32 << 20)}


class TestTierFleetE2E:
    def test_cold_miss_peer_pull_byte_identical(self):
        """THE acceptance E2E: replica r0 serves the seed request and
        owns its prefix pages; r0 drains; a same-prefix request must be
        served by cold r1 via a KV-page pull from r0 — byte-identical
        to the greedy reference — with both replicas' KV hierarchies
        fully unwound at exit and no payload left in the store."""
        server, client = _coord_pair()
        ns = "tier-pull"
        base = ["--cache-layout", "paged", "--kv-block-size", "16",
                "--ttl", "3.0"]
        seed, q1 = _shared_prefix_requests()
        procs = launch_local_fleet(
            f"127.0.0.1:{server.port}", 1, namespace=ns,
            replica_args=base, env_overrides={0: TIER_FLEET_ENV})
        before = obs.snapshot()["counters"]
        try:
            wait_live(client, 1, namespace=ns, timeout_s=90.0)
            router = Router(client, namespace=ns)
            [c0] = router.run([seed], timeout_s=120.0)
            procs += scale_fleet(
                f"127.0.0.1:{server.port}", 1, start_index=1,
                namespace=ns, replica_args=base,
                env_overrides={1: TIER_FLEET_ENV})
            wait_live(client, 2, namespace=ns, timeout_s=90.0)
            client.set(f"{ns}/draining/r0", b"1")
            _wait_owner_summary(client, ns, "r0")
            [c1] = router.run([q1], timeout_s=120.0)
        finally:
            stop_fleet(client, procs, namespace=ns)

        want = _fleet_want([seed, q1])
        np.testing.assert_array_equal(c0.tokens, want["seed"])
        np.testing.assert_array_equal(
            c1.tokens, want["q1"],
            err_msg="pulled-prefix output diverged from re-prefill")
        after = obs.snapshot()["counters"]

        def delta(name):
            return (after.get(name, {}).get("value", 0)
                    - before.get(name, {}).get("value", 0))

        assert delta("router/prefix_pulls") == 1
        assert delta("router/prefix_pull_fallbacks") == 0
        reports = exit_reports(client, namespace=ns)
        assert set(reports) == {"r0", "r1"}
        for rid, rep in reports.items():
            assert rep["pool_drained"] is True, (rid, rep)
            assert rep["tier_drained"] is True, (rid, rep)
            assert rep["clean"] is True, (rid, rep)
        assert client.keys(f"{ns}/kv/") == []   # no leaked payloads

    def test_kill_owner_mid_pull_falls_back_exact(self):
        """Owner SIGKILLed between advertising its pages and answering
        the pull: the router must detect the death, revert the parked
        request to an ordinary prefill on the survivor, and the output
        must STILL be byte-identical — a pull can never lose or corrupt
        a request."""
        server, client = _coord_pair()
        ns = "tier-pull-kill"
        base = ["--cache-layout", "paged", "--kv-block-size", "16",
                "--ttl", "3.0"]
        seed, q1 = _shared_prefix_requests()
        procs = launch_local_fleet(
            f"127.0.0.1:{server.port}", 1, namespace=ns,
            replica_args=base, env_overrides={0: TIER_FLEET_ENV})
        before = obs.snapshot()["counters"]
        try:
            wait_live(client, 1, namespace=ns, timeout_s=90.0)
            router = Router(client, namespace=ns, lost_after_s=5.0,
                            pull_timeout_s=60.0)
            [c0] = router.run([seed], timeout_s=120.0)
            procs += scale_fleet(
                f"127.0.0.1:{server.port}", 1, start_index=1,
                namespace=ns, replica_args=base,
                env_overrides={1: TIER_FLEET_ENV})
            wait_live(client, 2, namespace=ns, timeout_s=90.0)
            client.set(f"{ns}/draining/r0", b"1")
            _wait_owner_summary(client, ns, "r0")
            # the owner dies holding fresh summaries: its 3s lease keeps
            # it "live" through the router's pull decision, so the pull
            # is initiated and then stranded — the fallback under test
            procs[0].kill()
            [c1] = router.run([q1], timeout_s=120.0)
        finally:
            stop_fleet(client, procs, namespace=ns)

        np.testing.assert_array_equal(
            c1.tokens, _fleet_want([q1])["q1"],
            err_msg="fallback re-prefill diverged after owner death")
        assert procs[0].returncode == -9
        after = obs.snapshot()["counters"]

        def delta(name):
            return (after.get(name, {}).get("value", 0)
                    - before.get(name, {}).get("value", 0))

        assert delta("router/prefix_pulls") == 1
        assert delta("router/prefix_pull_fallbacks") == 1
        # the survivor unwinds clean; the corpse leaves no report
        reports = exit_reports(client, namespace=ns)
        assert set(reports) == {"r1"}
        assert reports["r1"]["pool_drained"] is True
        assert reports["r1"]["tier_drained"] is True
        assert client.keys(f"{ns}/kv/") == []
