"""Continuous-batching serve loop: token parity with the fixed rollouts,
slot reuse, mixed lengths, stop semantics (round-3 verdict item 3)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.models.generate import greedy_generate
from tpudist.models.serving import Completion, Request, ServeLoop
from tpudist.models.transformer import TransformerConfig, TransformerLM

CFG = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                        num_kv_heads=2, embed_dim=64, max_seq_len=96)


@pytest.fixture(scope="module")
def params():
    return TransformerLM(CFG).init(
        jax.random.key(0), jnp.zeros((1, 2), jnp.int32))["params"]


def _prompt(seed, n):
    return np.asarray(jax.random.randint(jax.random.key(seed), (n,), 0, 64))


def _want(params, prompt, n, **kw):
    out = greedy_generate(CFG, params, jnp.asarray(prompt)[None, :], n, **kw)
    return np.asarray(out)[0, len(prompt):]


class TestParity:
    @pytest.mark.parametrize("attn", ["dense", "flash"])
    def test_single_request_matches_greedy(self, params, attn):
        loop = ServeLoop(CFG, params, num_slots=2, steps_per_sync=5,
                         decode_attention=attn, prefill_chunk=8)
        [c] = loop.run([Request(_prompt(1, 7), 17, rid="a")])
        assert c.rid == "a" and c.reason == "length"
        np.testing.assert_array_equal(c.tokens, _want(params, c.prompt, 17))

    def test_mixed_lengths_and_slot_reuse(self, params):
        """5 requests with different prompt lengths/budgets through 2
        slots: queueing, mid-flight admission into freed slots, and every
        request's tokens still bit-match its own dedicated greedy
        rollout."""
        reqs = [Request(_prompt(10 + i, 3 + 5 * i), 6 + 3 * i, rid=i)
                for i in range(5)]
        loop = ServeLoop(CFG, params, num_slots=2, steps_per_sync=4,
                         decode_attention="flash", prefill_chunk=8)
        comps = loop.run(reqs)
        assert sorted(c.rid for c in comps) == [0, 1, 2, 3, 4]
        for c in comps:
            assert c.reason == "length"
            np.testing.assert_array_equal(
                c.tokens, _want(params, c.prompt, 6 + 3 * c.rid),
                err_msg=f"request {c.rid}")

    def test_independent_of_batch_company(self, params):
        """A request's tokens must not depend on WHICH requests share the
        slots (per-row cache isolation): same request served alone and
        in company yields identical tokens."""
        req = Request(_prompt(33, 9), 12, rid="x")
        alone = ServeLoop(CFG, params, num_slots=1, steps_per_sync=6,
                          prefill_chunk=8, decode_attention="flash")
        [ca] = alone.run([Request(_prompt(33, 9), 12, rid="x")])
        crowd = ServeLoop(CFG, params, num_slots=3, steps_per_sync=6,
                          prefill_chunk=8, decode_attention="flash")
        comps = crowd.run([Request(_prompt(40, 5), 20, rid="other1"),
                           req,
                           Request(_prompt(41, 14), 7, rid="other2")])
        cx = next(c for c in comps if c.rid == "x")
        np.testing.assert_array_equal(cx.tokens, ca.tokens)


class TestStopAndBudget:
    def test_stop_token_completion(self, params):
        prompt = _prompt(5, 6)
        ref = greedy_generate(CFG, params, jnp.asarray(prompt)[None, :],
                              30, stop_tokens=(7,))
        ref_tokens, ref_len = np.asarray(ref[0])[0], int(ref[1][0])
        loop = ServeLoop(CFG, params, num_slots=2, steps_per_sync=4,
                         prefill_chunk=8, stop_tokens=(7,))
        [c] = loop.run([Request(prompt, 30, rid=0)])
        gen = ref_tokens[len(prompt):len(prompt) + ref_len]
        if ref_len < 30:  # the reference hit the stop token
            assert c.reason == "stop"
            np.testing.assert_array_equal(c.tokens, gen)
        else:
            assert c.reason == "length"

    def test_budget_one_completes_at_prefill(self, params):
        loop = ServeLoop(CFG, params, num_slots=1, steps_per_sync=4,
                         prefill_chunk=8)
        [c] = loop.run([Request(_prompt(9, 4), 1, rid=0)])
        assert c.reason == "length" and c.tokens.shape == (1,)
        np.testing.assert_array_equal(c.tokens, _want(params, c.prompt, 1))

    def test_request_validation(self, params):
        loop = ServeLoop(CFG, params, num_slots=1)
        with pytest.raises(ValueError, match="max_seq_len"):
            loop.run([Request(_prompt(1, 90), 20)])
        with pytest.raises(ValueError, match="non-empty"):
            loop.run([Request(np.zeros((0,), np.int32), 5)])
        with pytest.raises(ValueError, match="num_slots"):
            ServeLoop(CFG, params, num_slots=0)


class TestSampling:
    def test_sampled_runs_and_respects_budget(self, params):
        loop = ServeLoop(CFG, params, num_slots=2, steps_per_sync=4,
                         prefill_chunk=8, temperature=0.9,
                         key=jax.random.key(3))
        comps = loop.run([Request(_prompt(20, 4), 9, rid=0),
                          Request(_prompt(21, 11), 5, rid=1)])
        by = {c.rid: c for c in comps}
        assert by[0].tokens.shape == (9,) and by[1].tokens.shape == (5,)
        assert all(int(t) < 64 for c in comps for t in c.tokens)


class TestScannedCheckpoint:
    def test_auto_unstack(self, params):
        import dataclasses

        from tpudist.models import stack_layer_params

        scfg = dataclasses.replace(CFG, scan_layers=True)
        stacked = stack_layer_params(params, CFG.num_layers)
        loop = ServeLoop(scfg, stacked, num_slots=1, steps_per_sync=4,
                         prefill_chunk=8)
        [c] = loop.run([Request(_prompt(2, 5), 8, rid=0)])
        np.testing.assert_array_equal(c.tokens, _want(params, c.prompt, 8))


class TestPadCapRegression:
    def test_prompt_near_cache_end_with_nondividing_chunk(self, params):
        """Review repro: prefill_chunk not dividing max_seq_len and a
        prompt near the cache end — the uncapped pad used to clamp the
        final chunk's write backwards and corrupt real prompt KV."""
        prompt = _prompt(50, 92)  # Lp would be 100 > max_seq_len 96
        loop = ServeLoop(CFG, params, num_slots=1, steps_per_sync=4,
                         prefill_chunk=10)
        [c] = loop.run([Request(prompt, 3, rid=0)])
        np.testing.assert_array_equal(c.tokens, _want(params, prompt, 3))

    def test_bad_request_rejected_before_any_decode(self, params):
        """One malformed request fails run() up front — completed work is
        never silently discarded mid-run."""
        loop = ServeLoop(CFG, params, num_slots=1, steps_per_sync=4,
                         prefill_chunk=8)
        with pytest.raises(ValueError, match="max_seq_len"):
            loop.run([Request(_prompt(1, 4), 8, rid="good"),
                      Request(_prompt(2, 90), 20, rid="bad")])
        # the loop is still usable and state is clean
        [c] = loop.run([Request(_prompt(1, 4), 8, rid="good")])
        np.testing.assert_array_equal(c.tokens, _want(params, c.prompt, 8))


class TestPipelinedDispatch:
    def test_depth_validation(self, params):
        with pytest.raises(ValueError, match="pipeline_depth"):
            ServeLoop(CFG, params, num_slots=1, pipeline_depth=0)

    def test_pipelined_exact_match_mixed_workload(self, params):
        """The staleness contract must not cost a token: the pipelined
        loop is byte-identical to the synchronous loop (depth 1) on a
        mixed prompt-length / stop-token workload with queueing and
        mid-flight slot reuse.  At the default depth 2 that holds for
        tokens, finish reasons and finish ORDER; at depth 3 a freed lane
        is seen two segments late, so a queued request may be admitted
        (and finish) later than in the synchronous run — its tokens and
        reason may not change.  Same instance across depths: shared
        executables, so any divergence is host-scheduling, not
        numerics."""
        reqs = [Request(_prompt(10 + i, 3 + 5 * i), 25, rid=i)
                for i in range(6)]
        # tokens the seeded model emits in some of these streams and not
        # in others (rid 3 meets neither); the assert below says so if a
        # jax release changes the draw
        loop = ServeLoop(CFG, params, num_slots=2, steps_per_sync=4,
                         decode_attention="flash", prefill_chunk=8,
                         stop_tokens=(6, 15))

        def sig(comps):
            return [(c.rid, tuple(c.tokens.tolist()), c.reason)
                    for c in comps]

        loop.pipeline_depth = 1
        sync = sig(loop.run(reqs))
        # the workload exercises BOTH finish paths under pipelining
        assert {r for _, _, r in sync} == {"stop", "length"}
        assert sorted(r for r, _, _ in sync) == list(range(6))
        loop.pipeline_depth = 2
        assert sig(loop.run(reqs)) == sync, "depth 2 diverged"
        loop.pipeline_depth = 3
        assert sorted(sig(loop.run(reqs))) == sorted(sync), \
            "depth 3 diverged"

    def test_default_depth_is_pipelined(self, params):
        loop = ServeLoop(CFG, params, num_slots=1)
        assert loop.pipeline_depth == 2

    def test_host_wait_recorded(self, params):
        """serve/host_wait must tick on a pipelined run (the fetch time
        the loop actually paid) and serve/pipeline_depth must be live."""
        from tpudist import obs

        loop = ServeLoop(CFG, params, num_slots=2, steps_per_sync=4,
                         prefill_chunk=8)
        before = obs.snapshot()["histograms"].get(
            "serve/host_wait", {}).get("count", 0)
        loop.run([Request(_prompt(60, 5), 9, rid=0),
                  Request(_prompt(61, 8), 6, rid=1)])
        snap = obs.snapshot()
        assert snap["histograms"]["serve/host_wait"]["count"] > before
        assert "serve/pipeline_depth" in snap["gauges"]


class TestPagedCache:
    """cache_layout='paged': the block-pool KV cache must be a pure
    LAYOUT change — token-identical to dense — while its HBM scales with
    reserved tokens and the pool drains back to free."""

    def _sig(self, comps):
        return [(c.rid, tuple(c.tokens.tolist()), c.reason) for c in comps]

    @pytest.mark.parametrize("depth", [1, 2])
    @pytest.mark.parametrize("attn", ["dense", "flash"])
    def test_paged_token_identical_to_dense(self, params, attn, depth):
        """The acceptance bar: paged greedy output is TOKEN-IDENTICAL to
        the dense layout at pipeline depths 1 and 2, on a mixed-length
        workload with queueing, stops, and slot reuse."""
        reqs = [Request(_prompt(70 + i, 3 + 5 * i), 20, rid=i)
                for i in range(5)]
        dense = ServeLoop(CFG, params, num_slots=2, steps_per_sync=4,
                          decode_attention=attn, prefill_chunk=8,
                          stop_tokens=(7, 13), pipeline_depth=depth)
        want = self._sig(dense.run(reqs))
        paged = ServeLoop(CFG, params, num_slots=2, steps_per_sync=4,
                          decode_attention=attn, prefill_chunk=8,
                          stop_tokens=(7, 13), pipeline_depth=depth,
                          cache_layout="paged", kv_block_size=16)
        assert self._sig(paged.run(reqs)) == want
        paged.pool.check()
        assert paged.pool.free_blocks == paged.pool.num_blocks

    def test_small_pool_queues_instead_of_oom(self, params):
        """A pool sized for ~one request at a time must still serve the
        whole workload (capacity gate queues, FIFO) and match dense."""
        reqs = [Request(_prompt(80 + i, 6), 10, rid=i) for i in range(4)]
        dense = ServeLoop(CFG, params, num_slots=2, steps_per_sync=4,
                          decode_attention="dense", prefill_chunk=8)
        want = {c.rid: tuple(c.tokens.tolist()) for c in dense.run(reqs)}
        # 2 blocks of 16 = 32 tokens: fits one request's 16-token
        # reservation (6 + 10), never two slots' worth at once
        paged = ServeLoop(CFG, params, num_slots=2, steps_per_sync=4,
                          decode_attention="dense", prefill_chunk=8,
                          cache_layout="paged", kv_block_size=16,
                          kv_num_blocks=2)
        got = {c.rid: tuple(c.tokens.tolist()) for c in paged.run(reqs)}
        assert got == want
        paged.pool.check()
        assert paged.pool.free_blocks == 2

    def test_paged_hbm_smaller_than_dense(self, params):
        """The point of the PR: at equal slot count, a right-sized pool's
        KV bytes are a fraction of the dense layout's B x S buffers."""
        def kv_bytes(loop):
            total = 0

            def walk(node):
                nonlocal total
                if not isinstance(node, dict):
                    return
                for k, v in node.items():
                    if k in ("cached_key", "cached_value", "paged_key",
                             "paged_value"):
                        total += v.size * v.dtype.itemsize
                    elif isinstance(v, dict):
                        walk(v)

            walk(loop.cache)
            return total

        dense = ServeLoop(CFG, params, num_slots=4, steps_per_sync=4,
                          decode_attention="dense")
        paged = ServeLoop(CFG, params, num_slots=4, steps_per_sync=4,
                          decode_attention="dense", cache_layout="paged",
                          kv_block_size=16, kv_num_blocks=6)
        assert kv_bytes(paged) < kv_bytes(dense) / 2

    def test_paged_validation(self, params):
        with pytest.raises(ValueError, match="cache_layout"):
            ServeLoop(CFG, params, num_slots=1, cache_layout="sparse")
        with pytest.raises(ValueError, match="block_size"):
            ServeLoop(CFG, params, num_slots=1, cache_layout="paged",
                      kv_block_size=12)
        import dataclasses
        # a windowed model serves paged since PR 31 (its own block group;
        # tests/test_window_serving.py); a segment's staged tokens must
        # fit in the window
        wcfg = dataclasses.replace(CFG, attention_window=16)
        with pytest.raises(ValueError, match="fit in the window"):
            ServeLoop(wcfg, params, num_slots=1, cache_layout="paged",
                      steps_per_sync=32)
        # a request whose reservation can NEVER fit the pool fails fast
        loop = ServeLoop(CFG, params, num_slots=1, cache_layout="paged",
                         kv_block_size=16, kv_num_blocks=2)
        with pytest.raises(ValueError, match="pool capacity"):
            loop.run([Request(_prompt(1, 40), 20)])

    def test_obs_gauges_live(self, params):
        from tpudist import obs

        loop = ServeLoop(CFG, params, num_slots=2, steps_per_sync=4,
                         prefill_chunk=8, cache_layout="paged",
                         kv_block_size=16)
        loop.run([Request(_prompt(90, 5), 8, rid=0)])
        g = obs.snapshot()["gauges"]
        assert g["serve/kv_blocks_used"]["value"] == 0          # drained
        assert g["serve/kv_blocks_free"]["value"] == loop.pool.num_blocks
        assert "serve/kv_frag" in g


class TestPromptDtypeValidation:
    """Regression: a float prompt used to silently truncate through
    _admit's np.asarray(prompt, np.int32) cast."""

    def test_float_prompt_rejected(self, params):
        loop = ServeLoop(CFG, params, num_slots=1)
        with pytest.raises(ValueError, match="integer token ids"):
            loop.run([Request(np.array([3.7, 5.2]), 4)])

    def test_integer_dtypes_accepted(self, params):
        loop = ServeLoop(CFG, params, num_slots=1, steps_per_sync=4,
                         prefill_chunk=8)
        for dt in (np.int32, np.int64, np.uint8):
            [c] = loop.run([Request(np.array([3, 5, 9], dt), 4, rid=dt)])
            np.testing.assert_array_equal(
                c.tokens, _want(params, c.prompt, 4))


class TestAdmissionControl:
    """ISSUE 6 satellites: bounded queue with load shedding and
    per-request deadlines that refund their KV reservation."""

    def test_max_queue_sheds_newest_as_rejected(self, params):
        import time

        from tpudist import obs

        before = obs.snapshot()["counters"].get(
            "serve/rejected", {}).get("value", 0)
        loop = ServeLoop(CFG, params, num_slots=1, steps_per_sync=4,
                         prefill_chunk=8, max_queue=1)
        reqs = [Request(_prompt(70 + i, 4), 6, rid=f"q{i}")
                for i in range(5)]
        comps = {c.rid: c for c in loop.run(reqs)}
        assert len(comps) == 5  # shed requests still get a Completion
        # q0 fills the slot, q1 holds the one queue place; the NEWEST
        # arrivals are shed (earlier arrivals keep their FIFO place)
        served = {r for r, c in comps.items() if c.reason == "length"}
        shed = {r for r, c in comps.items() if c.reason == "rejected"}
        assert served == {"q0", "q1"} and shed == {"q2", "q3", "q4"}
        for rid in shed:
            assert comps[rid].tokens.shape == (0,)
        for rid in served:
            np.testing.assert_array_equal(
                comps[rid].tokens, _want(params, comps[rid].prompt, 6))
        after = obs.snapshot()["counters"]["serve/rejected"]["value"]
        assert after - before == 3

    def test_max_queue_validation(self, params):
        with pytest.raises(ValueError, match="max_queue"):
            ServeLoop(CFG, params, num_slots=1, max_queue=-1)

    def test_expired_queued_deadline_times_out(self, params):
        import time

        loop = ServeLoop(CFG, params, num_slots=1, steps_per_sync=4,
                         prefill_chunk=8)
        comps = {c.rid: c for c in loop.run([
            Request(_prompt(1, 4), 6, rid="late",
                    deadline_s=time.time() - 100.0),
            Request(_prompt(2, 5), 6, rid="ok"),
        ])}
        assert comps["late"].reason == "timeout"
        assert comps["late"].tokens.shape == (0,)
        assert comps["ok"].reason == "length"
        np.testing.assert_array_equal(
            comps["ok"].tokens, _want(params, comps["ok"].prompt, 6))

    def test_inflight_deadline_refunds_paged_pool(self, params):
        """A request whose deadline passes MID-DECODE must finalize
        reason='timeout' with the tokens it produced so far, and its KV
        blocks must come back to the pool even with segments still in
        flight (the zombie-slot path) — then the loop must serve a fresh
        request exactly (requeue-safe finalize)."""
        import time

        loop = ServeLoop(CFG, params, num_slots=2, steps_per_sync=4,
                         prefill_chunk=8, cache_layout="paged",
                         kv_block_size=16)
        # deterministic expiry: the swappable clock jumps past the
        # deadline on its 9th read — after admission and a few
        # dispatched segments, before the 40-token budget completes
        t0 = time.time()
        calls = [0]

        def clock():
            calls[0] += 1
            return t0 + (1000.0 if calls[0] > 8 else 0.0)

        loop._clock = clock
        [c] = loop.run([Request(_prompt(77, 6), 40, rid="doomed",
                                deadline_s=t0 + 500.0)])
        assert c.reason == "timeout"
        assert 0 < c.tokens.shape[0] < 40  # partial, mid-decode
        # the partial output is a prefix of the uninterrupted rollout
        np.testing.assert_array_equal(
            c.tokens, _want(params, c.prompt, 40)[:c.tokens.shape[0]])
        # no orphaned blocks: the reservation was refunded in full
        loop.pool.check()
        assert loop.pool.free_blocks == loop.pool.num_blocks
        # the loop (and the recycled blocks) still serve exactly
        loop._clock = time.time
        [c2] = loop.run([Request(_prompt(78, 5), 12, rid="next")])
        assert c2.reason == "length"
        np.testing.assert_array_equal(
            c2.tokens, _want(params, c2.prompt, 12))


class TestServiceMode:
    """run(source=..., sink=...): incremental intake for the fleet's
    replica worker, with streaming completions."""

    def test_incremental_intake_streams_to_sink(self, params):
        loop = ServeLoop(CFG, params, num_slots=2, steps_per_sync=4,
                         prefill_chunk=8)
        batches = iter([
            [Request(_prompt(1, 4), 6, rid="a"),
             Request(_prompt(2, 9), 8, rid="b")],
            [],                                     # idle poll
            [Request(_prompt(3, 6), 5, rid="c")],   # late arrival
            None,                                   # close + drain
        ])
        streamed = []
        out = loop.run(source=lambda: next(batches),
                       sink=streamed.append, idle_wait_s=0.0)
        assert sorted(c.rid for c in out) == ["a", "b", "c"]
        assert [c.rid for c in streamed] == [c.rid for c in out]
        for c in out:
            assert c.reason == "length"
            np.testing.assert_array_equal(
                c.tokens,
                _want(params, c.prompt, c.tokens.shape[0]))

    def test_malformed_request_completes_invalid(self, params):
        """Service mode can't raise on a bad wire request (the loop must
        keep serving the fleet) — it completes reason='invalid'."""
        loop = ServeLoop(CFG, params, num_slots=1, steps_per_sync=4,
                         prefill_chunk=8)
        batches = iter([
            [Request(_prompt(1, 90), 20, rid="toolong"),
             Request(_prompt(1, 4), 6, rid="fine")],
            None,
        ])
        comps = {c.rid: c for c in loop.run(source=lambda: next(batches),
                                            idle_wait_s=0.0)}
        assert comps["toolong"].reason == "invalid"
        assert comps["toolong"].tokens.shape == (0,)
        np.testing.assert_array_equal(
            comps["fine"].tokens, _want(params, comps["fine"].prompt, 6))


@pytest.fixture(scope="module")
def params_v2():
    """A SECOND weight set (different init seed): the hot-swap target.
    Same shapes/dtypes as ``params``, so the rebind must not retrace."""
    return TransformerLM(CFG).init(
        jax.random.key(1), jnp.zeros((1, 2), jnp.int32))["params"]


def _want2(params_v2, prompt, n):
    out = greedy_generate(CFG, params_v2, jnp.asarray(prompt)[None, :], n)
    return np.asarray(out)[0, len(prompt):]


class TestWeightHotSwap:
    """request_swap: drain-gated rebind of self.params.  The contract —
    requests admitted before the swap complete on the OLD weights,
    requests admitted after decode on the NEW ones, and no request ever
    straddles versions."""

    def test_midstream_swap_drains_then_rebinds(self, params, params_v2):
        """old0/old1 hold the two slots when the swap arrives (old
        weights); q is QUEUED behind them — never admitted pre-swap, so
        the barrier holds it for the NEW weights; new0/new1 arrive with
        the swap request itself."""
        loop = ServeLoop(CFG, params, num_slots=2, steps_per_sync=4,
                         prefill_chunk=8)
        old = [Request(_prompt(60 + i, 4 + 2 * i), 8 + i, rid=f"old{i}")
               for i in range(2)]
        queued = Request(_prompt(65, 6), 7, rid="q")
        new = [Request(_prompt(70 + i, 5 + i), 6 + 2 * i, rid=f"new{i}")
               for i in range(2)]
        events = []
        polls = {"n": 0}

        def source():
            polls["n"] += 1
            if polls["n"] == 1:
                return old + [queued]
            if polls["n"] == 2:
                # swap requested while old requests are still decoding;
                # the new batch arrives in the SAME poll and must wait
                # behind the admission barrier
                loop.request_swap(
                    lambda: params_v2, version=7,
                    on_swapped=lambda: events.append("swapped"))
                return new
            done = sum(1 for e in events if e != "swapped")
            return None if done == len(old) + len(new) + 1 else []

        comps = {c.rid: c for c in loop.run(
            source=source, sink=lambda c: events.append(c.rid),
            idle_wait_s=0.0)}
        assert len(comps) == 5
        for i, r in enumerate(old):
            np.testing.assert_array_equal(
                comps[r.rid].tokens, _want(params, r.prompt, 8 + i),
                err_msg=f"{r.rid} must decode on the OLD weights")
        np.testing.assert_array_equal(
            comps["q"].tokens, _want2(params_v2, queued.prompt, 7),
            err_msg="a request still queued at swap time is held by the "
                    "admission barrier and decodes on the NEW weights")
        for i, r in enumerate(new):
            np.testing.assert_array_equal(
                comps[r.rid].tokens, _want2(params_v2, r.prompt, 6 + 2 * i),
                err_msg=f"{r.rid} must decode on the NEW weights")
        # ordering: every pre-swap completion lands before on_swapped,
        # every post-swap one after — the drain gate, observed
        swap_at = events.index("swapped")
        assert {e for e in events[:swap_at]} == {r.rid for r in old}
        assert {e for e in events[swap_at + 1:]} == (
            {r.rid for r in new} | {"q"})
        from tpudist import obs
        assert obs.snapshot()["gauges"][
            "serve/weights_version"]["value"] == 7

    def test_swap_between_runs_no_retrace(self, params, params_v2):
        loop = ServeLoop(CFG, params, num_slots=2, steps_per_sync=4,
                         prefill_chunk=8)
        req = Request(_prompt(80, 6), 9, rid="a")
        [c1] = loop.run([Request(_prompt(80, 6), 9, rid="a")])
        np.testing.assert_array_equal(c1.tokens, _want(params, req.prompt, 9))
        traced = (loop._segment._cache_size()
                  if hasattr(loop._segment, "_cache_size") else None)
        loop.request_swap(lambda: params_v2, version=2)
        [c2] = loop.run([Request(_prompt(80, 6), 9, rid="a")])
        np.testing.assert_array_equal(
            c2.tokens, _want2(params_v2, req.prompt, 9))
        if traced is not None:
            # params is a jit ARGUMENT with unchanged avals: the swap
            # must not have grown the executable cache
            assert loop._segment._cache_size() == traced

    @pytest.mark.parametrize("depth", [1, 2])
    def test_swap_paged_layout_drains_pool(self, params, params_v2, depth):
        loop = ServeLoop(CFG, params, num_slots=2, steps_per_sync=4,
                         prefill_chunk=8, cache_layout="paged",
                         kv_block_size=16, pipeline_depth=depth)
        old = [Request(_prompt(82, 7), 8, rid="old")]
        new = [Request(_prompt(83, 5), 7, rid="new")]
        polls = {"n": 0}
        seen = []

        def source():
            polls["n"] += 1
            if polls["n"] == 1:
                return old
            if polls["n"] == 2:
                loop.request_swap(lambda: params_v2, version=3)
                return new
            return None if len(seen) == 2 else []

        comps = {c.rid: c for c in loop.run(
            source=source, sink=seen.append, idle_wait_s=0.0)}
        np.testing.assert_array_equal(
            comps["old"].tokens, _want(params, old[0].prompt, 8))
        np.testing.assert_array_equal(
            comps["new"].tokens, _want2(params_v2, new[0].prompt, 7))
        assert loop.pool.used_blocks == 0  # fully drained through the swap

    def test_failed_restore_keeps_old_weights_and_completes(self, params):
        """params_fn returning None (missing snapshot): the rebind is
        skipped but the swap COMPLETES — on_swapped fires, admission
        resumes, and the queued request decodes on the old weights."""
        loop = ServeLoop(CFG, params, num_slots=1, steps_per_sync=4,
                         prefill_chunk=8)
        fired = []
        loop.request_swap(lambda: None, version=9,
                          on_swapped=lambda: fired.append(True))
        req = Request(_prompt(85, 5), 8, rid="q")
        [c] = loop.run([Request(_prompt(85, 5), 8, rid="q")])
        assert fired == [True]
        np.testing.assert_array_equal(c.tokens, _want(params, req.prompt, 8))

    def test_idle_swap_applies_immediately(self, params, params_v2):
        """No traffic in flight: the swap lands on the next loop tick,
        before any later admission."""
        loop = ServeLoop(CFG, params, num_slots=1, steps_per_sync=4,
                         prefill_chunk=8)
        loop.request_swap(lambda: params_v2, version=1)
        req = Request(_prompt(86, 4), 6, rid="q")
        [c] = loop.run([Request(_prompt(86, 4), 6, rid="q")])
        np.testing.assert_array_equal(
            c.tokens, _want2(params_v2, req.prompt, 6))


class TestOverloadDegradation:
    """ISSUE 9 overload tiers: priority-ordered shedding past the hard
    queue bound, and the soft DEGRADED watermark that clamps
    best-effort budgets before anything must be rejected."""

    def test_shed_takes_lowest_priority_newest_first(self, params):
        """Past ``max_queue`` the victim is the NEWEST request of the
        LOWEST priority class — priority traffic survives overload even
        when it arrived last."""
        loop = ServeLoop(CFG, params, num_slots=1, steps_per_sync=4,
                         prefill_chunk=8, max_queue=1)
        reqs = [Request(_prompt(60 + i, 4), 6, rid=f"q{i}",
                        priority=(1 if i == 4 else 0))
                for i in range(5)]
        comps = {c.rid: c for c in loop.run(reqs)}
        served = {r for r, c in comps.items() if c.reason == "length"}
        shed = {r for r, c in comps.items() if c.reason == "rejected"}
        # q0 fills the slot; q4 (priority 1, newest arrival) outranks
        # the whole best-effort backlog for the one queue place
        assert served == {"q0", "q4"} and shed == {"q1", "q2", "q3"}
        for rid in served:
            np.testing.assert_array_equal(
                comps[rid].tokens, _want(params, comps[rid].prompt, 6))

    def test_degraded_clamps_best_effort_not_priority(self, params):
        """Past the soft watermark, best-effort admissions get a short
        answer (budget clamped to ``degrade_max_new``) instead of a
        later rejection; priority admissions keep their full budget.
        Results stay exact — a clamped request IS a shorter request."""
        from tpudist import obs

        c0 = obs.snapshot()["counters"].get(
            "serve/degrade_clamped", {}).get("value", 0)
        loop = ServeLoop(CFG, params, num_slots=1, steps_per_sync=4,
                         prefill_chunk=8, degrade_queue=0,
                         degrade_max_new=2)
        reqs = [Request(_prompt(50, 4), 6, rid="head"),
                Request(_prompt(51, 4), 6, rid="cheap"),
                Request(_prompt(52, 4), 6, rid="vip", priority=1)]
        comps = {c.rid: c for c in loop.run(reqs)}
        assert all(c.reason == "length" for c in comps.values())
        # head admitted before the backlog built: full budget
        assert comps["head"].tokens.shape == (6,)
        # cheap admitted DEGRADED: clamped, but exact for its budget
        assert comps["cheap"].tokens.shape == (2,)
        np.testing.assert_array_equal(
            comps["cheap"].tokens,
            _want(params, comps["cheap"].prompt, 2))
        # vip admitted from the same degraded backlog: untouched
        assert comps["vip"].tokens.shape == (6,)
        c1 = obs.snapshot()["counters"]["serve/degrade_clamped"]["value"]
        assert c1 - c0 == 1
        # queue emptied at the end: the loop left degraded mode
        assert obs.snapshot()["gauges"]["serve/degraded"]["value"] == 0.0

    def test_degrade_queue_defaults_and_validation(self, params):
        loop = ServeLoop(CFG, params, num_slots=1, max_queue=8)
        assert loop.degrade_queue == 4      # soft watermark: half hard
        loop = ServeLoop(CFG, params, num_slots=1)
        assert loop.degrade_queue is None   # unbounded queue: no tiers
        with pytest.raises(ValueError, match="degrade_queue"):
            ServeLoop(CFG, params, num_slots=1, degrade_queue=-1)
        with pytest.raises(ValueError, match="degrade_max_new"):
            ServeLoop(CFG, params, num_slots=1, degrade_queue=2,
                      degrade_max_new=0)
