"""Fused multi-token decode + speculative serving (PR 8): the on-device
N-step inner loop must stay token-identical to the single-token
reference loop across layouts, depths, and decode modes, while paying
~1/N of its host dispatches."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.models.serving import Request, ServeLoop
from tpudist.models.transformer import TransformerConfig, TransformerLM
from tpudist.ops.flash_decode import flash_decode, paged_flash_decode

CFG = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                        num_kv_heads=2, embed_dim=64, max_seq_len=96)
DRAFT_CFG = TransformerConfig(vocab_size=64, num_layers=1, num_heads=2,
                              num_kv_heads=1, embed_dim=32, max_seq_len=96)


@pytest.fixture(scope="module")
def params():
    return TransformerLM(CFG).init(
        jax.random.key(0), jnp.zeros((1, 2), jnp.int32))["params"]


@pytest.fixture(scope="module")
def draft_params():
    return TransformerLM(DRAFT_CFG).init(
        jax.random.key(7), jnp.zeros((1, 2), jnp.int32))["params"]


def _prompt(seed, n):
    return np.asarray(jax.random.randint(
        jax.random.key(seed), (n,), 1, 64, dtype=jnp.int32))


def _reqs():
    return [Request(prompt=_prompt(i, 5 + 3 * i), max_new_tokens=12, rid=i)
            for i in range(4)]


def _serve(params, reqs, **kw):
    loop = ServeLoop(CFG, params, num_slots=2, prefill_chunk=16,
                     stop_tokens=(1,), auto_unstack=False, **kw)
    comps = loop.run(reqs)
    return {c.rid: list(c.tokens) for c in comps}, loop


@pytest.fixture(scope="module")
def reference(params):
    """The single-token loop: one host dispatch per generated token."""
    got, _ = _serve(params, _reqs(), steps_per_sync=1, pipeline_depth=1,
                    decode_attention="dense")
    return got


class TestFusedExactMatch:
    @pytest.mark.parametrize("depth", [1, 2])
    @pytest.mark.parametrize("attn,layout", [
        ("dense", "dense"), ("flash", "dense"),
        ("flash", "paged"), ("dense", "paged")])
    def test_matches_single_token_loop(self, params, reference, depth,
                                       attn, layout):
        kw = dict(steps_per_sync=8, pipeline_depth=depth,
                  decode_attention=attn)
        if layout == "paged":
            kw.update(cache_layout="paged", kv_block_size=16)
        got, loop = _serve(params, _reqs(), **kw)
        assert got == reference
        if loop.pool is not None:
            assert loop.pool.used_blocks == 0
            loop.pool.check()

    @pytest.mark.parametrize("n_fused,budget,bound,fewer_than_single", [
        (8, 12, 1 / 4, None),       # short answers: admission waves weigh
        (16, 48, 1 / 16 + 0.02, 8.0)], ids=["n8-short", "n16-long"])
    def test_fewer_dispatches(self, params, n_fused, budget, bound,
                              fewer_than_single):
        """The amortization itself: an N-step segment serves the batch's
        tokens in a handful of host dispatches (admission waves add a
        few), never one per token — at N=16 and 48-token answers at most
        1/N + 0.02 a token, and at least 8x fewer than the single-token
        loop pays for the same requests."""
        def dispatches_per_token(steps_per_sync):
            loop = ServeLoop(CFG, params, num_slots=2, prefill_chunk=16,
                             auto_unstack=False,
                             steps_per_sync=steps_per_sync,
                             pipeline_depth=2, decode_attention="flash")
            # the obs counter is registry-global (shared across loops in
            # this process) — diff around the run
            before = loop._obs_dispatches.value()
            comps = loop.run([Request(prompt=_prompt(i, 5 + 3 * i),
                                      max_new_tokens=budget, rid=i)
                              for i in range(4)])
            n_tokens = sum(len(c.tokens) for c in comps)
            assert n_tokens == 4 * budget
            return (loop._obs_dispatches.value() - before) / n_tokens

        fused = dispatches_per_token(n_fused)
        assert fused <= bound, fused
        if fewer_than_single:
            assert dispatches_per_token(1) / fused >= fewer_than_single

    def test_mid_segment_eos(self, params, reference):
        """Requests whose stop token lands mid-segment (not at an N
        boundary) finalize with identical tokens — the in-graph freeze +
        host slice drop everything past the stop."""
        got, _ = _serve(params, _reqs(), steps_per_sync=16,
                        pipeline_depth=2, decode_attention="flash")
        assert got == reference

    def test_tight_pool_reservation(self, params):
        """A pool sized exactly to the concurrent footprint: lanes run
        their reservation to the cap mid-segment, freeze in-graph at
        budget end, and the queued request admits after the refund —
        with exact tokens and a fully drained pool."""
        reqs = [Request(prompt=_prompt(i, 6), max_new_tokens=20, rid=i)
                for i in range(3)]
        want, _ = _serve(params, [Request(prompt=_prompt(i, 6),
                                          max_new_tokens=20, rid=i)
                                  for i in range(3)],
                         steps_per_sync=1, pipeline_depth=1,
                         decode_attention="dense")
        # 2 slots x ceil(26/8)=4 blocks == the whole 8-block pool
        got, loop = _serve(params, reqs, steps_per_sync=16,
                           pipeline_depth=2, decode_attention="flash",
                           cache_layout="paged", kv_block_size=8,
                           kv_num_blocks=8)
        assert got == want
        assert loop.pool.used_blocks == 0
        loop.pool.check()


class TestDeadlineClamp:
    def _state(self, deadline):
        return [{"req": Request(prompt=_prompt(0, 4), max_new_tokens=30,
                                rid=0, deadline_s=deadline),
                 "seq": 0, "tokens": [], "pending_first": False}]

    def test_clamps_to_slack(self, params):
        loop = ServeLoop(CFG, params, num_slots=1, steps_per_sync=32,
                         auto_unstack=False, decode_attention="dense")
        t = [0.0]
        loop._clock = lambda: t[0]
        loop._step_ema = 1.0                      # 1 s/token, forced
        assert loop._plan_steps(self._state(10.0)) == 10
        t[0] = 9.5
        assert loop._plan_steps(self._state(10.0)) == 1
        # no deadline in flight -> full segments
        assert loop._plan_steps(self._state(None)) == 32
        # no EMA yet -> full segments (first dispatch measures it)
        loop._step_ema = None
        assert loop._plan_steps(self._state(0.5)) == 32

    def test_timeout_precision(self, params):
        """With the clamp, a deadline expiring early in a long segment
        is honored within ~a segment of ONE token, not steps_per_sync:
        the killed request keeps at most a couple of tokens."""
        loop = ServeLoop(CFG, params, num_slots=1, steps_per_sync=32,
                         auto_unstack=False, decode_attention="flash",
                         pipeline_depth=1)
        t = [0.0]
        loop._clock = lambda: t[0]
        loop._step_ema = 1.0                      # 1 s/token, forced

        orig = loop._segment

        def ticking_segment(*a):
            out = orig(*a)
            t[0] += float(np.asarray(a[7]))       # n_steps seconds
            return out

        loop._segment = ticking_segment
        [c] = loop.run([Request(prompt=_prompt(3, 5), max_new_tokens=30,
                                rid="d", deadline_s=4.0)])
        assert c.reason == "timeout"
        # 1 token/s against a 4 s deadline: ~4 tokens, never the 30 a
        # full unclamped 32-step segment would have produced
        assert len(c.tokens) <= 6


class TestSpeculativeServe:
    @pytest.mark.parametrize("kw", [
        dict(pipeline_depth=1, decode_attention="dense", num_draft=3),
        dict(pipeline_depth=2, decode_attention="dense",
             num_draft="adaptive", spec_ladder=(2, 4)),
        dict(pipeline_depth=2, decode_attention="flash", num_draft=3),
        dict(pipeline_depth=2, decode_attention="flash", num_draft=3,
             cache_layout="paged", kv_block_size=16),
    ], ids=["dense-k3", "dense-adaptive", "flash-k3", "paged-k3"])
    def test_greedy_exact_match(self, params, draft_params, reference, kw):
        got, loop = _serve(params, _reqs(), steps_per_sync=8,
                           decode_mode="speculative", draft_cfg=DRAFT_CFG,
                           draft_params=draft_params, **kw)
        assert got == reference
        if loop.pool is not None:
            assert loop.pool.used_blocks == 0
            loop.pool.check()

    def test_obs_and_policy_updates(self, params, draft_params):
        got, loop = _serve(params, _reqs(), steps_per_sync=8,
                           pipeline_depth=2, decode_attention="dense",
                           decode_mode="speculative", draft_cfg=DRAFT_CFG,
                           draft_params=draft_params,
                           num_draft="adaptive", spec_ladder=(2, 4))
        assert loop._obs_dispatches.value() > 0
        assert loop._obs_spec_k.value() in (2, 4)
        assert 0.0 <= loop._obs_spec_accept.value() <= 1.0
        assert loop._spec_policy.rounds_seen > 0

    def test_headroom_validation(self, params, draft_params):
        loop = ServeLoop(CFG, params, num_slots=1, auto_unstack=False,
                         decode_attention="dense",
                         decode_mode="speculative", draft_cfg=DRAFT_CFG,
                         draft_params=draft_params, num_draft=8)
        # prompt + max_new + k - 1 = 60 + 30 + 7 = 97 > 96
        with pytest.raises(ValueError, match="speculative serving"):
            loop._validate(Request(prompt=_prompt(0, 60),
                                   max_new_tokens=30))

    def test_requires_draft(self, params):
        with pytest.raises(ValueError, match="draft_cfg"):
            ServeLoop(CFG, params, num_slots=1, auto_unstack=False,
                      decode_mode="speculative")


class TestMultiQueryDecodeKernels:
    """flash_decode / paged_flash_decode with s_q > 1 (the verify
    chunk): per-query side visibility must match s_q independent calls."""

    def _setup(self, b=2, h=4, h_kv=2, d=8, s_cache=32, cap=8):
        ks = jax.random.split(jax.random.key(11), 5)
        flat = h_kv * d
        q = jax.random.normal(ks[0], (b, 3, h, d), jnp.float32)
        k_cache = jax.random.normal(ks[1], (b, s_cache, flat), jnp.float32)
        v_cache = jax.random.normal(ks[2], (b, s_cache, flat), jnp.float32)
        side_k = jax.random.normal(ks[3], (b, cap, flat), jnp.float32)
        side_v = jax.random.normal(ks[4], (b, cap, flat), jnp.float32)
        lens = jnp.array([5, 9], jnp.int32)
        return q, k_cache, v_cache, side_k, side_v, lens, h_kv

    def test_dense_multi_query_matches_per_token(self):
        q, kc, vc, sk, sv, lens, h_kv = self._setup()
        side_len = 6   # AFTER all 3 writes: queries see 4, 5, 6 side slots
        got = flash_decode(q, kc, vc, lens, side_k=sk, side_v=sv,
                           side_len=side_len, packed_kv_heads=h_kv,
                           interpret=True)
        for j in range(3):
            want = flash_decode(q[:, j:j + 1], kc, vc, lens, side_k=sk,
                                side_v=sv, side_len=side_len - (2 - j),
                                packed_kv_heads=h_kv, interpret=True)
            np.testing.assert_allclose(np.asarray(got[:, j:j + 1]),
                                       np.asarray(want), rtol=2e-5,
                                       atol=2e-5)

    def test_multi_query_requires_side(self):
        q, kc, vc, *_ , lens, h_kv = self._setup()
        with pytest.raises(ValueError, match="side buffers"):
            flash_decode(q, kc, vc, lens, packed_kv_heads=h_kv,
                         interpret=True)

    def test_paged_multi_query_matches_per_token(self):
        b, h, h_kv, d, bs = 2, 4, 2, 8, 8
        flat = h_kv * d
        m = 4                                     # blocks per slot
        ks = jax.random.split(jax.random.key(13), 5)
        q = jax.random.normal(ks[0], (b, 3, h, d), jnp.float32)
        pool_k = jax.random.normal(ks[1], (b * m + 1, bs, flat))
        pool_v = jax.random.normal(ks[2], (b * m + 1, bs, flat))
        table = jnp.arange(b * m, dtype=jnp.int32).reshape(b, m)
        side_k = jax.random.normal(ks[3], (b, 8, flat))
        side_v = jax.random.normal(ks[4], (b, 8, flat))
        lens = jnp.array([5, 9], jnp.int32)
        side_len = 5
        got = paged_flash_decode(q, pool_k, pool_v, table, lens,
                                 side_k=side_k, side_v=side_v,
                                 side_len=side_len, packed_kv_heads=h_kv,
                                 interpret=True)
        for j in range(3):
            want = paged_flash_decode(
                q[:, j:j + 1], pool_k, pool_v, table, lens, side_k=side_k,
                side_v=side_v, side_len=side_len - (2 - j),
                packed_kv_heads=h_kv, interpret=True)
            np.testing.assert_allclose(np.asarray(got[:, j:j + 1]),
                                       np.asarray(want), rtol=2e-5,
                                       atol=2e-5)
