"""Fused multi-token decode (PR 8): the on-device N-step inner loop must
stay token-identical to the single-token reference loop across layouts
and depths, while paying ~1/N of its host dispatches.  A lane decodes ONE
token a step: the per-row cache paths and the decode kernels refuse more."""

import inspect
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.models.serving import Request, ServeLoop
from tpudist.models.transformer import TransformerConfig, TransformerLM
from tpudist.ops.flash_decode import flash_decode, paged_flash_decode

CFG = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                        num_kv_heads=2, embed_dim=64, max_seq_len=96)


@pytest.fixture(scope="module")
def params():
    return TransformerLM(CFG).init(
        jax.random.key(0), jnp.zeros((1, 2), jnp.int32))["params"]


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


# the three per-row paths of CausalSelfAttention, by the loop that takes each
PER_ROW = {
    "dense_direct": dict(decode_attention="dense"),
    "dense_sided": dict(decode_attention="flash"),
    "paged": dict(decode_attention="flash", cache_layout="paged",
                  kv_block_size=16),
}


class TestOneTokenALaneAStep:
    @pytest.mark.parametrize("path", PER_ROW)
    def test_per_row_cache_refuses_two_tokens(self, params, path):
        loop = ServeLoop(CFG, params, num_slots=2, steps_per_sync=8,
                         auto_unstack=False, **PER_ROW[path])
        toks = jnp.ones((2, 2), jnp.int32)
        with pytest.raises(ValueError, match="one token a lane a call"):
            loop.model.apply({"params": params, "cache": loop.cache}, toks,
                             positions=jnp.zeros((2, 2), jnp.int32),
                             mutable=["cache"])

    @pytest.mark.parametrize("kernel", ["flash_decode",
                                        "paged_flash_decode"])
    def test_decode_kernels_refuse_two_queries(self, kernel):
        b, h, h_kv, d, rows = 2, 4, 2, 8, 8
        ks = jax.random.split(jax.random.key(11), 5)
        q = jax.random.normal(ks[0], (b, 2, h, d), jnp.float32)
        k, v, side_k, side_v = (
            jax.random.normal(key, (b, rows, h_kv * d), jnp.float32)
            for key in ks[1:])
        lens = jnp.array([3, 5], jnp.int32)
        side = dict(side_k=side_k, side_v=side_v, side_len=2,
                    packed_kv_heads=h_kv, interpret=True)
        with pytest.raises(ValueError, match="one query token a call"):
            if kernel == "flash_decode":
                flash_decode(q, k, v, lens, **side)
            else:
                paged_flash_decode(
                    q, k, v, jnp.arange(b, dtype=jnp.int32)[:, None], lens,
                    **side)

    @pytest.mark.parametrize("path", ["dense_sided", "paged"])
    def test_side_buffers_hold_a_segment(self, params, path):
        loop = ServeLoop(CFG, params, num_slots=2, steps_per_sync=8,
                         auto_unstack=False, **PER_ROW[path])
        sides = {leaf.shape[1] for path_, leaf in
                 jax.tree_util.tree_leaves_with_path(loop.cache)
                 if getattr(path_[-1], "key", "").startswith("side_")
                 and leaf.ndim == 3}
        assert loop.side == 8 and sides == {8}


def test_serve_loop_signature_is_documented():
    """Every keyword of ``ServeLoop.__init__`` has an entry in the class
    docstring, and the docstring's entries name no keyword it lacks."""
    params = inspect.signature(ServeLoop.__init__).parameters
    keywords = {n for n, p in params.items() if p.kind is p.KEYWORD_ONLY}
    args = ServeLoop.__doc__.split("Args:")[1]
    heads = re.findall(r"^      (\w[\w /]*):", args, flags=re.M)
    named = {n for head in heads for n in head.split(" / ")}
    assert named - {"cfg", "params", "num_slots"} == keywords
