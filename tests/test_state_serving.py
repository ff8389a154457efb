"""Layers whose past is a STATE through ``ServeLoop``: the conv tail and the
matrix state live per slot beside the full layers' K/V pages; a step the
lane does not own leaves them as they were; a slot starts blank; what the
loop cannot do with state yet is refused in words."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import olmo_hybrid as ref
from tests.test_linear_attention import dims_of, init_params, tiny_cfg
from tpudist import obs
from tpudist.models import Request, ServeLoop
from tpudist.models.serving import _state_nodes

OPTS = dict(num_slots=3, steps_per_sync=4, prefill_chunk=16,
            cache_layout="paged", kv_block_size=8, kv_num_blocks=48,
            max_prefill_lanes=2)
# float32 program against float32-HIGHEST reference: the 5th digit
LOGIT_TOL = 5e-4


@functools.cache
def _model():
    cfg = tiny_cfg()
    return cfg, init_params(cfg)


def _loop(**over):
    cfg, params = _model()
    return ServeLoop(cfg, params, **{**OPTS, **over})


def _requests(seed, n, lo=5, hi=60, new=(3, 14)):
    rng = np.random.default_rng(seed)
    cfg, _ = _model()
    return [Request(rng.integers(0, cfg.vocab_size,
                                 int(rng.integers(lo, hi))).astype(np.int32),
                    int(rng.integers(*new)), rid=f"r{i}")
            for i in range(n)]


def _worst_gap(comps) -> float:
    """How far a served token's logit lies below the reference's best."""
    cfg, params = _model()
    fwd = ref.Forward(dims_of(cfg))
    worst = 0.0
    for c in comps:
        served = np.asarray(c.tokens)
        seq = np.concatenate([np.asarray(c.prompt), served[:-1]])
        padded = np.zeros(cfg.max_seq_len, np.int32)
        padded[:len(seq)] = seq
        logits = np.asarray(fwd.logits(
            params, jnp.asarray(padded), len(c.prompt) - 1))[:len(served)]
        gaps = logits.max(-1) - logits[np.arange(len(served)), served]
        worst = max(worst, float(gaps.max()))
    return worst


@pytest.mark.parametrize("layout", ["paged", "dense"])
def test_served_tokens_are_the_references_best(layout):
    """More requests than lanes, prompts of one to four chunks with padded
    last chunks, lanes reused: every served token is (to rounding) the
    token the reference's full forward puts first."""
    loop = _loop(cache_layout=layout)
    reqs = _requests(1, 8)
    done = loop.run(reqs)
    assert sorted(c.rid for c in done) == sorted(r.rid for r in reqs)
    assert all(c.reason == "length" for c in done)
    assert all(len(c.tokens) == r.max_new_tokens
               for c, r in zip(sorted(done, key=lambda c: c.rid),
                               sorted(reqs, key=lambda r: r.rid)))
    assert _worst_gap(done) < LOGIT_TOL


def test_a_reused_slot_starts_blank():
    """One lane, two requests in turn: the second's tokens are what it gets
    alone in a fresh loop (a state left over from the first would show)."""
    reqs = _requests(2, 2, lo=20, hi=40, new=(6, 7))
    both = {c.rid: c.tokens for c in _loop(num_slots=1).run(reqs)}
    alone = _loop(num_slots=1).run(reqs[1:])[0]
    np.testing.assert_array_equal(both[reqs[1].rid], alone.tokens)
    assert _worst_gap([alone]) < LOGIT_TOL


def _seeded_segment_inputs(loop, seed=0):
    """A slot cache whose state leaves hold numbers, and lanes to run."""
    key = jax.random.key(seed)

    def fill(node):
        if not isinstance(node, dict):
            return node
        out = {k: fill(v) for k, v in node.items()}
        if "state" in out:
            for name in ("state", "conv"):
                out[name] = jax.random.normal(
                    jax.random.fold_in(key, out[name].size),
                    out[name].shape, out[name].dtype)
        return out

    loop._stamp_table()
    return fill(loop.cache)


def _states(cache):
    return [{k: np.asarray(v) for k, v in node.items()}
            for node in _state_nodes(cache)]


def test_a_step_the_lane_does_not_own_moves_no_state():
    """One segment of 4 steps over three lanes: lane 0 decodes all four,
    lane 1 is empty (not active), lane 2 has a budget of 2 and is past
    ``remaining`` for the last two.  Lane 1 ends with the state it had, bit
    for bit; lane 2 with the state a segment of 2 steps leaves."""
    cfg, params = _model()
    results = {}
    for n_steps in (4, 2):
        loop = _loop()
        cache = _seeded_segment_inputs(loop)
        before = _states(cache)
        tok = jnp.asarray([5, 6, 7], jnp.int32)
        active = jnp.asarray([True, False, True])
        remaining = jnp.asarray([9, 9, 2], jnp.int32)
        out = loop._segment(params, cache, tok, active, remaining,
                            loop._first, loop._key, jnp.int32(n_steps),
                            jnp.bool_(False))
        results[n_steps] = (before, _states(out[0]), np.asarray(out[5]))
    before, after4, emits4 = results[4]
    _, after2, _ = results[2]
    assert len(before) == 3                      # the three linear layers
    for old, new4, new2 in zip(before, after4, after2):
        for leaf in ("state", "conv"):
            np.testing.assert_array_equal(new4[leaf][1], old[leaf][1])
            np.testing.assert_array_equal(new4[leaf][2], new2[leaf][2])
            assert not np.array_equal(new4[leaf][0], new2[leaf][0])
            assert not np.array_equal(new4[leaf][2], old[leaf][2])
    # lane 2 emitted its two tokens and then pads
    assert (emits4[2, 3:] == loop.pad_token).all()


def test_a_frozen_lane_keeps_its_state_over_whole_segments():
    """A lane frozen for ALL of a segment (its request finished; the host
    has not released it) keeps its state; the lane beside it moves."""
    cfg, params = _model()
    loop = _loop()
    cache = _seeded_segment_inputs(loop, 3)
    before = _states(cache)
    out = loop._segment(params, cache, jnp.asarray([1, 2, 3], jnp.int32),
                        jnp.asarray([False, True, False]),
                        jnp.asarray([0, 5, 0], jnp.int32), loop._first,
                        loop._key, jnp.int32(4), jnp.bool_(False))
    for old, new in zip(before, _states(out[0])):
        for leaf in ("state", "conv"):
            np.testing.assert_array_equal(new[leaf][0], old[leaf][0])
            np.testing.assert_array_equal(new[leaf][2], old[leaf][2])
            assert not np.array_equal(new[leaf][1], old[leaf][1])


def test_padded_rows_of_a_last_chunk_move_no_state():
    """The chunk program with the prompt ending at row 4 of 16 leaves the
    state five tokens leave."""
    cfg, params = _model()
    loop = _loop()
    toks = np.zeros((1, 16), np.int32)
    toks[0, :5] = [9, 8, 7, 6, 5]
    padded, _ = loop._prefill_chunk(params, loop._blank1, toks, np.int32(0),
                                    np.int32(4), chunk=16)
    junk = toks.copy()
    junk[0, 5:] = 11
    other, _ = loop._prefill_chunk(params, loop._blank1, junk, np.int32(0),
                                   np.int32(4), chunk=16)
    for a, b in zip(_states(padded), _states(other)):
        for leaf in ("state", "conv"):
            np.testing.assert_array_equal(a[leaf], b[leaf])
            assert np.abs(a[leaf]).max() > 0


def test_state_lanes_and_bytes_are_reported():
    cfg, params = _model()
    loop = _loop()
    # three linear layers: a lane's state 8 x 64 float32, its conv tail
    # 3 x 128 float32
    assert loop._state_lane_bytes == 3 * (8 * 64 * 4 + 3 * 128 * 4)
    obs.tracer.enabled = True
    obs.tracer.clear()
    try:
        loop.run(_requests(4, 5))
        drains = [e for e in obs.tracer.events()
                  if e["name"] == "serve/segment_drain"]
    finally:
        obs.tracer.enabled = False
    assert drains
    assert all(0 <= d["args"]["state_lanes"] <= 3 for d in drains)
    assert max(d["args"]["state_lanes"] for d in drains) >= 2
    assert all(d["args"]["state_bytes"] == d["args"]["state_lanes"]
               * loop._state_lane_bytes for d in drains)
    assert obs.gauge("serve/state_bytes").value() % loop._state_lane_bytes == 0


def test_no_prefix_cache_and_no_host_tier():
    loop = _loop()
    assert loop._prefix_cache is None and loop._tier is None
    assert loop._state_layers == [0, 1, 2]


def test_a_host_tier_budget_makes_no_tier(monkeypatch):
    monkeypatch.setenv("TPUDIST_KV_HOST_TIER_BYTES", str(1 << 20))
    loop = _loop(prefix_sharing=True)
    assert loop._prefix_cache is None and loop._tier is None


@pytest.mark.parametrize("kw,match", [
    (dict(role="prefill"), "carry a lane's blocks and no state"),
    (dict(role="decode"), "carry a lane's blocks and no state"),
    (dict(preempt="migrate"), "carry a lane's blocks and no state"),
    (dict(chunked_prefill=False), "admitted chunk by chunk"),
])
def test_what_state_cannot_do_yet_is_refused_in_words(kw, match):
    with pytest.raises(ValueError, match=match):
        _loop(**kw)
