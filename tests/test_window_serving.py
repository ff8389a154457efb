"""A model with sliding-window AND full-attention layers through the
program against the plain reference (``benchmarks/reference/mellum2.py``),
on the CPU at a small size with the published model's shape kept: grouped
queries (4 heads on 2 K/V heads of a STATED width, 4 x 16 on a 48-wide
model), three sliding layers then one full, a window of 24, YaRN on the
full layer alone, a softmax router over 8 experts of which 4 are held, 2 a
token, every layer an expert layer, seeded weights.

Float32 compute: the program and the reference then agree to rounding, and
the tolerances below are set from that (each states its reason).  Two
controls must FAIL the same comparison: the program's weights rounded to
float8, and the reference with the band LEFT OUT of the sliding layers.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import mellum2 as ref
from tpudist import obs
from tpudist.models import (MoEConfig, MoEMLP, Request, ServeLoop,
                            TransformerConfig, TransformerLM, YarnScaling)
from tpudist.models.generate import _blank_cache
from tpudist.models.speculative import _set_cache_index

VOCAB, EMBED, SEQ, WINDOW = 97, 48, 128, 24
DIMS = ref.Dims(
    vocab=VOCAB, layers=4, embed=EMBED, heads=4, kv_heads=2, head_dim=16,
    expert_ff=32, experts=8, top_k=2, held=(0, 4),
    sliding=(True, True, True, False), window=WINDOW, rope_theta=10000.0,
    yarn_factor=4.0, yarn_original=32,
    yarn_attention_factor=0.1 * float(np.log(4.0)) + 1.0)
# logits are O(1) at these widths; float32 against float32-HIGHEST differs
# in the 6th digit
LOGIT_TOL = 2e-4


def _moe(held=DIMS.held, experts=DIMS.experts) -> MoEConfig:
    return MoEConfig(num_experts=experts, top_k=DIMS.top_k,
                     experts="gated_silu", d_ff=DIMS.expert_ff,
                     scoring="softmax", held=held)


def _cfg(**over) -> TransformerConfig:
    base = dict(
        vocab_size=VOCAB, num_layers=DIMS.layers, num_heads=DIMS.heads,
        num_kv_heads=DIMS.kv_heads, head_size=DIMS.head_dim,
        embed_dim=EMBED, max_seq_len=SEQ, compute_dtype=jnp.float32,
        norm="rmsnorm", positions="rotary", rope_theta=DIMS.rope_theta,
        rope_scaling=YarnScaling(DIMS.yarn_factor, DIMS.yarn_original, 32.0,
                                 1.0, 1.0, 0.0),
        window_rope_scaling=None,
        layer_windows=tuple(WINDOW if s else None for s in DIMS.sliding),
        mlp="gated_silu", mlp_dim=DIMS.expert_ff, moe=_moe())
    base.update(over)
    return TransformerConfig(**base)


@functools.cache
def _params():
    return TransformerLM(_cfg()).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]


def _fp8(tree):
    return jax.tree.map(
        lambda w: ref._fake_fp8(w, 0) if w.ndim >= 2 else w, tree)


# -- (a) the plain forward and the config's vocabulary -----------------------

def test_forward_matches_reference_at_a_stated_head_width():
    """The un-cached forward (a band in the sliding layers' attention, YaRN
    on the full layer only, heads of 16 on a 48-wide model)."""
    toks = jax.random.randint(jax.random.key(1), (1, 80), 0, VOCAB)
    got = TransformerLM(_cfg()).apply({"params": _params()}, toks)[0]
    want = ref.Forward(DIMS).logits(_params(), toks[0])
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    attn = _params()["block0"]["attn"]
    assert attn["q"]["kernel"].shape == (EMBED, 4 * 16)
    assert attn["kv"]["kernel"].shape == (EMBED, 2 * 2 * 16)
    assert attn["proj"]["kernel"].shape == (4 * 16, EMBED)


def test_layer_kinds_are_read_from_the_config():
    cfg = _cfg()
    assert cfg.windows == (WINDOW, WINDOW, WINDOW, None)
    assert cfg.head_dim == 16 and cfg.attn_dim == 64
    assert cfg.layer_rope_scaling(0) is None
    assert cfg.layer_rope_scaling(3) is cfg.rope_scaling
    # attention_window stays the spelling for "every layer"
    every = _cfg(layer_windows=None, attention_window=8)
    assert every.windows == (8, 8, 8, 8)
    assert every.layer_rope_scaling(0) is None
    same = _cfg(window_rope_scaling="as_full")
    assert same.layer_rope_scaling(0) is same.rope_scaling
    assert TransformerConfig().head_dim == 32       # the quotient, as ever
    with pytest.raises(ValueError, match="layer_windows"):
        _cfg(layer_windows=(WINDOW,)).windows


def test_the_reference_without_its_band_is_another_model():
    """The structure control: the sliding layers' band left out moves the
    logits far beyond the tolerance once a sequence passes the window."""
    toks = jax.random.randint(jax.random.key(1), (80,), 0, VOCAB)
    with_band = ref.Forward(DIMS).logits(_params(), toks)
    without = ref.Forward(DIMS, window=False).logits(_params(), toks)
    np.testing.assert_allclose(with_band[:WINDOW], without[:WINDOW],
                               atol=LOGIT_TOL, rtol=0)
    assert float(jnp.abs(with_band[WINDOW:] - without[WINDOW:]).max()) \
        > 50 * LOGIT_TOL


# -- (b) the window layers' prefill cache ------------------------------------

def _chunked_logits(model, toks, chunk):
    """Every position's logits through the batch-1 scalar-index cache, the
    write cursor forced to each chunk's offset as the serve loop does."""
    cache, got = _blank_cache(model, 1), []
    for lo in range(0, toks.shape[1], chunk):
        piece = toks[:, lo: lo + chunk]
        logits, mut = model.apply(
            {"params": _params(), "cache": _set_cache_index(cache, lo)},
            piece, positions=jnp.arange(lo, lo + piece.shape[1])[None, :],
            mutable=["cache"])
        cache = mut["cache"]
        got.append(logits[0])
    return cache, np.concatenate(got)


@pytest.mark.parametrize("decode_attention", ["dense", "flash"])
def test_rolling_prefill_matches_one_shot_dense_prefill(decode_attention):
    """A prompt of several chunks, longer than the window: a windowed
    layer's batch-1 cache holds ``window + chunk`` rows and not
    ``max_seq_len``, and every position's logits are the one-shot dense
    prefill's (and the reference's)."""
    chunk = 8
    toks = jax.random.randint(jax.random.key(2), (1, 72), 0, VOCAB)
    rolling = TransformerLM(_cfg(), decode=True,
                            decode_attention=decode_attention,
                            prefill_window_rows=chunk)
    cache, got = _chunked_logits(rolling, toks, chunk)
    assert cache["block0"]["attn"]["cached_key"].shape == (
        1, WINDOW + chunk, 2 * 16)
    assert cache["block3"]["attn"]["cached_key"].shape == (1, SEQ, 2 * 16)
    one_shot = TransformerLM(_cfg(), decode=True)
    _, want = _chunked_logits(one_shot, toks, toks.shape[1])
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    np.testing.assert_allclose(
        got, ref.Forward(DIMS).logits(_params(), toks[0]), atol=LOGIT_TOL,
        rtol=0)


def test_rolling_prefill_refuses_a_chunk_wider_than_its_buffer():
    model = TransformerLM(_cfg(), decode=True, prefill_window_rows=8)
    with pytest.raises(ValueError, match="prefill_window_rows"):
        model.apply({"params": _params(), "cache": _blank_cache(model, 1)},
                    jnp.zeros((1, 9), jnp.int32), mutable=["cache"])


# -- (c) ServeLoop end to end -------------------------------------------------

REQUESTS = [(40, 30), (5, 20), (70, 40), (17, 50), (24, 9), (25, 30)]


def _serve(params, decode_attention, checked=True):
    """Six requests through two lanes: prompts below, at and above the
    window, of one to nine chunks; every long lane decodes beyond the
    window and crosses block releases (blocks of 8 rows, segments of 4
    steps); lanes are released and admitted in mid-run."""
    loop = ServeLoop(_cfg(), params, num_slots=2, cache_layout="paged",
                     kv_block_size=8, prefill_chunk=8, steps_per_sync=4,
                     decode_attention=decode_attention)
    if checked:
        # the allocator's invariants, both groups, at every dispatch
        stamp = loop._stamp_table

        def stamped():
            loop.pool.check()
            stamp()
        loop._stamp_table = stamped
    rng = np.random.default_rng(0)
    reqs = [Request(rng.integers(0, VOCAB, n).astype(np.int32), m,
                    rid=f"r{i}") for i, (n, m) in enumerate(REQUESTS)]
    done = loop.run(reqs)
    loop.pool.check()
    assert loop.pool.used_blocks == 0
    assert loop.pool.window_group.used_blocks == 0
    return loop, done


def _worst_gap(done, fw=None):
    """How far a served token's reference logit lies below the reference's
    best, at its widest over every served position (teacher-forced)."""
    fw = fw or ref.Forward(DIMS)
    worst = 0.0
    for c in done:
        assert c.reason == "length"
        served = np.asarray(c.tokens)
        seq = np.concatenate([np.asarray(c.prompt), served[:-1]])
        logits = np.asarray(fw.logits(_params(), jnp.asarray(seq),
                                      len(c.prompt) - 1))
        worst = max(worst, float(
            (logits.max(-1) - logits[np.arange(len(served)), served]).max()))
    return worst


@pytest.mark.parametrize("decode_attention", ["dense", "flash"])
def test_serve_loop_matches_reference(decode_attention):
    """Chunked prefill through the rolling buffers, the finish's insert
    into BOTH groups, decode through both (``flash`` = the windowed and the
    plain paged kernel under interpret), releases inside the run.  In
    float32 the served token is the reference's arg-max at every position,
    to a gap no wider than rounding."""
    loop, done = _serve(_params(), decode_attention)
    assert len(done) == len(REQUESTS)
    assert _worst_gap(done) <= LOGIT_TOL
    # a request of 110 tokens passed through 14 blocks of the window
    # group and held 5 at a time
    group = loop.pool.window_group
    assert group.lane_blocks == 5 and group.released >= 15


def test_serve_loop_with_fp8_weights_fails_the_same_tolerance():
    """The precision control."""
    assert _worst_gap(_serve(_fp8(_params()), "dense", False)[1]) \
        > 50 * LOGIT_TOL


def test_serve_loop_against_a_reference_without_the_band_fails():
    """The structure control: the served tokens held to a reference whose
    sliding layers see the whole context."""
    _, done = _serve(_params(), "dense", False)
    assert _worst_gap(done, ref.Forward(DIMS, window=False)) \
        > 50 * LOGIT_TOL


def test_segment_spans_carry_the_window_layers_rows():
    names = ("decode_rows_window_computed", "decode_rows_window_live",
             "kv_window_blocks_released", "decode_rows_live")
    before = [obs.counter(f"serve/{n}").value() for n in names]
    loop, _ = _serve(_params(), "dense", False)
    computed, live, released, full_live = (
        obs.counter(f"serve/{n}").value() - b
        for n, b in zip(names, before))
    assert released == loop.pool.window_group.released
    # the trim: the window layers walk at most a window and two blocks a
    # lane, the full layers the whole length
    assert 0 < live <= computed < full_live
    spans = [e["args"] for e in obs.tracer.events()
             if e["name"] == "serve/segment_drain"][-5:]
    for a in spans:
        assert a["rows_window_live"] <= a["rows_window"] <= a["rows"]
        assert a["rows_window_live"] <= a["lanes"] * WINDOW
        assert a["blocks_released"] >= 0 and "expert_tokens" in a
    assert obs.gauge("serve/kv_window_blocks_used").value() == 0
    assert obs.gauge("serve/kv_window_blocks_free").value() == \
        loop.kv_window_blocks


def test_every_layer_windowed_is_served_from_the_window_group():
    """``attention_window`` (every layer) through the paged layout: no
    layer uses the full group's pool."""
    cfg = _cfg(layer_windows=None, attention_window=WINDOW,
               window_rope_scaling="as_full", rope_scaling=None)
    params = TransformerLM(cfg).init(
        jax.random.key(3), jnp.zeros((1, 8), jnp.int32))["params"]
    loop = ServeLoop(cfg, params, num_slots=2, cache_layout="paged",
                     kv_block_size=8, prefill_chunk=8, steps_per_sync=4,
                     decode_attention="dense")
    rng = np.random.default_rng(1)
    reqs = [Request(rng.integers(0, VOCAB, n).astype(np.int32), m, rid=i)
            for i, (n, m) in enumerate([(50, 30), (9, 40)])]
    done = {c.rid: c for c in loop.run(reqs)}
    fwd = jax.jit(lambda t: TransformerLM(cfg).apply({"params": params}, t))
    for r in reqs:
        seq = list(r.prompt)
        for tok in done[r.rid].tokens:
            buf = np.zeros((1, SEQ), np.int32)
            buf[0, : len(seq)] = seq
            logits = np.asarray(fwd(jnp.asarray(buf))[0, len(seq) - 1])
            assert logits.max() - logits[tok] <= LOGIT_TOL
            seq.append(int(tok))
    assert loop.pool.window_group.released > 0


# -- (d) what a cache of two block groups cannot do yet -----------------------

PAGED = dict(num_slots=2, cache_layout="paged", kv_block_size=8,
             prefill_chunk=8, steps_per_sync=4)


@pytest.mark.parametrize("options,reason", [
    (dict(role="prefill"), "role='both'"),
    (dict(role="decode"), "role='both'"),
    (dict(preempt="migrate"), "preempt='degrade'"),
    (dict(steps_per_sync=32), "fit in the window"),
], ids=["role_prefill", "role_decode", "migrate",
        "segment_longer_than_window"])
def test_a_window_group_refuses_at_construction(options, reason):
    with pytest.raises(ValueError, match=reason):
        ServeLoop(_cfg(), _params(), **{**PAGED, **options})


def test_two_window_widths_are_refused():
    cfg = _cfg(layer_windows=(WINDOW, 16, WINDOW, None))
    with pytest.raises(ValueError, match="ONE window block group"):
        ServeLoop(cfg, _params(), **PAGED)


def test_a_window_group_shares_no_prefix_and_has_no_host_tier(monkeypatch):
    monkeypatch.setenv("TPUDIST_KV_HOST_TIER_BYTES", str(1 << 20))
    loop = ServeLoop(_cfg(), _params(), prefix_sharing=True, **PAGED)
    assert loop._prefix_cache is None and loop._tier is None
    group = loop.pool.window_group
    with pytest.raises(RuntimeError, match="shares nothing"):
        loop.pool.admit(0, 20, 4, shared=[1])
    with pytest.raises(RuntimeError, match="exports nothing"):
        loop.pool.export_slot(0)
    assert group.used_blocks == 0


def test_beam_and_speculative_rollouts_keep_the_dense_path():
    """Beam search (and the speculative rollout) are rollouts of their own
    over the dense layout's scalar index, not modes of the paged loop: a
    model with layers of two kinds takes that path with each layer's own
    window, and a beam of one is the reference's greedy continuation."""
    from tpudist.models.beam import beam_search_generate

    toks = jax.random.randint(jax.random.key(4), (1, 30), 0, VOCAB)
    out = np.asarray(beam_search_generate(_cfg(), _params(), toks, 6,
                                          beam_size=1))[0, 0]
    logits = np.asarray(ref.Forward(DIMS).logits(
        _params(), jnp.asarray(out[:-1]), 29))
    assert (logits.max(-1) - logits[np.arange(6), out[30:]]).max() \
        <= LOGIT_TOL


# -- (e) the shares add up -----------------------------------------------------

def test_four_shares_of_the_experts_add_up_to_the_uncut_layer():
    """Four chips hold 16 of 64 experts each: the routed parts the four
    shares give are the uncut reference layer, and every one of a token's
    eight choices lands on exactly one share."""
    e, k, d, f = 64, 8, 48, 32
    dims = dataclasses.replace(DIMS, experts=e, top_k=k, held=(0, e))
    ks = jax.random.split(jax.random.key(8), 4)
    full = {"router": {"kernel": jax.random.normal(ks[0], (d, e)) / 4},
            "w_gate": jax.random.normal(ks[1], (e, d, f)) / 7,
            "w_up": jax.random.normal(ks[2], (e, d, f)) / 7,
            "w_down": jax.random.normal(ks[3], (e, f, d)) / 6}
    x = jax.random.normal(jax.random.key(9), (40, d))
    want, _ = ref.moe(x, full, dims)
    total, counts = 0.0, []
    for first in range(0, e, 16):
        share = {n: (v[first: first + 16] if n.startswith("w_") else v)
                 for n, v in full.items()}
        moe = MoEConfig(num_experts=e, top_k=k, experts="gated_silu",
                        d_ff=f, scoring="softmax", held=(first, 16))
        (routed, _), stats = MoEMLP(d_model=d, d_ff=f, moe=moe).apply(
            {"params": share}, x, mutable=["stats"])
        total = total + routed
        counts.append(stats["stats"]["expert_tokens"])
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=0)
    assert int(sum(c.sum() for c in counts)) == 40 * k


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_softmax_router_matches_literal_sort(seed):
    """``route(scoring="softmax")``: a softmax over ALL experts, the eight
    largest, renormalised to sum 1, no scale."""
    from tpudist.models.moe import route

    logits = 2.0 * jax.random.normal(jax.random.key(seed), (33, 64))
    moe = MoEConfig(num_experts=64, top_k=8, experts="gated_silu",
                    scoring="softmax")
    w, experts = route(logits, None, moe)
    probs = jax.nn.softmax(logits, -1)
    want = jnp.argsort(-probs, axis=-1)[:, :8]
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(want, -1))
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-6)
    np.testing.assert_allclose(
        w, jnp.take_along_axis(probs, experts, 1)
        / jnp.take_along_axis(probs, experts, 1).sum(-1, keepdims=True),
        atol=1e-6)
