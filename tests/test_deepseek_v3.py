"""The DeepSeek-V3 block through the program against the plain reference
(``benchmarks/reference/deepseek_v3.py``), on the CPU at a small size
with every ratio of the published model kept: 4 heads, latents 96 / 64,
nope 32, rope 16, v 32, 16 experts in 4 groups of which 2 are eligible, 4 a
token, 4 held (experts 8-11: the seeded bias favours their group), a
shared expert, 1 dense + 2 expert layers, seeded weights.

Float32 compute: the program and the reference then agree to rounding, and
the tolerances below are set from that (each states its reason).  The
control rounds the program's weights to float8 e4m3 and must FAIL the same
comparison.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist.models import (MLAConfig, MoEConfig, MoEMLP, Request, ServeLoop,
                            TransformerConfig, TransformerLM, YarnScaling)
from tpudist.models import moe as moe_lib
from tpudist.models.generate import _blank_cache
from benchmarks.reference import deepseek_v3 as ref
from tpudist.models.transformer import LatentSelfAttention

VOCAB, EMBED, SEQ = 128, 256, 128
DIMS = ref.Dims(
    vocab=VOCAB, layers=3, first_k_dense=1, embed=EMBED, heads=4, q_lora=96,
    kv_lora=64, nope=32, rope=16, v_head=32, dense_ff=512, expert_ff=64,
    experts=16, top_k=4, n_group=4, topk_group=2, routed_scale=2.5,
    n_shared=1, held=(8, 4), yarn_original=32)
# logits are O(1) at these widths; float32 against float32-HIGHEST differs
# in the 6th digit (measured 6e-6 on the full forward)
LOGIT_TOL = 2e-4


def _moe(held=DIMS.held, n_shared=1) -> MoEConfig:
    return MoEConfig(
        num_experts=DIMS.experts, top_k=DIMS.top_k, experts="gated_silu",
        d_ff=DIMS.expert_ff, scoring="sigmoid", n_group=DIMS.n_group,
        topk_group=DIMS.topk_group, routed_scale=DIMS.routed_scale,
        correction_bias=True, n_shared=n_shared, held=held)


def _cfg(vocab=VOCAB, moe=None) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=vocab, num_layers=DIMS.layers, num_heads=DIMS.heads,
        embed_dim=EMBED, max_seq_len=SEQ, compute_dtype=jnp.float32,
        norm="rmsnorm", positions="rotary",
        rope_scaling=YarnScaling(40.0, DIMS.yarn_original, 32.0, 1.0, 1.0,
                                 1.0),
        mlp="gated_silu", mlp_dim=DIMS.dense_ff,
        mla=MLAConfig(DIMS.q_lora, DIMS.kv_lora, DIMS.nope, DIMS.rope,
                      DIMS.v_head),
        moe=moe or _moe(), first_k_dense=DIMS.first_k_dense)


@functools.cache
def _params(vocab=VOCAB):
    """Seeded weights, and a correction bias large enough to move choices
    (std 0.3 on scores in (0, 1))."""
    params = TransformerLM(_cfg(vocab)).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    params = jax.tree.map(lambda a: a, params)
    for i in (1, 2):
        params[f"block{i}"]["moe"]["router_bias"] = 0.3 * jax.random.normal(
            jax.random.key(10 + i), (DIMS.experts,))
    return params


def _fp8(tree):
    """Every matrix rounded to float8 e4m3 under a per-column absmax scale:
    the control precision of the benchmark's reference."""
    return jax.tree.map(
        lambda w: ref._fake_fp8(w, 0) if w.ndim >= 2 else w, tree)


# -- (a) the served path against the reference's full forward ---------------

@pytest.mark.parametrize("chunk", [16, 40], ids=["offsets_0_16_32", "one"])
def test_chunked_prefill_logits_match_reference(chunk):
    """Prefill through the batch-1 latent cache, a chunk at offset 0 and
    chunks at offsets > 0 (expanded attention over the cached prefix):
    every position's logits against the reference's full forward."""
    toks = jax.random.randint(jax.random.key(1), (1, 40), 0, VOCAB)
    model = TransformerLM(_cfg(), decode=True)
    cache, got = _blank_cache(model, 1), []
    for lo in range(0, 40, chunk):
        cache, logits = _advance(model, cache, toks[:, lo: lo + chunk], lo)
        got.append(logits[0])
    want = ref.Forward(DIMS).logits(_params(), toks[0])
    np.testing.assert_allclose(np.concatenate(got), want, atol=LOGIT_TOL,
                               rtol=0)


def _advance(model, cache, piece, lo):
    logits, mut = model.apply(
        {"params": _params(), "cache": cache}, piece,
        positions=jnp.arange(lo, lo + piece.shape[1])[None, :],
        mutable=["cache"])
    return mut["cache"], logits


def _serve(params, decode_attention):
    """Five requests through three lanes: lanes are admitted and released
    in mid-run, prompts take one to three chunks, segments of four steps
    stage their tokens in the side buffer."""
    loop = ServeLoop(_cfg(), params, num_slots=3, cache_layout="paged",
                     kv_block_size=16, kv_num_blocks=32, prefill_chunk=16,
                     steps_per_sync=4, decode_attention=decode_attention)
    rng = np.random.default_rng(0)
    reqs = [Request(rng.integers(0, VOCAB, n).astype(np.int32), m,
                    rid=f"r{i}")
            for i, (n, m) in enumerate(
                [(37, 9), (5, 12), (20, 6), (33, 7), (17, 10)])]
    done = loop.run(reqs)
    assert loop.pool.used_blocks == 0
    return done


def _worst_gap(done):
    """How far a served token's reference logit lies below the reference's
    best, at its widest over every served position (teacher-forced)."""
    fw = ref.Forward(DIMS)
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
    """ServeLoop end to end: chunked prefill, the paged latent cache, the
    absorbed decode (``flash`` = ``paged_mla_decode`` and ``flash_fwd``
    under interpret), expert layers with held experts.  In float32 the
    served token is the reference's arg-max at every position, to a gap
    no wider than rounding."""
    assert _worst_gap(_serve(_params(), decode_attention)) <= LOGIT_TOL


def test_serve_loop_with_fp8_weights_fails_the_same_tolerance():
    """The control: the same comparison with the program's weights rounded
    to float8 must fail, or the tolerance proves nothing."""
    assert _worst_gap(_serve(_fp8(_params()), "dense")) > 50 * LOGIT_TOL


def test_segment_counts_expert_tokens():
    """The drained segments' ``expert_tokens``: every step gives each of
    the 2 expert layers ``lanes x top_k`` assignments, of which the share
    on the held experts is counted."""
    from tpudist import obs

    before = [obs.counter(f"serve/{n}").value() for n in
              ("expert_tokens", "expert_tokens_max", "expert_slots",
               "lane_steps")]
    _serve(_params(), "dense")
    tokens, busiest, slots, lane_steps = (
        obs.counter(f"serve/{n}").value() - b for n, b in zip(
            ("expert_tokens", "expert_tokens_max", "expert_slots",
             "lane_steps"), before))
    assert slots == lane_steps // 3 * 2 * DIMS.held[1]
    assert 0 < busiest <= tokens <= lane_steps * DIMS.top_k * 2
    spans = [e["args"] for e in obs.tracer.events()
             if e["name"] == "serve/segment_drain"]
    assert all("expert_tokens" in a and "expert_tokens_max" in a
               for a in spans[-3:])


# -- (b) absorbed decode equals expanded attention on the same rows ---------

@pytest.mark.parametrize("decode_attention", ["dense", "flash"])
@pytest.mark.parametrize("length", [1, 15, 16, 17, 40])
def test_absorbed_equals_expanded(length, decode_attention):
    cfg = dataclasses.replace(_cfg(), num_layers=1, moe=None)
    x = jax.random.normal(jax.random.key(3), (1, length + 1, EMBED))
    plain = LatentSelfAttention(cfg)
    variables = plain.init(jax.random.key(4), x)
    want = plain.apply(variables, x)[:, length]
    # the first `length` rows through the dense prefill cache ...
    cached = LatentSelfAttention(cfg, decode=True)
    blank = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(cached.init, jax.random.key(0), x[:, :1])["cache"])
    _, mut = cached.apply({**variables, "cache": blank}, x[:, :length],
                          mutable=["cache"])
    rows = mut["cache"]["cached_latent"][0]                 # [S, W]
    # ... re-blocked into a pool behind a scrambled page table
    bs, nb = 16, SEQ // 16
    perm = np.random.default_rng(0).permutation(nb)
    pool = jnp.zeros((nb, bs, rows.shape[1])).at[perm].set(
        rows.reshape(nb, bs, -1))
    paged = LatentSelfAttention(
        cfg, decode=True, decode_attention=decode_attention,
        serve_side_slots=4, cache_layout="paged", kv_num_blocks=nb,
        kv_block_size=bs)
    cache = {"paged_latent": pool,
             "page_table": jnp.asarray(perm, jnp.int32)[None, :],
             "cache_index": jnp.asarray([length], jnp.int32),
             "side_latent": jnp.zeros((1, 4, rows.shape[1])),
             "side_index": jnp.zeros((), jnp.int32)}
    got, _ = paged.apply({**variables, "cache": cache}, x[:, length:],
                         positions=jnp.asarray([[length]]),
                         mutable=["cache"])
    np.testing.assert_allclose(got[:, 0], want, atol=2e-5, rtol=0)


# -- (c) the router against a literal implementation ------------------------

def _route_both(seed, bias_std):
    ks = jax.random.split(jax.random.key(seed), 3)
    x = jax.random.normal(ks[0], (64, EMBED))
    router = jax.random.normal(ks[1], (EMBED, DIMS.experts)) / 16
    bias = bias_std * jax.random.normal(ks[2], (DIMS.experts,))
    logits = jnp.matmul(x, router, precision="highest")
    return (moe_lib.route(logits, bias, _moe()),
            ref.route(x, router, bias, DIMS), jax.nn.sigmoid(logits), bias)


@pytest.mark.parametrize("bias_std", [0.0, 0.3], ids=["nobias", "bias"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_router_matches_literal_sort(seed, bias_std):
    (w, idx), (w_ref, idx_ref), _, _ = _route_both(seed, bias_std)
    order, order_ref = np.argsort(idx, -1), np.argsort(idx_ref, -1)
    np.testing.assert_array_equal(np.take_along_axis(idx, order, -1),
                                  np.take_along_axis(idx_ref, order_ref, -1))
    np.testing.assert_allclose(np.take_along_axis(w, order, -1),
                               np.take_along_axis(w_ref, order_ref, -1),
                               rtol=1e-6)


def test_router_bias_moves_the_choice_and_not_the_weight():
    (w, idx), _, scores, bias = _route_both(5, 0.3)
    (_, idx_nobias), _, _, _ = _route_both(5, 0.0)
    assert (np.sort(idx, -1) != np.sort(idx_nobias, -1)).any()
    picked = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
    np.testing.assert_allclose(
        w, picked / picked.sum(-1, keepdims=True) * DIMS.routed_scale,
        rtol=1e-6)


def test_router_keeps_to_the_eligible_groups_and_renormalises():
    (w, idx), _, scores, bias = _route_both(6, 0.3)
    per_group = DIMS.experts // DIMS.n_group
    groups = np.asarray(idx) // per_group
    assert all(len(set(g)) <= DIMS.topk_group for g in groups)
    # the eligible groups are those with the best two-expert sums
    choice = np.asarray(scores + bias).reshape(64, DIMS.n_group, per_group)
    best = np.argsort(-np.sort(choice, -1)[..., -2:].sum(-1), -1)
    for g, b in zip(groups, best[:, : DIMS.topk_group]):
        assert set(g) <= set(b)
    np.testing.assert_allclose(np.asarray(w).sum(-1), DIMS.routed_scale,
                               rtol=1e-6)


# -- (d) the shares add up ---------------------------------------------------

def test_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips hold 4 of 16 experts each: the routed parts that the four
    shares give, plus the shared expert counted once, are the uncut
    reference layer."""
    ks = jax.random.split(jax.random.key(8), 8)
    e, d, f = DIMS.experts, EMBED, DIMS.expert_ff
    full = {
        "router": {"kernel": jax.random.normal(ks[0], (d, e)) / 16},
        "router_bias": 0.3 * jax.random.normal(ks[1], (e,)),
        "w_gate": jax.random.normal(ks[2], (e, d, f)) / 16,
        "w_up": jax.random.normal(ks[3], (e, d, f)) / 16,
        "w_down": jax.random.normal(ks[4], (e, f, d)) / 8,
        "shared": {n: {"kernel": jax.random.normal(k, s) / 16}
                   for n, k, s in (("gate", ks[5], (d, f)),
                                   ("up", ks[6], (d, f)),
                                   ("down", ks[7], (f, d)))},
    }
    x = jax.random.normal(jax.random.key(9), (48, d))
    want, _ = ref.moe(x, full, dataclasses.replace(DIMS, held=(0, e)))
    total = moe_lib.GatedMLP(d, f).apply({"params": full["shared"]}, x)
    counts = []
    for first in range(0, e, 4):
        share = {k: (v[first: first + 4] if k.startswith("w_") else v)
                 for k, v in full.items() if k != "shared"}
        layer = MoEMLP(d_model=d, d_ff=f, moe=_moe((first, 4), n_shared=0))
        (routed, _), stats = layer.apply({"params": share}, x,
                                         mutable=["stats"])
        total = total + routed
        counts.append(stats["stats"]["expert_tokens"])
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=0)
    # every one of a token's choices lands on exactly one share
    assert int(sum(c.sum() for c in counts)) == 48 * DIMS.top_k


# -- (e) the vocabulary slice -------------------------------------------------

def test_vocabulary_slice_gives_the_uncut_heads_rows():
    big = 8 * VOCAB
    full = _params(big)
    cut = jax.tree.map(lambda a: a, full)
    cut["tok_embed"] = {"embedding": full["tok_embed"]["embedding"][:VOCAB]}
    cut["lm_head"] = {"kernel": full["lm_head"]["kernel"][:, :VOCAB]}
    toks = jax.random.randint(jax.random.key(2), (1, 24), 0, VOCAB)
    got = TransformerLM(_cfg()).apply({"params": cut}, toks)
    want = TransformerLM(_cfg(big)).apply({"params": full}, toks)
    # a narrower head is another matmul shape: equal to float32 rounding
    np.testing.assert_allclose(got, want[..., :VOCAB], atol=1e-5, rtol=0)
    np.testing.assert_allclose(
        got[0], ref.Forward(DIMS).logits(cut, toks[0]), atol=LOGIT_TOL,
        rtol=0)


# -- the block's other vocabulary ---------------------------------------------

def test_prefill_rebuilds_only_the_rows_that_cover_the_chunk(monkeypatch):
    """The expanded prefill picks, at run time, the first bucket of cache
    rows that covers the chunk's end; with the bucket base at 32 a 128-row
    cache has three, and every offset gives the one-shot result."""
    from tpudist.models import transformer

    monkeypatch.setattr(transformer, "_MLA_PREFILL_ROWS", 32)
    toks = jax.random.randint(jax.random.key(7), (1, 96), 0, VOCAB)
    model = TransformerLM(_cfg(), decode=True)
    cache, got = _blank_cache(model, 1), []
    for lo in range(0, 96, 16):
        cache, logits = _advance(model, cache, toks[:, lo: lo + 16], lo)
        got.append(logits[0])
    want = TransformerLM(_cfg()).apply({"params": _params()}, toks)[0]
    np.testing.assert_allclose(np.concatenate(got), want, atol=LOGIT_TOL,
                               rtol=0)


def test_rotary_positions_on_plain_attention():
    """``positions="rotary"`` without latent attention: the cached decode
    (keys stored rotated, a query at its own position) equals the full
    forward."""
    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=4,
                            num_kv_heads=2, embed_dim=64, max_seq_len=32,
                            positions="rotary", norm="rmsnorm",
                            mlp="gated_silu", mlp_dim=96)
    toks = jax.random.randint(jax.random.key(0), (2, 12), 0, 64)
    params = TransformerLM(cfg).init(jax.random.key(1), toks)["params"]
    assert "pos_embed" not in params and "gate" in params["block0"]["mlp"]
    want = TransformerLM(cfg).apply({"params": params}, toks)
    model = TransformerLM(cfg, decode=True)
    cache = _blank_cache(model, 2)
    got = []
    for t in range(12):
        logits, mut = model.apply(
            {"params": params, "cache": cache}, toks[:, t: t + 1],
            positions=jnp.full((2, 1), t), mutable=["cache"])
        cache = mut["cache"]
        got.append(logits[:, 0])
    np.testing.assert_allclose(jnp.stack(got, 1), want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("leaving", [dict(preempt="migrate"),
                                     dict(role="prefill"),
                                     dict(role="decode")],
                         ids=["migrate", "role_prefill", "role_decode"])
def test_a_latent_cache_refuses_the_kv_pair_payloads(leaving):
    with pytest.raises(ValueError, match="key/value pair"):
        ServeLoop(_cfg(), _params(), num_slots=2, cache_layout="paged",
                  kv_block_size=16, **leaving)


def test_a_latent_cache_shares_prefixes_and_has_no_host_tier(monkeypatch):
    """The tier's spill writes a K/V pair a layer: a latent cache keeps its
    prefix cache and, whatever the budget says, no tier."""
    monkeypatch.setenv("TPUDIST_KV_HOST_TIER_BYTES", str(1 << 20))
    loop = ServeLoop(_cfg(), _params(), num_slots=2, cache_layout="paged",
                     kv_block_size=16, prefix_sharing=True)
    assert loop._prefix_cache is not None and loop._tier is None
    assert loop._prefix_cache.spill_hook is None
