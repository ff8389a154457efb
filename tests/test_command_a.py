"""A model of the Command A+ family through the program against the plain
reference (``benchmarks/reference/command_a.py``), on the CPU at a small
size with the published model's shape kept: the PARALLEL block under one
scale-only LayerNorm, grouped queries (8 heads on 2 K/V heads of a stated
width), three rotary sliding layers (a window of 24) then one POSITION-FREE
full layer, a sigmoid router over 8 experts of which 4 are held, 2 a token,
two shared experts AVERAGED, a head TIED to the embedding times a
``logit_scale`` that is not 1, seeded weights.

Float32 compute: the program and the reference then agree to rounding, and
the tolerances below are set from that.  Each new value of the block's
vocabulary is also taken away alone: the program without it is the
reference's control and no longer the reference.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import command_a as ref
from tpudist import obs
from tpudist.models import (MoEConfig, MoEMLP, Request, ServeLoop,
                            TransformerConfig, TransformerLM)
from tpudist.models.transformer import DecoderBlock, make_norm

VOCAB, EMBED, SEQ, WINDOW = 97, 48, 128, 24
DIMS = ref.Dims(
    vocab=VOCAB, layers=4, embed=EMBED, heads=8, kv_heads=2, head_dim=16,
    expert_ff=32, experts=8, top_k=2, held=(0, 4), n_shared=2,
    sliding=(True, True, True, False), window=WINDOW, norm_eps=1e-5,
    rope_theta=10000.0, logit_scale=0.5)
# logits are O(1) at these widths; float32 against float32-HIGHEST differs
# in the 6th digit
LOGIT_TOL = 2e-4


def _moe(**over) -> MoEConfig:
    base = dict(num_experts=DIMS.experts, top_k=DIMS.top_k,
                experts="gated_silu", d_ff=DIMS.expert_ff, scoring="sigmoid",
                n_shared=DIMS.n_shared, shared_combine="mean",
                held=DIMS.held)
    base.update(over)
    return MoEConfig(**base)


def _cfg(**over) -> TransformerConfig:
    base = dict(
        vocab_size=VOCAB, num_layers=DIMS.layers, num_heads=DIMS.heads,
        num_kv_heads=DIMS.kv_heads, head_size=DIMS.head_dim,
        embed_dim=EMBED, max_seq_len=SEQ, compute_dtype=jnp.float32,
        norm="layernorm_scale", norm_eps=DIMS.norm_eps,
        norm_order="parallel", positions="rotary",
        full_layer_positions="none", rope_theta=DIMS.rope_theta,
        layer_windows=tuple(WINDOW if s else None for s in DIMS.sliding),
        mlp="gated_silu", mlp_dim=DIMS.expert_ff, moe=_moe(),
        tie_embeddings=True, logit_scale=DIMS.logit_scale)
    base.update(over)
    return TransformerConfig(**base)


@functools.cache
def _params():
    """Seeded weights whose LayerNorm scales are not all 1, so that a norm
    read twice, or a second one, shows."""
    params = TransformerLM(_cfg()).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    keys = iter(jax.random.split(jax.random.key(7), 16))

    def scales(path, leaf):
        if path[-1].key == "scale":
            return 1.0 + 0.3 * jax.random.normal(next(keys), leaf.shape)
        return leaf
    return jax.tree_util.tree_map_with_path(scales, params)


def _tokens(n=80, key=1):
    return jax.random.randint(jax.random.key(key), (n,), 0, VOCAB)


def _logits(cfg, params=None, toks=None):
    toks = _tokens() if toks is None else toks
    return TransformerLM(cfg).apply(
        {"params": _params() if params is None else params}, toks[None])[0]


# -- (a) the whole forward ------------------------------------------------------

def test_forward_matches_reference_in_the_parallel_form():
    """Past the window (80 tokens, a window of 24): the band and the
    rotation on the sliding layers, neither on the full one, one norm a
    layer, the shared mean, the tied head."""
    want = ref.Forward(DIMS).logits(_params(), _tokens())
    np.testing.assert_allclose(_logits(_cfg()), want, atol=LOGIT_TOL, rtol=0)


def test_the_tree_has_one_norm_a_layer_no_bias_and_no_head_of_its_own():
    p = _params()
    assert "lm_head" not in p and set(p["ln_f"]) == {"scale"}
    for i in range(DIMS.layers):
        assert set(p[f"block{i}"]) == {"ln1", "attn", "moe"}
        assert set(p[f"block{i}"]["ln1"]) == {"scale"}
    shared = p["block0"]["moe"]["shared"]
    assert shared["gate"]["kernel"].shape == (EMBED, 2 * DIMS.expert_ff)
    assert shared["down"]["kernel"].shape == (2 * DIMS.expert_ff, EMBED)
    assert p["block0"]["attn"]["kv"]["kernel"].shape == (EMBED, 2 * 2 * 16)


# -- (b) each new value alone -------------------------------------------------

def test_scale_only_layer_norm_against_the_reference():
    x = 3.0 + 2.0 * jax.random.normal(jax.random.key(3), (5, EMBED))
    scale = 1.0 + jax.random.normal(jax.random.key(4), (EMBED,))
    got = make_norm(_cfg(), "n").apply({"params": {"scale": scale}}, x)
    np.testing.assert_allclose(
        got, ref.layer_norm(x, scale, DIMS.norm_eps), atol=1e-5, rtol=0)
    # mean-centred: a shift of every feature changes nothing
    np.testing.assert_allclose(
        got, make_norm(_cfg(), "n").apply({"params": {"scale": scale}},
                                          x + 7.0), atol=1e-4, rtol=0)


def test_positions_are_a_layer_kinds():
    cfg = _cfg()
    assert [cfg.layer_positions(i) for i in range(4)] == [
        "rotary", "rotary", "rotary", "none"]
    assert _cfg(full_layer_positions="as_model").layer_positions(3) \
        == "rotary"
    # a model that does not say so keeps one value for every layer
    assert TransformerConfig(positions="rotary").layer_positions(None) \
        == "rotary"
    assert TransformerConfig().layer_positions(0) == "learned"
    with pytest.raises(ValueError, match="full_layer_positions"):
        _cfg(full_layer_positions="learned")
    with pytest.raises(ValueError, match="ROTARY window"):
        _cfg(positions="none")


# what the program is without ONE of the new values, and the reference's
# control that is the same model
ALONE = {
    "rope_full": dict(full_layer_positions="as_model"),
    "shared_sum": dict(moe=_moe(shared_combine="sum")),
    "sequential": dict(norm_order="pre"),
}


@pytest.mark.parametrize("control", sorted(ALONE))
def test_the_program_without_one_value_is_that_control(control):
    params = _params()
    if control == "sequential":
        # the control's second norm has the first one's scale
        params = {k: ({**v, "ln2": v["ln1"]} if k.startswith("block") else v)
                  for k, v in params.items()}
    got = _logits(_cfg(**ALONE[control]), params)
    same = ref.Forward(DIMS, ref.CONTROLS[control]).logits(
        _params(), _tokens())
    exact = ref.Forward(DIMS).logits(_params(), _tokens())
    np.testing.assert_allclose(got, same, atol=LOGIT_TOL, rtol=0)
    assert float(jnp.abs(got - exact).max()) > 50 * LOGIT_TOL


def test_tied_head_and_logit_scale():
    half = _logits(_cfg())
    np.testing.assert_allclose(half, 0.5 * _logits(_cfg(logit_scale=1.0)),
                               atol=1e-6, rtol=0)
    # an untied head is a leaf of its own, and takes the scale too
    untied = _cfg(tie_embeddings=False)
    p = TransformerLM(untied).init(jax.random.key(0),
                                   jnp.zeros((1, 8), jnp.int32))["params"]
    assert p["lm_head"]["kernel"].shape == (EMBED, VOCAB)
    np.testing.assert_allclose(
        _logits(untied, p),
        0.5 * _logits(dataclasses.replace(untied, logit_scale=1.0), p),
        atol=1e-6, rtol=0)


def test_unknown_values_are_refused_in_words():
    with pytest.raises(ValueError, match="norm_order"):
        _cfg(norm_order="both")
    with pytest.raises(ValueError, match="layernorm_scale"):
        _logits(_cfg(norm="scale"))
    with pytest.raises(ValueError, match="shared_combine"):
        _logits(_cfg(moe=_moe(shared_combine="median")))


# -- (c) ServeLoop end to end -------------------------------------------------

REQUESTS = [(40, 30), (5, 20), (70, 40), (17, 50), (24, 9), (25, 30)]


@functools.cache
def _serve(decode_attention):
    """Six requests through two lanes: prompts below, at and above the
    window, of one to nine chunks; every long lane decodes beyond the
    window and crosses block releases (blocks of 8 rows, segments of 4
    steps); lanes are released and admitted in mid-run."""
    loop = ServeLoop(_cfg(), _params(), num_slots=2, cache_layout="paged",
                     kv_block_size=8, prefill_chunk=8, steps_per_sync=4,
                     decode_attention=decode_attention)
    rng = np.random.default_rng(0)
    reqs = [Request(rng.integers(0, VOCAB, n).astype(np.int32), m,
                    rid=f"r{i}") for i, (n, m) in enumerate(REQUESTS)]
    done = loop.run(reqs)
    loop.pool.check()
    assert loop.pool.used_blocks == 0
    assert loop.pool.window_group.used_blocks == 0
    return loop, done


def _worst_gap(done, control=ref.EXACT):
    """How far a served token's reference logit lies below the reference's
    best, at its widest over every served position (teacher-forced)."""
    fw = ref.Forward(DIMS, control)
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
    """Chunked prefill through the rolling buffers (rotated keys in the
    window layers', unrotated in the full layer's), the finish's insert into
    BOTH groups, decode through both walks, releases inside the run.  In
    float32 the served token is the reference's arg-max at every position,
    to a gap no wider than rounding."""
    loop, done = _serve(decode_attention)
    assert len(done) == len(REQUESTS)
    assert _worst_gap(done) <= LOGIT_TOL
    assert loop.pool.window_group.released >= 15


@pytest.mark.parametrize("control", sorted(ref.CONTROLS))
def test_served_tokens_against_a_control_fail(control):
    """The four controls the cell's limits hold out (float8; rotary on the
    full layer; the sequential block; the shared experts summed): the
    served tokens held to each read far beyond rounding."""
    assert _worst_gap(_serve("dense")[1], ref.CONTROLS[control]) \
        > 50 * LOGIT_TOL


def test_the_loop_says_its_rows_bytes_and_heads_a_grid_row():
    loop, _ = _serve("flash")
    # K and V of a token in a layer: 2 x 2 heads x 16 x 4 bytes
    assert obs.gauge("serve/kv_row_bytes").value() == 256
    assert obs.gauge("serve/kv_heads_per_grid_row").value() \
        == loop._row_heads > 0
    spans = [e["args"] for e in obs.tracer.events()
             if e["name"] == "serve/segment_drain"][-3:]
    for a in spans:
        assert a["heads_per_grid_row"] * a["grid_rows"] == 2 * 2
        assert a["rows_window_live"] <= a["rows_window"] <= a["rows"]


@pytest.mark.parametrize("options,reason", [
    (dict(role="prefill"), "role='both'"),
    (dict(preempt="migrate"), "preempt='degrade'"),
    (dict(steps_per_sync=32), "fit in the window"),
], ids=["role_prefill", "migrate", "segment_longer_than_window"])
def test_what_the_loop_cannot_do_for_this_family_is_refused(options,
                                                            reason):
    """As for every model with window layers in a paged cache."""
    opts = dict(num_slots=2, cache_layout="paged", kv_block_size=8,
                prefill_chunk=8, steps_per_sync=4)
    with pytest.raises(ValueError, match=reason):
        ServeLoop(_cfg(), _params(), **{**opts, **options})


def test_no_prefix_is_shared_and_no_host_tier_kept(monkeypatch):
    monkeypatch.setenv("TPUDIST_KV_HOST_TIER_BYTES", str(1 << 20))
    loop = ServeLoop(_cfg(), _params(), num_slots=2, cache_layout="paged",
                     kv_block_size=8, prefill_chunk=8, steps_per_sync=4,
                     prefix_sharing=True)
    assert loop._prefix_cache is None and loop._tier is None


# -- (d) the shares add up ------------------------------------------------------

def test_eight_shares_add_up_to_the_uncut_layer():
    """Eight chips hold 2 of 16 experts each: the routed parts the eight
    shares give, with the shared experts' mean, the attention and the
    residual counted ONCE, are the uncut reference layer; and every one of
    a token's choices lands on exactly one share."""
    e, k = 16, 4
    dims = dataclasses.replace(DIMS, experts=e, top_k=k, held=(0, e))
    cfg = _cfg(moe=_moe(num_experts=e, top_k=k, held=(0, e)))
    block = DecoderBlock(cfg, expert_layer=True, layer=0)
    x = jax.random.normal(jax.random.key(9), (1, 40, EMBED))
    full = block.init(jax.random.key(8), x)["params"]
    full["ln1"]["scale"] = 1.0 + 0.3 * jax.random.normal(
        jax.random.key(5), (EMBED,))
    want, _ = ref.block(x[0], full, dims=dims, sliding=True)

    def share(first, n_shared):
        moe = _moe(num_experts=e, top_k=k, held=(first, 2),
                   n_shared=n_shared)
        p = {n: (v[first: first + 2] if n.startswith("w_") else v)
             for n, v in full["moe"].items()
             if n_shared or n != "shared"}
        return moe, p

    # share 0 as the program builds its block: x + attention + its routed
    # part + the shared mean
    moe0, p0 = share(0, DIMS.n_shared)
    total = DecoderBlock(dataclasses.replace(cfg, moe=moe0),
                         expert_layer=True, layer=0).apply(
        {"params": {**full, "moe": p0}}, x)[0]
    h = ref.layer_norm(x[0], full["ln1"]["scale"], DIMS.norm_eps)
    counts = []
    for first in range(0, e, 2):
        moe, p = share(first, 0)
        (routed, _), stats = MoEMLP(
            d_model=EMBED, d_ff=DIMS.expert_ff, moe=moe).apply(
            {"params": p}, h, mutable=["stats"])
        counts.append(stats["stats"]["expert_tokens"])
        if first:
            total = total + routed
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=0)
    assert int(sum(c.sum() for c in counts)) == 40 * k


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sigmoid_router_matches_literal_sort(seed):
    from tpudist.models.moe import route

    x = jax.random.normal(jax.random.key(seed), (64, EMBED))
    w = jax.random.normal(jax.random.key(seed + 10), (EMBED, 8)) / 5
    want_w, want_e = ref.route(x, w, DIMS)
    got_w, got_e = route(x @ w, None, _moe())
    np.testing.assert_array_equal(np.sort(got_e, -1), np.sort(want_e, -1))
    np.testing.assert_allclose(np.sort(got_w, -1), np.sort(want_w, -1),
                               atol=1e-6, rtol=0)


# -- (e) the paged walks at this family's heads ---------------------------------

@pytest.mark.parametrize("window", [None, 4096])
def test_grid_rows_for_8_kv_heads_of_128_are_the_calls_grid(window):
    """8 K/V heads of 128 in bfloat16, pages of 128 rows, 8 a tile: a
    lane's tile slots are 8 MiB, the budget 4: TWO grid rows of four heads
    a lane, in the full walk and the window walk alike; the call's
    ``grid=`` is the helper's number."""
    from tests.test_paged_decode_walk import _pallas_grids
    from tpudist.ops.flash_decode import paged_flash_decode, paged_grid_rows

    lanes, h, h_kv, d, block, m = 4, 128, 8, 128, 128, 144
    rows = paged_grid_rows(lanes, h_kv, d, block, m)
    assert rows == 2 * lanes
    sds = jax.ShapeDtypeStruct
    pool = sds((lanes * m, block, h_kv * d), jnp.bfloat16)
    side = sds((lanes, 16, h_kv * d), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(
        lambda q, k, v, table, n, sk, sv: paged_flash_decode(
            q, k, v, table, n, packed_kv_heads=h_kv, side_k=sk, side_v=sv,
            side_len=1, window=window, interpret=True))(
        sds((lanes, 1, h, d), jnp.bfloat16), pool, pool,
        sds((lanes, m), jnp.int32), sds((lanes,), jnp.int32), side, side)
    assert _pallas_grids(jaxpr.jaxpr) == [(rows,)]
