"""The Keye-VL 2.0 language block (grouped-query attention with a per-head
q/k norm and a learned indexer, an expert FFN in every layer) through the
program against the plain reference (``benchmarks/reference/keye_vl2.py``),
on the CPU at a small size with the published model's shape kept: 4 query
heads on 2 K/V heads of a stated width (4 x 16 on a 48-wide model), an
indexer of 8 heads of 16 with ONE index key a token that keeps 16 rows, far
under the contexts used (up to 72), so that the selection bites everywhere;
a softmax router over 8 experts of which 4 are held, 2 a token, seeded
weights.

Float32 compute: the program and the reference then agree to rounding, and
choose the same rows except where two index scores lie closer than
rounding, which the seeds below do not hit.
"""

import dataclasses
import functools
import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import keye_vl2 as ref
from tpudist import obs
from tpudist.models import (MoEConfig, MoEMLP, Request, ServeLoop,
                            TransformerConfig, TransformerLM)
from tpudist.models.generate import _blank_cache
from tpudist.models.serving import _index_leaves, _kv_leaves
from tpudist.ops.flash_attention import flash_chosen_rows
from tpudist.ops.flash_decode import (index_queries_per_row, index_select,
                                      index_select_mask, kv_row, pack_kv,
                                      paged_flash_decode, paged_grid_rows,
                                      paged_index_scores, sparse_gqa_attend,
                                      unpack_kv)

VOCAB, EMBED, SEQ, TOPK = 97, 48, 128, 16
DIMS = ref.Dims(
    vocab=VOCAB, layers=3, embed=EMBED, heads=4, kv_heads=2, head_dim=16,
    expert_ff=32, experts=8, top_k=2, held=(0, 4), index_heads=8,
    index_dim=16, index_topk=TOPK, rope_theta=10000.0)
# logits are O(1) at these widths; float32 against float32-HIGHEST differs
# in the 6th digit
LOGIT_TOL = 2e-4


def _moe(held=DIMS.held, experts=DIMS.experts) -> MoEConfig:
    return MoEConfig(num_experts=experts, top_k=DIMS.top_k,
                     experts="gated_silu", d_ff=DIMS.expert_ff,
                     scoring="softmax", held=held)


def _cfg(topk=TOPK, **over) -> TransformerConfig:
    base = dict(
        vocab_size=VOCAB, num_layers=DIMS.layers, num_heads=DIMS.heads,
        num_kv_heads=DIMS.kv_heads, head_size=DIMS.head_dim,
        embed_dim=EMBED, max_seq_len=SEQ, compute_dtype=jnp.float32,
        norm="rmsnorm", positions="rotary", rope_theta=DIMS.rope_theta,
        mlp="gated_silu", mlp_dim=DIMS.expert_ff, moe=_moe(), qk_norm=True,
        index_heads=DIMS.index_heads, index_head_dim=DIMS.index_dim,
        index_topk=topk)
    base.update(over)
    return TransformerConfig(**base)


def _plain_cfg() -> TransformerConfig:
    """The same model without an indexer."""
    return _cfg(topk=None, index_heads=None, index_head_dim=None)


@functools.cache
def _params():
    """Seeded leaves; the norms' scales and the index key norm's bias are
    drawn too, so that each is exercised."""
    tree = TransformerLM(_cfg()).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    keys = iter(jax.random.split(jax.random.key(6), 64))

    def draw(path, leaf):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        if "q_norm" in name or "k_norm/scale" in name:
            return 1.0 + 0.2 * jax.random.normal(next(keys), leaf.shape)
        if "idx_k_norm/bias" in name:
            return 0.1 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    return jax.tree_util.tree_map_with_path(draw, tree)


def _plain_params():
    return {
        name: ({**node, "attn": {k: v for k, v in node["attn"].items()
                                 if not k.startswith("idx_")}}
               if name.startswith("block") else node)
        for name, node in _params().items()}


# -- (a) the program against the reference ------------------------------------

def test_one_shot_forward_matches_reference():
    toks = jax.random.randint(jax.random.key(1), (1, 72), 0, VOCAB)
    got = TransformerLM(_cfg()).apply({"params": _params()}, toks)[0]
    want = ref.Forward(DIMS).logits(_params(), toks[0])
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    attn = _params()["block0"]["attn"]
    assert attn["idx_q"]["kernel"].shape == (EMBED, 8 * 16)
    assert attn["idx_k"]["kernel"].shape == (EMBED, 16)
    assert attn["idx_w"]["kernel"].shape == (EMBED, 8)
    assert attn["q_norm"]["scale"].shape == (16,)
    assert set(attn["idx_k_norm"]) == {"scale", "bias"}


def test_the_selection_bites():
    """The same sequence with the selection skipped, and with the wrong
    rows, gives other logits: the comparison above is of the choice."""
    toks = jax.random.randint(jax.random.key(1), (72,), 0, VOCAB)
    want = np.asarray(ref.Forward(DIMS).logits(_params(), toks))
    for select in ("all", "recent"):
        other = np.asarray(ref.Forward(DIMS, select=select).logits(
            _params(), toks))
        np.testing.assert_allclose(other[:TOPK], want[:TOPK],
                                   atol=LOGIT_TOL, rtol=0)
        assert np.abs(other[TOPK:] - want[TOPK:]).max() > 100 * LOGIT_TOL


def test_the_q_k_norm_is_in_the_forward():
    """Without the per-head norm on q and k the logits are another
    model's."""
    toks = jax.random.randint(jax.random.key(1), (1, 24), 0, VOCAB)
    with_norm = TransformerLM(_cfg()).apply({"params": _params()}, toks)
    bare = {
        name: ({**node, "attn": {k: v for k, v in node["attn"].items()
                                 if k not in ("q_norm", "k_norm")}}
               if name.startswith("block") else node)
        for name, node in _params().items()}
    without = TransformerLM(_cfg(qk_norm=False)).apply({"params": bare},
                                                       toks)
    assert float(jnp.abs(with_norm - without).max()) > 100 * LOGIT_TOL


@pytest.mark.parametrize("decode_attention", ["dense", "flash"])
@pytest.mark.parametrize("chunk", [16, 24], ids=["chunk16", "chunk24"])
def test_chunked_prefill_logits_match_reference(chunk, decode_attention):
    """Prefill through the batch-1 cache: a chunk that ends at or under
    ``index_topk`` is the dense program, every later one scores, selects
    and attends the chosen rows (``flash``: the kernels under interpret;
    ``dense``: the mask)."""
    toks = jax.random.randint(jax.random.key(2), (1, 72), 0, VOCAB)
    model = TransformerLM(_cfg(), decode=True,
                          decode_attention=decode_attention)
    cache, got = _blank_cache(model, 1), []
    for lo in range(0, 72, chunk):
        piece = toks[:, lo: lo + chunk]
        logits, mut = model.apply(
            {"params": _params(), "cache": cache}, piece,
            positions=jnp.arange(lo, lo + piece.shape[1])[None, :],
            mutable=["cache"])
        cache = mut["cache"]
        got.append(logits[0])
    want = ref.Forward(DIMS).logits(_params(), toks[0])
    np.testing.assert_allclose(np.concatenate(got), want, atol=LOGIT_TOL,
                               rtol=0)


def test_scalar_index_rollout_step_matches_reference():
    """A batch-1 rollout's one-token steps after a prefill (the dense
    scalar-index cache): the chosen rows by a mask."""
    toks = jax.random.randint(jax.random.key(4), (1, 40), 0, VOCAB)
    model = TransformerLM(_cfg(), decode=True)
    cache, got = _blank_cache(model, 1), []
    for lo, hi in [(0, 32)] + [(i, i + 1) for i in range(32, 40)]:
        logits, mut = model.apply(
            {"params": _params(), "cache": cache}, toks[:, lo:hi],
            positions=jnp.arange(lo, hi)[None, :], mutable=["cache"])
        cache = mut["cache"]
        got.append(logits[0])
    want = ref.Forward(DIMS).logits(_params(), toks[0])
    np.testing.assert_allclose(np.concatenate(got), want, atol=LOGIT_TOL,
                               rtol=0)


REQUESTS = [(61, 9), (5, 14), (20, 6), (33, 11), (17, 10)]


def _loop(cfg, params, decode_attention):
    return ServeLoop(cfg, params, num_slots=3, cache_layout="paged",
                     kv_block_size=16, kv_num_blocks=32, prefill_chunk=16,
                     steps_per_sync=4, decode_attention=decode_attention)


def _serve(cfg, params, decode_attention, watch=None):
    loop = _loop(cfg, params, decode_attention)
    rng = np.random.default_rng(0)
    reqs = [Request(rng.integers(0, VOCAB, n).astype(np.int32), m,
                    rid=f"r{i}") for i, (n, m) in enumerate(REQUESTS)]
    done = []

    def source():
        if watch is not None:
            watch(loop)
        out, reqs[:] = reqs[:2], reqs[2:]
        return out if out or len(done) < len(REQUESTS) else None

    loop.run(source=source, sink=done.append)
    assert loop.pool.used_blocks == 0
    loop.pool.check()
    return done


def _worst_gap(done, params, dims=DIMS):
    fw = ref.Forward(dims)
    worst = 0.0
    for c in done:
        assert c.reason == "length"
        served = np.asarray(c.tokens)
        seq = np.concatenate([np.asarray(c.prompt), served[:-1]])
        logits = np.asarray(fw.logits(params, jnp.asarray(seq),
                                      len(c.prompt) - 1))
        worst = max(worst, float(
            (logits.max(-1) - logits[np.arange(len(served)), served]).max()))
    return worst


@pytest.mark.parametrize("decode_attention", ["dense", "flash"])
def test_serve_loop_matches_reference(decode_attention):
    """ServeLoop end to end: chunked prefill with chosen rows, the paged
    cache of three leaves, and the decode step's scores -> selection ->
    attention over the chosen rows (``flash``: ``paged_index_scores`` and
    ``sparse_gqa_attend`` under interpret), lanes under and over
    ``index_topk`` in one step.  The served token is the reference's
    arg-max at every position."""
    done = _serve(_cfg(), _params(), decode_attention)
    assert len(done) == len(REQUESTS)
    assert _worst_gap(done, _params()) <= LOGIT_TOL


# a bfloat16 model against the float32 reference: 0.16 on both routes (the
# worst position is a prefill's), 1.35 with the selection skipped, 4.1 with
# a word's halves swapped on their way into the walk
BF16_TOL = 0.4


@functools.cache
def _served_bf16(decode_attention, topk=TOPK):
    return _serve(_cfg(topk=topk, compute_dtype=jnp.bfloat16), _params(),
                  decode_attention)


@pytest.mark.parametrize("decode_attention", ["flash", "dense"])
def test_a_bfloat16_model_serves_over_word_rows(decode_attention):
    """The same loop in bfloat16, where ``paged_kv`` / ``side_kv`` are rows
    of ``uint32`` words: the finish packs the prompt's K and V, the step
    its token's, the merge moves words, and the rows come back out in the
    walk (``flash``) or through ``unpack_kv`` (``dense``).  The two routes
    round differently from a prompt's first chunk on, so neither serves
    the other's tokens to the last; each serves the reference's within
    bfloat16's rounding, far under what wrong rows or swapped halves
    read."""
    done = _served_bf16(decode_attention)
    assert len(done) == len(REQUESTS)
    assert _worst_gap(done, _params()) <= BF16_TOL
    # the routes agree on the requests no near-tie splits
    other = {c.rid: c.tokens for c in _served_bf16(
        "dense" if decode_attention == "flash" else "flash")}
    assert sum(np.array_equal(c.tokens, other[c.rid]) for c in done) >= 2


def test_a_bfloat16_loop_that_skips_the_selection_fails_that_tolerance():
    done = _served_bf16("dense", SEQ + 8)
    assert _worst_gap(done, _params()) > 3 * BF16_TOL


def test_swapped_halves_of_a_word_fail_that_tolerance(monkeypatch):
    """The planted fault: the walk takes a word's value for its key."""
    fd = importlib.import_module("tpudist.ops.flash_decode")
    half = fd._word_half
    monkeypatch.setattr(fd, "_word_half",
                        lambda w, h, dtype: half(w, 1 - h, dtype))
    fd._paged_decode_one.clear_cache()
    try:
        done = _serve(_cfg(compute_dtype=jnp.bfloat16), _params(), "flash")
    finally:
        monkeypatch.undo()
        fd._paged_decode_one.clear_cache()
    assert _worst_gap(done, _params()) > 3 * BF16_TOL


def test_serve_loop_that_skips_the_selection_fails_the_same_tolerance():
    """The control: a program that attends every row (the same weights,
    ``index_topk`` beyond every context) is not what the reference
    computes."""
    done = _serve(_cfg(topk=SEQ + 8), _params(), "dense")
    assert _worst_gap(done, _params()) > 50 * LOGIT_TOL


@pytest.mark.parametrize("decode_attention", ["dense", "flash"])
def test_context_at_or_under_topk_gives_the_plain_models_tokens(
        decode_attention):
    """With ``index_topk`` no context reaches, every row is chosen: the
    served tokens are those of the same model WITHOUT an indexer, and a
    one-shot forward of ``index_topk`` tokens gives its logits."""
    mine = _serve(_cfg(topk=SEQ + 8), _params(), decode_attention)
    theirs = {c.rid: c.tokens for c in _serve(
        _plain_cfg(), _plain_params(), decode_attention)}
    assert len(mine) == len(REQUESTS) and all(
        np.array_equal(c.tokens, theirs[c.rid]) for c in mine)
    toks = jax.random.randint(jax.random.key(3), (1, TOPK), 0, VOCAB)
    np.testing.assert_allclose(
        TransformerLM(_cfg()).apply({"params": _params()}, toks),
        TransformerLM(_plain_cfg()).apply({"params": _plain_params()},
                                          toks), atol=1e-6, rtol=0)


# -- (b) the routines against jax.numpy ---------------------------------------

def _scores(q, w, keys, seen):
    sc = jnp.einsum("thd,trd->thr", q, keys)
    sc = (jnp.maximum(sc, 0) * w[:, :, None]).sum(1)
    return jnp.where(jnp.arange(keys.shape[1])[None] < seen[:, None], sc,
                     -jnp.inf)


# (queries a grid row, one table row for all, rows a page, table entries,
# rows the LAST query of each grid row sees).  A tile of the walk is 1024
# rows: 40 pages of 8 are one short tile; 130 are a whole tile and two pages
# of a second, so the kernel's block is 2048 columns wide and the table's
# 1040 a slice of it; 16 pages of 8 are a prefill chunk's batch-1 cache
SCORE_CASES = {
    "step_empty_short_long": (1, False, 8, 40, (0, 37, 300)),
    "step_full_one_tile_edge": (1, False, 8, 40, (320, 1, 129)),
    "step_two_tiles_sliced": (1, False, 8, 130, (0, 1030, 1024, 500, 1040)),
    "two_queries_own_tables": (2, False, 8, 130, (1, 1040, 777)),
    "four_queries_chunk_at_100": (4, True, 8, 16, (104, 108, 112)),
    "eight_queries_chunk_at_0": (8, True, 8, 16, (8,)),
    "eight_queries_two_tiles": (8, True, 8, 130, (0, 8, 1029, 1040)),
    "sixteen_queries_chunk_at_40": (16, True, 8, 16, (56,)),
    "sixteen_queries_two_tiles": (16, True, 16, 70, (16, 1024, 1031, 0)),
}


@pytest.mark.parametrize("numbers", ["normal", "whole"])
@pytest.mark.parametrize("case", SCORE_CASES)
def test_index_scores_against_jax_numpy(case, numbers):
    """The kernel's ``[T, table's rows]`` on both callers' forms: a decode
    step (a query a grid row, each its own table row) and a prefill chunk
    (``tq`` queries a grid row over ONE table row, each with its own limit:
    query ``j`` of a row sees ``tq - 1 - j`` rows fewer than its last).  A
    length of 0 leaves the block ``-inf``, one inside a tile cuts it there,
    and a table that ends inside a tile is the slice after the call.  On
    small whole numbers every product and sum is exact in float32, so the
    scores are the reference's bit for bit, whatever form the kernel
    writes them in."""
    tq, shared, bs, m, last = SCORE_CASES[case]
    rng = np.random.default_rng(len(case))
    lanes, h, d, n = len(last), 8, 16, 160
    t = lanes * tq
    draw = (rng.normal if numbers == "normal"
            else lambda size: rng.integers(-3, 4, size))
    q = jnp.asarray(draw(size=(t, h, d)), jnp.float32)
    w = jnp.asarray(draw(size=(t, h)), jnp.float32)
    pool = jnp.asarray(draw(size=(n, bs, d)), jnp.float32)
    table = jnp.asarray(rng.permutation(n)[:m][None] if shared
                        else rng.integers(0, n, (lanes, m)), jnp.int32)
    got = paged_index_scores(q, w, pool, table, jnp.asarray(last, jnp.int32),
                             interpret=True)
    keys = jnp.repeat(pool[jnp.broadcast_to(table, (lanes, m))]
                      .reshape(lanes, m * bs, d), tq, axis=0)
    seen = (jnp.repeat(jnp.asarray(last), tq) - (tq - 1)
            + jnp.tile(jnp.arange(tq), lanes))
    want = _scores(q, w, keys, seen)
    assert got.shape == (t, m * bs) and got.dtype == jnp.float32
    if numbers == "whole":
        assert np.array_equal(got, want)
        return
    assert np.array_equal(np.isfinite(got), np.isfinite(want))
    np.testing.assert_allclose(np.where(np.isfinite(want), got, 0),
                               np.where(np.isfinite(want), want, 0),
                               atol=1e-5)


def test_queries_a_grid_row_follow_the_output_block():
    # the cell's chunk: 2048 queries of 16 heads over 32768 rows
    assert index_queries_per_row(2048, 16, 32768) == 16
    assert index_queries_per_row(2048, 64, 32768) == 8
    assert index_queries_per_row(2048, 16, 1 << 20) == 1
    assert index_queries_per_row(7, 16, 128) == 1


# sizes of the cases below: queries, query heads a K/V head, ids a query,
# source rows, side rows
ROWS_T, ROWS_G, ROWS_K, ROWS_N, ROWS_CAP = 5, 2, 16, 200, 4


def _given_rows(rng, h_kv, d, count, dtype=jnp.float32):
    """Queries, a K and a V source, side buffers and ids for
    ``sparse_gqa_attend``: a query's first ``count`` ids ascend, so its
    staged rows (ids from ``n`` on) come last among them; what follows is
    ignored."""
    t, g, k, n, cap = ROWS_T, ROWS_G, ROWS_K, ROWS_N, ROWS_CAP
    flat = h_kv * d
    q = jnp.asarray(rng.normal(size=(t, h_kv * g, d)), dtype)
    k_src, v_src = (jnp.asarray(rng.normal(size=(n, flat)), dtype)
                    for _ in range(2))
    side_k, side_v = (jnp.asarray(rng.normal(size=(t, cap, flat)), dtype)
                      for _ in range(2))
    ids = rng.integers(0, n + cap, (t, k))
    for i, c in enumerate(count):
        ids[i, :c] = np.sort(rng.choice(
            np.arange(n - 6, n + cap), size=c, replace=False)
            if c <= 10 else rng.choice(n + cap, size=c, replace=False))
    return (q, k_src, v_src, side_k, side_v, jnp.asarray(ids, jnp.int32),
            jnp.asarray(count, jnp.int32))


# what an indexer's layer keeps a token: K and V in ONE row of 32-bit words
_joined = pack_kv
DTYPES = pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                                 ids=["f32_side_by_side", "bf16_words"])


def _bits(x):
    """An array's bits, to compare NaNs and signed zeros too."""
    x = np.asarray(x)
    return x.view({2: np.uint16, 4: np.uint32}[x.dtype.itemsize])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float16, jnp.float32])
def test_a_row_of_words_gives_k_and_v_back_bit_for_bit(dtype):
    """``pack_kv`` -> ``unpack_kv``: a 16-bit K and V share a word (K the
    low half), 32-bit numbers lie side by side; signed zeros, infinities,
    NaNs with a payload and denormals come back as they went in."""
    size = jnp.dtype(dtype).itemsize
    uint = {2: np.uint16, 4: np.uint32}[size]
    rng = np.random.default_rng(11)
    raw = rng.integers(0, 1 << (8 * size), (2, 7, 24), dtype=np.uint64)
    top = 8 * size - 1
    mantissa = {jnp.bfloat16: 7, jnp.float16: 10, jnp.float32: 23}[dtype]
    ones = ((1 << top) - 1) >> mantissa << mantissa        # the exponent
    special = np.array([
        0, 1 << top,                                       # 0.0, -0.0
        ones, ones | 1 << top,                             # inf, -inf
        ones | 1, ones | 1 << top | 5,                     # NaN payloads
        ones | 1 << (mantissa - 1) | 3,                    # a quiet NaN
        1, 1 << top | 3, (1 << mantissa) - 1],             # denormals
        np.uint64)
    raw[:, 0, :len(special)] = special
    raw[1, 0, :len(special)] = special[::-1]
    k, v = (jax.lax.bitcast_convert_type(
        jnp.asarray(half.astype(uint)), dtype) for half in raw)
    row = pack_kv(k, v)
    width, words = kv_row(24, dtype)
    assert row.shape == (7, width) and row.dtype == words
    assert row.dtype.itemsize == 4 and row.nbytes == k.nbytes + v.nbytes
    if size == 2:
        np.testing.assert_array_equal(
            np.asarray(row), raw[0] | raw[1] << 16)
    back_k, back_v = unpack_kv(row, dtype)
    assert back_k.dtype == back_v.dtype == dtype
    np.testing.assert_array_equal(_bits(back_k), _bits(k))
    np.testing.assert_array_equal(_bits(back_v), _bits(v))
    with pytest.raises(ValueError, match="hold K and V"):
        unpack_kv(row, jnp.float32 if size == 2 else jnp.bfloat16)


@pytest.mark.parametrize("h_kv,d", [(2, 128), (4, 128), (2, 16)],
                         ids=["kv2_d128", "kv4_d128", "kv2_d16_paired"])
def test_sparse_attend_over_given_rows(h_kv, d):
    rng = np.random.default_rng(2)
    t, g, k, n, cap = ROWS_T, ROWS_G, ROWS_K, ROWS_N, ROWS_CAP
    q, k_src, v_src, side_k, side_v, ids, count = _given_rows(
        rng, h_kv, d, [1, 5, 16, 9, 16])
    got = sparse_gqa_attend(q, _joined(k_src, v_src), ids, count,
                            packed_kv_heads=h_kv,
                            side_kv=_joined(side_k, side_v), interpret=True)

    def rows(src, side):
        out = jnp.where(
            (ids >= n)[..., None],
            jnp.take_along_axis(
                side, jnp.clip(ids - n, 0, cap - 1)[..., None], 1),
            src[jnp.minimum(ids, n - 1)])
        return jnp.repeat(out.reshape(t, k, h_kv, d), g, axis=2)

    logits = jnp.einsum("thd,tkhd->thk", q, rows(k_src, side_k)) * d ** -0.5
    logits = jnp.where(jnp.arange(k)[None, None] < count[:, None, None],
                       logits, -jnp.inf)
    want = jnp.einsum("thk,tkhd->thd", jax.nn.softmax(logits, -1),
                      rows(v_src, side_v))
    np.testing.assert_allclose(got, want, atol=1e-5)


@DTYPES
@pytest.mark.parametrize("h_kv,d", [(4, 128), (2, 16)],
                         ids=["kv4_d128", "kv2_d16_paired"])
@pytest.mark.parametrize("staged,count", [
    (True, (1, 5, 16, 9, 16)), (False, (16, 16, 16, 16, 16)),
    (False, (3, 16, 7, 12, 1)), (True, (0, 16, 0, 4, 16))],
    ids=["staged", "plain", "count_under_k", "a_lane_of_length_0"])
def test_one_gather_gives_the_bits_of_two(h_kv, d, staged, count, dtype):
    """The ONE gather of rows that hold K and V (words of a key and a
    value each; 32-bit keys beside values), attended as the two halves of
    a tile's words (of its columns), against what it replaced, written
    here: a gather of the K rows and one of the V rows (the staged rows
    patched into each), attended by the walk over two pools.  The same
    rows, the same online softmax in the same order: the same bits."""
    rng = np.random.default_rng(9)
    t, k, n, cap = ROWS_T, ROWS_K, ROWS_N, ROWS_CAP
    q, k_src, v_src, side_k, side_v, ids, count = _given_rows(
        rng, h_kv, d, count, dtype)
    if not staged:
        ids = jnp.minimum(ids, n - 1)
    got = sparse_gqa_attend(
        q, _joined(k_src, v_src), ids, count, packed_kv_heads=h_kv,
        side_kv=_joined(side_k, side_v) if staged else None,
        interpret=True)

    def gathered(src, side):
        rows = src[jnp.minimum(ids, n - 1)]
        if staged:
            rows = jnp.where(
                ((ids >= n) & (jnp.arange(k) < count[:, None]))[..., None],
                jnp.take_along_axis(
                    side, jnp.clip(ids - n, 0, cap - 1)[..., None], 1),
                rows)
        block = 8
        return rows.reshape(t * k // block, block, -1)

    want = paged_flash_decode(
        q[:, None], gathered(k_src, side_k), gathered(v_src, side_v),
        jnp.arange(t * k // 8, dtype=jnp.int32).reshape(t, k // 8), count,
        packed_kv_heads=h_kv, interpret=True)[:, 0]
    assert got.dtype == dtype
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_array_equal(_bits(got), _bits(want))


def test_word_rows_of_lanes_of_every_length_give_the_bits_of_two():
    """bfloat16 at a width the chip runs (4 K/V heads of 128, 1024 chosen
    rows: a tile of 8 pages of 128): lanes of 0, 5, 700 and ``k - 1``
    rows, staged rows among each lane's chosen, over word rows against the
    two gathers attended by the walk over two pools."""
    rng = np.random.default_rng(46)
    t, g, h_kv, d, k, n, cap = 4, 2, 4, 128, 1024, 1500, 8
    flat = h_kv * d
    q = jnp.asarray(rng.normal(size=(t, h_kv * g, d)), jnp.bfloat16)
    k_src, v_src = (jnp.asarray(rng.normal(size=(n, flat)), jnp.bfloat16)
                    for _ in range(2))
    side_k, side_v = (jnp.asarray(rng.normal(size=(t, cap, flat)),
                                  jnp.bfloat16) for _ in range(2))
    count = np.array([0, 5, 700, k - 1])
    ids = rng.integers(0, n + cap, (t, k))
    for i, c in enumerate(count):
        staged = min(c, 3 + i)
        ids[i, :c] = np.concatenate([
            np.sort(rng.choice(n, size=c - staged, replace=False)),
            n + np.sort(rng.choice(cap, size=staged, replace=False))])
    ids, count = jnp.asarray(ids, jnp.int32), jnp.asarray(count, jnp.int32)
    got = sparse_gqa_attend(
        q, pack_kv(k_src, v_src), ids, count, packed_kv_heads=h_kv,
        side_kv=pack_kv(side_k, side_v), interpret=True)

    def gathered(src, side):
        rows = jnp.where(
            ((ids >= n) & (jnp.arange(k) < count[:, None]))[..., None],
            jnp.take_along_axis(
                side, jnp.clip(ids - n, 0, cap - 1)[..., None], 1),
            src[jnp.minimum(ids, n - 1)])
        return rows.reshape(t * k // 128, 128, -1)

    want = paged_flash_decode(
        q[:, None], gathered(k_src, side_k), gathered(v_src, side_v),
        jnp.arange(t * k // 128, dtype=jnp.int32).reshape(t, k // 128),
        count, packed_kv_heads=h_kv, interpret=True)[:, 0]
    assert np.isfinite(np.asarray(got, np.float32)).all()
    assert np.asarray(got[1:], np.float32).any()
    np.testing.assert_array_equal(_bits(got), _bits(want))


@DTYPES
@pytest.mark.parametrize("lens", [(0, 37, 300), (320, 1, 129)],
                         ids=["empty_short_long", "full_one_tile_edge"])
@pytest.mark.parametrize("h_kv,d", [(4, 128), (2, 16), (8, 128)],
                         ids=["kv4_d128", "kv2_d16_paired",
                              "kv8_d128_two_rows_a_lane"])
def test_every_row_walk_of_the_one_pool_gives_the_bits_of_two(lens, h_kv, d,
                                                              dtype):
    """The every-row branch of an indexer's layer: ``paged_flash_decode``
    over the ONE pool of K and V and its one side buffer against the
    same call over the split pools and side buffers (8 K/V heads of 128:
    a lane's heads take two grid rows, each with its heads' words of a
    page, or its columns of both halves where keys lie beside values)."""
    rng = np.random.default_rng(4)
    b, g, bs, m, n, cap = 3, 2, 16, 24, 40, 4
    size = jnp.dtype(dtype).itemsize
    assert paged_grid_rows(b, h_kv, d, bs, m, itemsize=size) == (
        2 * b if h_kv == 8 and size == 4 else b)
    flat = h_kv * d
    q = jnp.asarray(rng.normal(size=(b, 1, h_kv * g, d)), dtype)
    k_pool, v_pool = (jnp.asarray(rng.normal(size=(n, bs, flat)), dtype)
                      for _ in range(2))
    side_k, side_v = (jnp.asarray(rng.normal(size=(b, cap, flat)), dtype)
                      for _ in range(2))
    table = jnp.asarray(rng.integers(0, n, (b, m)), jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    got = paged_flash_decode(
        q, _joined(k_pool, v_pool), None, table, lens,
        packed_kv_heads=h_kv, side_k=_joined(side_k, side_v), side_len=3,
        interpret=True)
    want = paged_flash_decode(
        q, k_pool, v_pool, table, lens, packed_kv_heads=h_kv,
        side_k=side_k, side_v=side_v, side_len=3, interpret=True)
    np.testing.assert_array_equal(_bits(got), _bits(want))


def test_a_word_pool_of_eight_heads_takes_two_rows_a_lane():
    """bfloat16 at the widths where a lane's tile slots pass VMEM's share
    (8 K/V heads of 128, pages of 128 rows, 8 a tile): two grid rows a
    lane, each ONE copy of its four heads' words of a page."""
    rng = np.random.default_rng(8)
    b, g, h_kv, d, bs, m, n, cap = 3, 2, 8, 128, 128, 8, 10, 4
    assert paged_grid_rows(b, h_kv, d, bs, m, itemsize=2) == 2 * b
    flat = h_kv * d
    q = jnp.asarray(rng.normal(size=(b, 1, h_kv * g, d)), jnp.bfloat16)
    k_pool, v_pool = (jnp.asarray(rng.normal(size=(n, bs, flat)),
                                  jnp.bfloat16) for _ in range(2))
    side_k, side_v = (jnp.asarray(rng.normal(size=(b, cap, flat)),
                                  jnp.bfloat16) for _ in range(2))
    table = jnp.asarray(rng.integers(0, n, (b, m)), jnp.int32)
    lens = jnp.asarray((0, 700, 1024), jnp.int32)
    got = paged_flash_decode(
        q, pack_kv(k_pool, v_pool), None, table, lens,
        packed_kv_heads=h_kv, side_k=pack_kv(side_k, side_v), side_len=2,
        interpret=True)
    want = paged_flash_decode(
        q, k_pool, v_pool, table, lens, packed_kv_heads=h_kv,
        side_k=side_k, side_v=side_v, side_len=2, interpret=True)
    np.testing.assert_array_equal(_bits(got), _bits(want))


@pytest.mark.parametrize("offset,rows", [(0, 64), (32, 64), (96, 128)])
def test_flash_pass_over_a_mask(offset, rows):
    """``flash_chosen_rows``: a chunk's queries at an offset over the rows
    a mask names, against a masked softmax; rows after a query are not
    attended whatever the mask says."""
    rng = np.random.default_rng(5)
    s, h, h_kv, d = 32, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(1, s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, rows, h_kv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, rows, h_kv, d)), jnp.float32)
    mask = rng.random((s, rows)) < 0.3
    mask[np.arange(s), np.minimum(offset + np.arange(s), rows - 1)] = True
    got = flash_chosen_rows(q, k, v, jnp.asarray(mask, jnp.int8), offset,
                            block_q=16, block_k=16, interpret=True)
    seen = mask & (np.arange(rows)[None] <= offset + np.arange(s)[:, None])
    kk, vv = (jnp.repeat(x, h // h_kv, axis=2) for x in (k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * d ** -0.5
    logits = jnp.where(seen[None, None], logits, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), vv)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_equal_scores_go_to_the_lower_position():
    sc = jnp.asarray([[1., 3., 3., -jnp.inf, 2., 3., 3., 0.],
                      [5., 5., 5., 5., 5., 5., 5., 5.],
                      [-jnp.inf, 1., -jnp.inf, 1., 1., -jnp.inf, 0., 0.]])
    got = np.asarray(index_select(sc, 4))
    assert got.tolist() == [[1, 2, 5, 6], [0, 1, 2, 3], [1, 3, 4, 6]]
    assert got.tolist() == np.sort(jax.lax.top_k(sc, 4)[1], axis=1).tolist()
    mask = np.asarray(index_select_mask(sc, 4))
    assert [np.flatnonzero(m).tolist() for m in mask] == got.tolist()
    # fewer than k scores above -inf: they come first, and alone in a mask
    few = jnp.asarray([[-jnp.inf, 2., -jnp.inf, 1., -jnp.inf, -jnp.inf,
                        -jnp.inf, -jnp.inf]])
    assert np.asarray(index_select(few, 4))[0, :2].tolist() == [1, 3]
    assert np.flatnonzero(index_select_mask(few, 4)[0]).tolist() == [1, 3]


def _rows_of_every_length(rng, sc):
    live = rng.integers(1, sc.shape[1] + 1, len(sc))
    return np.where(np.arange(sc.shape[1])[None] < live[:, None], sc,
                    -np.inf)


def _stepped(rng, t, r):
    """Scores in steps of a quarter (ties everywhere), rows of every
    length, one row all equal."""
    sc = _rows_of_every_length(
        rng, np.round(rng.normal(size=(t, r)).astype(np.float32) * 4) / 4)
    sc[2] = np.where(np.isfinite(sc[2]), 0.5, -np.inf)
    return sc


def _negative(rng, t, r):
    """Every score below zero (keys whose bits run against their order),
    in steps of an eighth, rows of every length."""
    return _rows_of_every_length(
        rng, -np.round(np.abs(rng.normal(size=(t, r))) * 8 + 1) / 8)


def _zeros(rng, t, r):
    """``-0.0`` beside ``+0.0`` and little else: two keys one apart that
    compare equal as floats, which ``jax.lax.top_k`` tells apart."""
    return rng.choice(np.float32([-0.0, 0.0, 0.0, -0.25, 0.25]), (t, r))


def _shallow(rng, t, r):
    """No row past a third of the width, and one with nothing but
    ``-inf``: passes that follow the rows stop steps before the end."""
    sc = _stepped(rng, t, r)
    sc[:, r // 3:] = -np.inf
    sc[1] = -np.inf
    return sc


@pytest.mark.parametrize("shape, scores", [
    pytest.param(shape, scores, id="x".join(map(str, shape)) + name)
    for shape, scores, name in [
        ((3, 300, 16), _stepped, ""), ((256, 1000, 64), _stepped, ""),
        ((5, 40, 16), _stepped, ""), ((4, 70, 64), _stepped, ""),
        ((6, 8192, 32), _stepped, ""),
        # a decode step's: 12 lanes, two pass steps and 128 columns more
        ((12, 4224, 64), _stepped, "-step"),
        # rows no multiple of the query block: a last block that overhangs
        ((40, 1000, 64), _stepped, "-overhang"),
        ((12, 2176, 48), _negative, "-negative"),
        ((20, 640, 200), _zeros, "-signed-zeros"),
        ((9, 8192, 32), _shallow, "-shallow"),
        ((9, 6272, 32), _shallow, "-shallow-with-a-tail")]])
def test_threshold_selection_is_top_k(shape, scores):
    """The same set as ``jax.lax.top_k``, in ascending order, whatever the
    scores hold; the mask names the same set, and passes that follow the
    rows (a prefill chunk's) give the same mask, with ``rows`` inside a
    pass step and at the width."""
    t, r, k = shape
    sc = scores(np.random.default_rng(3), t, r).astype(np.float32)
    k = min(k, r)
    want = np.asarray(jax.lax.top_k(jnp.asarray(sc), k)[1])
    got = np.asarray(index_select(jnp.asarray(sc), k))
    mask = np.asarray(index_select_mask(jnp.asarray(sc), k))
    follow = jax.jit(lambda s, n: index_select_mask(s, k, rows=n))
    live = int(np.isfinite(sc).any(axis=0).nonzero()[0].max()) + 1
    for rows in (live, r):
        assert np.array_equal(mask, follow(jnp.asarray(sc), jnp.int32(rows)))
    for i in range(t):
        n = min(int(np.isfinite(sc[i]).sum()), k)
        assert sorted(want[i, :n].tolist()) == got[i, :n].tolist()
        assert np.flatnonzero(mask[i]).tolist() == got[i, :n].tolist()


@pytest.mark.parametrize("tied_rows", [
    0, 3, 8, 9, pytest.param(96, id="every-row-ties"),
    pytest.param((31, 32), id="ties-straddle-two-blocks")],
    ids=lambda n: f"{n}-rows-tie")
def test_a_chunks_few_tied_rows_are_cut_apart(tied_rows):
    """A chunk's rows (three blocks of 32 queries) of float scores of which
    a few tie at the k-th value, as a deep chunk's do: the kernel searches
    for the cut among equal columns only in a block that holds such a row;
    none, some, all of the blocks, and two neighbours for the last row of
    one and the first of the next.  The mask is ``jax.lax.top_k``'s set
    every time, with passes that follow the rows too."""
    from tpudist.ops.flash_decode import _select_queries

    t, r, k = 96, 512, 32
    assert _select_queries(t, r) == 32
    rng = np.random.default_rng(
        tied_rows if isinstance(tied_rows, int) else 0)
    sc = rng.normal(size=(t, r)).astype(np.float32)
    live = rng.integers(k + 8, r + 1, t)
    sc = np.where(np.arange(r)[None] < live[:, None], sc, -np.inf)
    if isinstance(tied_rows, int):
        tied_rows = rng.choice(t, tied_rows, replace=False)
    for i in tied_rows:
        # the k-th value three more times, on both sides of its column
        order = np.argsort(-sc[i], kind="stable")
        kth = order[k - 1]
        spare = [c for c in order[k + 4:k + 40] if c != kth][:3]
        sc[i, spare] = sc[i, kth]
    want = np.asarray(jax.lax.top_k(jnp.asarray(sc), k)[1])
    mask = np.asarray(index_select_mask(jnp.asarray(sc), k))
    followed = np.asarray(jax.jit(
        lambda s, n: index_select_mask(s, k, rows=n))(
        jnp.asarray(sc), jnp.int32(live.max())))
    assert np.array_equal(mask, followed)
    for i in range(t):
        assert np.flatnonzero(mask[i]).tolist() == sorted(want[i].tolist())


# -- (c) a block of leaves of different width ----------------------------------

@functools.cache
def _watched_run():
    """One served run with ``check()`` at every poll and, from every poll,
    a copy of a layer's cache (the next segment donates it), the page
    table and the lanes' lengths."""
    snaps = []

    def watch(loop):
        loop.pool.check()
        snaps.append((
            jax.tree.map(jnp.copy, loop.cache["block1"]["attn"]),
            loop.pool.table.copy(),
            np.asarray(_index_leaves(loop.cache)[0])))

    return _serve(_cfg(), _params(), "flash", watch), snaps


@pytest.mark.parametrize("rid", ["r1", "r2", "r0"],
                         ids=["one_chunk", "two_chunks", "four_chunks"])
def test_the_leaves_ride_admission_side_buffer_and_release(rid):
    """The rows a live lane holds in ``paged_ikey`` (128 wide: 16 numbers
    and zeros) and in ``paged_kv`` (64 wide: K's 32 columns, then V's),
    read back through its page table after its prompt was inserted (the
    finish joins the halves, whatever the number of chunks the prompt
    took) and segments merged their staged rows (``side_kv``, joined by
    the step), are the rows a one-chunk prefill of the same tokens
    computes; ``check()`` holds at every poll, and every block comes
    back."""
    done, snaps = _watched_run()
    mine = next(c for c in done if c.rid == rid)
    seq = np.concatenate([mine.prompt, mine.tokens])
    model = TransformerLM(_cfg(), decode=True)
    _, mut = model.apply(
        {"params": _params(), "cache": _blank_cache(model, 1)},
        jnp.asarray(seq[None]), mutable=["cache"])
    want = {leaf: np.asarray(rows)[0] for leaf, rows in
            mut["cache"]["block1"]["attn"].items()
            if leaf.startswith("cached_")}
    assert sorted(want) == ["cached_ikey", "cached_key", "cached_value"]
    halves = np.concatenate([want["cached_key"], want["cached_value"]], -1)
    checked = 0
    for node, table, lengths in snaps:
        assert _kv_leaves(node, "paged") == ["ikey", "kv"]
        assert _kv_leaves(node, "side") == ["ikey", "kv"]
        assert "side_index" in node
        for lane, held in enumerate(lengths):
            # the lane holds this request, past its prompt: the finish's
            # rows and some a segment merged
            if not len(mine.prompt) < held <= len(seq):
                continue
            for leaf, rows_want, width in (("ikey", want["cached_ikey"], 128),
                                           ("kv", halves, 64)):
                pool = np.asarray(node[f"paged_{leaf}"])
                assert pool.shape[-1] == width
                rows = pool[table[lane]].reshape(-1, width)[:held]
                if not np.allclose(rows[:4, :4], rows_want[:4, :4],
                                   atol=2e-5):
                    break       # the lane holds another request
                np.testing.assert_allclose(rows, rows_want[:held],
                                           atol=2e-5)
                if leaf == "ikey":
                    assert not rows[:, DIMS.index_dim:].any()
                    assert rows[:, :DIMS.index_dim].any()
            else:
                checked += 1
    assert checked


# sha256 of the plain model's segment program (StableHLO text of
# ``serve_programs()["_segment_impl"]`` at this file's sizes, the paged
# kernel traced under interpret) as the commit BEFORE the one pool of K
# beside V lowered it: the layout is an indexer's layer's alone, so no
# other model's program moved by an operation.  A later change that means to
# alter every model's segment records its own text's hash here.
_PLAIN_SEGMENT_SHA256 = (
    "7a43e3b3fb68f7e63e1d871f64b423bc31dc8d5b5be95f094293ad8fae1d3ed2")


def test_a_model_without_an_indexer_keeps_its_leaves_and_its_program():
    loop = _loop(_plain_cfg(), _plain_params(), "flash")
    node = loop.cache["block1"]["attn"]
    assert _kv_leaves(node, "paged") == ["key", "value"]
    assert _kv_leaves(node, "side") == ["key", "value"]
    assert loop._grid_rows == 3      # a lane's two narrow heads share a row
    jitted, args, static = loop.serve_programs()["_segment_impl"]
    text = jitted.lower(*args, **static).as_text()
    assert (hashlib.sha256(text.encode()).hexdigest()
            == _PLAIN_SEGMENT_SHA256)


def test_eight_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The guide's share test at toy size: the expert layer with all 8
    experts against the sum of its 4 shares of 2 held experts each (the
    router over all 8 in every share)."""
    x = jax.random.normal(jax.random.key(7), (12, EMBED))
    whole = MoEMLP(d_model=EMBED, d_ff=DIMS.expert_ff, moe=_moe(held=None),
                   dtype=jnp.float32)
    full = whole.init(jax.random.key(8), x)["params"]
    want, _ = whole.apply({"params": full}, x)
    total = 0.0
    for first in range(0, 8, 2):
        part = MoEMLP(d_model=EMBED, d_ff=DIMS.expert_ff,
                      moe=_moe(held=(first, 2)), dtype=jnp.float32)
        mine = {**full, **{k: full[k][first: first + 2]
                           for k in ("w_gate", "w_up", "w_down")}}
        total = total + part.apply({"params": mine}, x)[0]
    np.testing.assert_allclose(total, want, atol=1e-5)


# -- (d) what it refuses, and what it counts ------------------------------------

def test_refusals_at_construction():
    kw = dict(num_slots=2, kv_block_size=16)
    with pytest.raises(ValueError, match="indexer.*cache_layout='paged'"):
        ServeLoop(_cfg(), _params(), cache_layout="dense", **kw)
    with pytest.raises(ValueError, match="an indexer's key beside K and V"):
        ServeLoop(_cfg(), _params(), cache_layout="paged",
                  preempt="migrate", **kw)
    with pytest.raises(ValueError, match="an indexer's key beside K and V"):
        ServeLoop(_cfg(), _params(), cache_layout="paged", role="prefill",
                  **kw)
    with pytest.raises(ValueError, match="together"):
        _cfg(index_heads=None)
    with pytest.raises(ValueError, match="multiples of 8"):
        _cfg(topk=12)
    with pytest.raises(ValueError, match="no sliding window"):
        _cfg(attention_window=8)
    with pytest.raises(ValueError, match="causal attention only"):
        TransformerLM(_cfg()).apply({"params": _params()},
                                    jnp.zeros((1, 8), jnp.int32),
                                    causal=False)


def test_an_indexer_refuses_the_decode_role():
    with pytest.raises(ValueError, match="an indexer's key beside K and V"):
        ServeLoop(_cfg(), _params(), num_slots=2, cache_layout="paged",
                  kv_block_size=16, role="decode")


def test_prefix_sharing_and_the_host_tier_are_off(monkeypatch):
    monkeypatch.setenv("TPUDIST_KV_HOST_TIER_BYTES", str(1 << 20))
    loop = ServeLoop(_cfg(), _params(), num_slots=2, cache_layout="paged",
                     kv_block_size=16, prefix_sharing=True)
    assert loop._prefix_cache is None and loop._tier is None
    plain = ServeLoop(_plain_cfg(), _plain_params(), num_slots=2,
                      cache_layout="paged", kv_block_size=16,
                      prefix_sharing=True)
    assert plain._prefix_cache is not None and plain._tier is not None


def test_segments_and_chunks_say_what_was_scored_and_chosen():
    names = ("index_rows_selected", "index_rows_scored", "decode_rows_live")
    before = [obs.counter(f"serve/{n}").value() for n in names]
    obs.tracer.clear()
    _serve(_cfg(), _params(), "dense")
    chosen, scored, live = (obs.counter(f"serve/{n}").value() - b
                            for n, b in zip(names, before))
    assert 0 < chosen < scored == live
    drains = [e["args"] for e in obs.tracer.events()
              if e["name"] == "serve/segment_drain"]
    assert drains and all(
        a["rows_selected"] <= min(a["rows_scored"], 3 * TOPK)
        and a["rows_scored"] == a["rows_live"] for a in drains)
    assert any(a["rows_selected"] < a["rows_scored"] for a in drains)
    chunks = [e["args"] for e in obs.tracer.events()
              if e["name"] == "serve/prefill_chunk"]
    # a chunk is sparse once it ends beyond row index_topk
    assert chunks and all(
        a["sparse"] == (a["off"] + a["width"] > TOPK) for a in chunks)
    assert {a["sparse"] for a in chunks} == {True, False}


@pytest.mark.parametrize("decode_attention", ["flash", "dense"])
def test_segments_say_what_their_gathers_fetched(decode_attention):
    """``gathers``: ONE gather of chosen rows a layer and step.
    ``rows_gathered`` / ``serve/rows_gathered``: every lane's
    ``index_topk`` rows a gather, a layer, in each step that found some
    lane beyond ``index_topk`` rows; none on the route without kernels."""
    before = obs.counter("serve/rows_gathered").value()
    obs.tracer.clear()
    _serve(_cfg(), _params(), decode_attention)
    drains = [e["args"] for e in obs.tracer.events()
              if e["name"] == "serve/segment_drain"]
    a_step = 3 * TOPK * DIMS.layers        # lanes x rows x layers x 1
    assert drains and all(a["gathers"] == 1 for a in drains)
    assert all(a["rows_gathered"] % a_step == 0
               and a["rows_gathered"] <= a_step * a["steps_run"]
               for a in drains)
    total = sum(a["rows_gathered"] for a in drains)
    assert obs.counter("serve/rows_gathered").value() - before == total
    if decode_attention == "dense":
        assert total == 0
    else:
        # a segment whose longest lane starts beyond index_topk rows
        # gathers in every step it ran
        assert any(a["rows_gathered"] == a_step * a["steps_run"] > 0
                   for a in drains)


@pytest.mark.parametrize("dtype,words", [
    (jnp.float32, 2 * DIMS.kv_heads * DIMS.head_dim),
    (jnp.bfloat16, DIMS.kv_heads * DIMS.head_dim)],
    ids=["f32_a_number_a_word", "bf16_two_numbers_a_word"])
def test_a_row_is_32_bit_words_whatever_the_compute_dtype(dtype, words):
    """``paged_kv`` / ``side_kv`` by the compute dtype's width: a float32
    model keeps K beside V in float32, ``[., 2 x kv_heads x head_dim]``
    (the layout before words); a bfloat16 model ``uint32[., kv_heads x
    head_dim]``.  The same bytes a token either way; the index keys stay
    in the compute dtype.  ``serve/kv_row_words`` and the segment span's
    ``row_words`` say which."""
    cfg = _cfg(compute_dtype=dtype)
    obs.tracer.clear()
    assert len(_serve(cfg, _params(), "dense")) == len(REQUESTS)
    flat = DIMS.kv_heads * DIMS.head_dim
    node = _loop(cfg, _params(), "flash").cache["block1"]["attn"]
    want = ((2 * flat, jnp.float32) if dtype == jnp.float32
            else (flat, jnp.uint32))
    assert kv_row(flat, dtype) == want
    assert node["paged_kv"].shape == (32, 16, want[0])
    assert node["side_kv"].shape == (3, 4, want[0])
    assert node["paged_kv"].dtype == node["side_kv"].dtype == want[1]
    assert node["paged_kv"].shape[2] * node["paged_kv"].dtype.itemsize == (
        2 * flat * jnp.dtype(dtype).itemsize)
    assert node["paged_ikey"].dtype == node["side_ikey"].dtype == dtype
    assert obs.gauge("serve/kv_row_words").value() == words
    drains = [e["args"] for e in obs.tracer.events()
              if e["name"] == "serve/segment_drain"]
    assert drains and all(a["row_words"] == words for a in drains)


def test_a_model_without_an_indexer_gathers_rows_of_no_words():
    _loop(_cfg(), _params(), "flash")
    assert obs.gauge("serve/kv_row_words").value() > 0
    _loop(_plain_cfg(), _plain_params(), "flash")
    assert obs.gauge("serve/kv_row_words").value() == 0


def test_a_model_without_an_indexer_says_neither():
    obs.tracer.clear()
    _serve(_plain_cfg(), _plain_params(), "dense")
    assert all("rows_selected" not in e["args"]
               and "rows_scored" not in e["args"]
               and "gathers" not in e["args"]
               and "rows_gathered" not in e["args"]
               and "row_words" not in e["args"]
               and "sparse" not in e["args"]
               for e in obs.tracer.events()
               if e["name"] in ("serve/segment_drain",
                                "serve/prefill_chunk"))
