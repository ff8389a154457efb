"""A chunked admission's finish rides in the iteration of its last prefill
chunk (PR 30): the lane joins the segment dispatched in that same
iteration, so a ``c``-chunk prompt stands filled and not decoding for
``c - 1`` segments (``decode_seq - admit_seq`` of its ``serve/request``
span), no lane gets more than one chunk an iteration, and tokens are what
the plain greedy rollout gives.  Toy model on the CPU, dense and paged,
``pipeline_depth`` 1, 2 and 3."""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist import obs
from tpudist.models import Request, ServeLoop, TransformerConfig, TransformerLM
from tpudist.models.generate import greedy_generate

VOCAB, SEQ, CHUNK, STEPS, SLOTS = 64, 128, 8, 4, 3
CFG = TransformerConfig(vocab_size=VOCAB, num_layers=2, num_heads=2,
                        num_kv_heads=1, embed_dim=32, max_seq_len=SEQ)
LAYOUTS = {"dense": dict(decode_attention="dense"),
           "paged": dict(cache_layout="paged", kv_block_size=16,
                         prefix_sharing=False),
           "paged-shared": dict(cache_layout="paged", kv_block_size=16,
                                prefix_sharing=True)}
DEPTHS = (1, 2, 3)
CASES = [pytest.param(layout, depth, id=f"{layout}-depth{depth}")
         for layout in ("dense", "paged") for depth in DEPTHS]
# widths 8 and 3 only, so a loop compiles two chunk programs: prompts of
# 1, 1, 2, 5, 1 and 3 chunks behind a companion that decodes throughout
COMPANION = (5, 100)
LENGTHS = (CHUNK, 2 * CHUNK, 4 * CHUNK + 3, 3, 2 * CHUNK + 3)


@functools.lru_cache(maxsize=None)
def model_params():
    return TransformerLM(CFG).init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"]


@functools.lru_cache(maxsize=None)
def loop_of(layout: str, **kw) -> ServeLoop:
    """One loop a layout: the depths share its executables, so a
    difference between them is the host's scheduling, not numerics."""
    return ServeLoop(CFG, model_params(), num_slots=SLOTS,
                     steps_per_sync=STEPS, prefill_chunk=CHUNK,
                     **LAYOUTS[layout], **dict(kw))


def prompt(seed: int, n: int) -> np.ndarray:
    return np.random.default_rng(seed).integers(1, VOCAB, n).astype(np.int32)


def spans(name: str) -> list[dict]:
    return [e for e in obs.tracer.events() if e["name"] == name]


@functools.lru_cache(maxsize=None)
def companion_run(layout: str, depth: int):
    """Five prompts through two lanes while a third lane decodes for the
    whole run, so every iteration dispatches a segment: (completions,
    spans by name, what ``serve/admit_segments`` gained)."""
    loop = loop_of(layout)
    loop.pipeline_depth = depth
    reqs = [Request(prompt(0, COMPANION[0]), COMPANION[1], rid=0)] + [
        Request(prompt(i, n), 5, rid=i) for i, n in enumerate(LENGTHS, 1)]
    hist = obs.histogram("serve/admit_segments")
    before = hist.summary()
    obs.tracer.clear()
    done = loop.run(reqs)
    after = hist.summary()
    assert done[-1].rid == 0, "the companion has to outlast the others"
    names = ("serve/request", "serve/prefill_chunk", "serve/admit_finish",
             "serve/admit_poll", "serve/segment")
    return (done, {n: spans(n) for n in names},
            {k: after[k] - before[k] for k in ("count", "sum")})


@pytest.mark.parametrize("layout,depth", CASES)
def test_lane_waits_chunks_less_one_segments(layout, depth):
    """A one-chunk prompt joins the segment dispatched in the iteration
    that admitted its chunk (0 segments filled and idle; 1 before the
    finish was folded in), a ``c``-chunk prompt waits ``c - 1``; the
    histogram holds the same sum."""
    done, sp, gained = companion_run(layout, depth)
    waited = {}
    for e in sp["serve/request"]:
        a = e["args"]
        assert a["chunks"] == math.ceil(a["prompt_len"] / CHUNK)
        assert isinstance(a["admit_seq"], int)
        assert isinstance(a["decode_seq"], int)
        waited[a["rid"]] = a["decode_seq"] - a["admit_seq"]
        assert waited[a["rid"]] == a["chunks"] - 1, a
    assert sorted(waited) == list(range(len(LENGTHS) + 1))
    assert sorted(waited.values()) == [0, 0, 0, 1, 2, 4]
    assert gained == {"count": len(done), "sum": sum(waited.values())}


def iteration_of(sp: dict, event: dict) -> int:
    """Which outer iteration an event fell in: the loop opens one
    ``serve/admit_poll`` before its admissions advance and one after
    every drain, so no two ``advance_admissions`` share a count."""
    return sum(1 for p in sp["serve/admit_poll"] if p["ts"] <= event["ts"])


@pytest.mark.parametrize("layout,depth", CASES)
def test_one_chunk_a_lane_an_iteration(layout, depth):
    """The fold moves the finish and nothing else: a lane still gets at
    most ONE ``serve/prefill_chunk`` per segment dispatched, so the device
    time between two segments is what it was; the finish carries the
    ``seq`` of the lane's last chunk and sits in its iteration."""
    done, sp, _ = companion_run(layout, depth)
    chunks = sp["serve/prefill_chunk"]
    assert len(chunks) == sum(c.timing.chunks for c in done)
    per = {}
    for e in chunks:
        key = (e["args"]["seq"], e["args"]["slot"])
        per[key] = per.get(key, 0) + 1
    assert set(per.values()) == {1}
    # two lanes did prefill side by side, or the bound above is empty
    assert len({seq for seq, _ in per}) < len(per)
    # every seq the chunks name was dispatched as a segment of its own
    dispatched = {e["args"]["seq"] for e in sp["serve/segment"]}
    assert {seq for seq, _ in per} <= dispatched
    finishes = {e["args"]["rid"]: e for e in sp["serve/admit_finish"]}
    assert sorted(finishes) == sorted(c.rid for c in done)
    for rid, fin in finishes.items():
        last = max((e for e in chunks if e["args"]["rid"] == rid),
                   key=lambda e: e["ts"])
        assert fin["args"]["seq"] == last["args"]["seq"]
        assert fin["ts"] >= last["ts"]
        assert iteration_of(sp, fin) == iteration_of(sp, last)


@pytest.mark.parametrize("layout,depth", CASES)
def test_stamp_order_holds_at_every_depth(layout, depth):
    done, sp, _ = companion_run(layout, depth)
    for c in done:
        t = c.timing
        assert (t.enqueue <= t.admit <= t.prefill_done <= t.first_token
                <= t.done)
    for e in sp["serve/request"]:
        a = e["args"]
        assert 0 <= a["admit"] <= a["prefill_done"] <= a["first_token"]


def reference(prompts, budgets):
    """The plain greedy rollout of every prompt, and two stop tokens that
    end some of these streams and not others."""
    streams = [np.asarray(greedy_generate(
        CFG, model_params(), jnp.asarray(p)[None, :], n))[0, len(p):]
        for p, n in zip(prompts, budgets)]
    seen = [set(s.tolist()) for s in streams]
    stops = [t for t in range(VOCAB)
             if 0 < sum(t in s for s in seen) < len(seen) // 2][:2]
    assert len(stops) == 2, "no token ends some streams and spares others"
    want = {}
    for rid, s in enumerate(streams):
        hit = [i for i, t in enumerate(s.tolist()) if t in stops]
        want[rid] = ((tuple(s[:hit[0] + 1].tolist()), "stop") if hit
                     else (tuple(s.tolist()), "length"))
    assert {r for _, r in want.values()} == {"stop", "length"}
    return want, tuple(stops)


@functools.lru_cache(maxsize=None)
def mixed_workload():
    """Prompts of 1, 2 and 5 chunks (four share their first 16 tokens:
    one block of the paged layouts, and the last IS that block, a
    full-prompt hit whose COW block is two chunks wide), budgets of 2 to
    25 tokens, more requests than lanes."""
    shared = prompt(99, 16)
    prompts = [prompt(20, 3), prompt(21, CHUNK), prompt(22, 2 * CHUNK),
               np.concatenate([shared, prompt(23, 3 * CHUNK + 3)]),
               prompt(24, 4 * CHUNK + 3), prompt(25, CHUNK + 3),
               np.concatenate([shared, prompt(26, 3)]),
               np.concatenate([shared, prompt(27, 3 * CHUNK)]), shared]
    budgets = [25, 2, 12, 25, 7, 25, 18, 9, 25]
    want, stops = reference(prompts, budgets)
    return prompts, budgets, want, stops


@pytest.mark.parametrize("depth", DEPTHS)
@pytest.mark.parametrize("layout", list(LAYOUTS))
def test_mixed_workload_matches_greedy_reference(layout, depth):
    """Same programs in the same per-lane order: greedy tokens and finish
    reasons are the plain rollout's, stop and length endings, queueing and
    lane reuse, with prefix sharing on and off."""
    prompts, budgets, want, stops = mixed_workload()
    loop = loop_of(layout, stop_tokens=stops)
    loop.pipeline_depth = depth
    done = loop.run([Request(p, n, rid=i)
                     for i, (p, n) in enumerate(zip(prompts, budgets))])
    got = {c.rid: (tuple(c.tokens.tolist()), c.reason) for c in done}
    assert got == want
    if loop.pool is not None:
        loop.pool.check()
    if layout == "paged-shared":
        assert loop.prefix_stats["hits"] > 0


@pytest.mark.parametrize("depth", DEPTHS)
def test_prefill_role_handoff_leaves_with_its_last_chunk(depth):
    """``role="prefill"``: the export follows the finish, so it too leaves
    in the iteration of the last chunk; the lane never decodes
    (``decode_seq`` None), and a decode-role loop that adopts the pages
    gives the plain rollout's tokens with nothing waited."""
    prompts, budgets, want, stops = mixed_workload()
    reqs = [Request(p, n, rid=i)
            for i, (p, n) in enumerate(zip(prompts, budgets))]
    pre = loop_of("paged", role="prefill", stop_tokens=stops)
    pre.pipeline_depth = depth
    obs.tracer.clear()
    handoffs = pre.run(reqs)
    assert sorted(c.rid for c in handoffs) == list(range(len(reqs)))
    assert all(c.reason == "handoff" and c.handoff is not None
               for c in handoffs)
    assert pre.pool.free_blocks == pre.pool.num_blocks
    sp = {n: spans(n) for n in ("serve/request", "serve/prefill_chunk",
                                "serve/admit_finish", "serve/admit_poll")}
    for e in sp["serve/request"]:
        assert e["args"]["decode_seq"] is None
        assert e["args"]["admit_seq"] == 0  # this loop dispatches no segment
    for fin in sp["serve/admit_finish"]:
        last = max((e for e in sp["serve/prefill_chunk"]
                    if e["args"]["rid"] == fin["args"]["rid"]),
                   key=lambda e: e["ts"])
        assert iteration_of(sp, fin) == iteration_of(sp, last)
    dec = loop_of("paged", role="decode", stop_tokens=stops)
    dec.pipeline_depth = depth
    obs.tracer.clear()
    done = dec.run([Request(r.prompt, r.max_new_tokens, rid=r.rid,
                            kv_handoff=h.handoff)
                    for r in reqs for h in handoffs if h.rid == r.rid])
    assert {c.rid: (tuple(c.tokens.tolist()), c.reason)
            for c in done} == want
    assert all(e["args"]["decode_seq"] == e["args"]["admit_seq"]
               for e in spans("serve/request"))
