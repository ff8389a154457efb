"""ServeLoop's own clock: the stamps every completion carries, the record
every drained segment leaves, the counters ticked from it, and a compile
that names the span it happened in.  Toy model on the CPU; the four loops
are dense / paged by one-shot / chunked admission."""

import math
import time

import jax
import numpy as np
import pytest

from tpudist import obs
from tpudist.models import (
    Request,
    RequestTiming,
    ServeLoop,
    TransformerConfig,
    TransformerLM,
)

VOCAB, SEQ, CHUNK, STEPS, SLOTS = 64, 128, 8, 8, 2
LAYOUTS = {"dense": dict(decode_attention="dense"),
           "paged": dict(cache_layout="paged", kv_block_size=16,
                         prefix_sharing=False)}
CASES = [pytest.param(layout, chunked, id=f"{layout}-{how}")
         for layout in LAYOUTS
         for chunked, how in ((False, "oneshot"), (True, "chunked"))]
EPS = 1e-6  # spans keep microseconds as floats; stamps are seconds


@pytest.fixture(scope="module")
def lm():
    cfg = TransformerConfig(vocab_size=VOCAB, num_layers=2, num_heads=2,
                            num_kv_heads=1, embed_dim=32, max_seq_len=SEQ)
    params = TransformerLM(cfg).init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"]
    return cfg, params


def make_loop(lm, layout: str, chunked: bool, **kw) -> ServeLoop:
    cfg, params = lm
    return ServeLoop(cfg, params, num_slots=SLOTS, steps_per_sync=STEPS,
                     prefill_chunk=CHUNK, chunked_prefill=chunked,
                     **LAYOUTS[layout], **kw)


def requests(lengths, max_new, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rng.integers(1, VOCAB, n).astype(np.int32), m, rid=i)
            for i, (n, m) in enumerate(zip(lengths, max_new))]


def spans(name: str) -> list[dict]:
    return [e for e in obs.tracer.events() if e["name"] == name]


def run_traced(loop, reqs, **kw):
    """(completions, this run's spans by name)."""
    obs.tracer.clear()
    done = loop.run(reqs, **kw)
    return done, {n: spans(n) for n in (
        "serve/request", "serve/segment_fetch", "serve/segment_drain")}


@pytest.mark.parametrize("layout,chunked", CASES)
def test_stamps_present_and_ordered(lm, layout, chunked):
    loop = make_loop(lm, layout, chunked)
    done, sp = run_traced(loop, requests([5, 19, 30, 9], [6, 3, 12, 1]))
    assert len(done) == 4
    fetch_end = {e["args"]["seq"]: (e["ts"] + e["dur"]) * 1e-6
                 for e in sp["serve/segment_fetch"]}
    for c in done:
        t = c.timing
        assert isinstance(t, RequestTiming) and c.reason == "length"
        assert (t.enqueue <= t.admit <= t.prefill_done <= t.first_token
                <= t.done)
        assert t.tokens == len(c.tokens)
        # the first token is stamped when the fetch of the segment that
        # carried it has returned: that segment's drain span starts there
        carried = [e["args"]["seq"] for e in sp["serve/segment_drain"]
                   if abs(e["ts"] * 1e-6 - t.first_token) < EPS]
        assert len(carried) == 1
        assert fetch_end[carried[0]] <= t.first_token + EPS
    if not chunked:
        assert all(c.timing.prefill_done == c.timing.admit for c in done)


@pytest.mark.parametrize("layout,chunked", CASES)
def test_stop_completion_is_stamped(lm, layout, chunked):
    """Whatever token comes second becomes the stop token of a second,
    identical run: it ends ``stop`` with two tokens and every stamp."""
    [free] = make_loop(lm, layout, chunked).run(requests([11], [5]))
    loop = make_loop(lm, layout, chunked,
                     stop_tokens=(int(free.tokens[1]),))
    [c] = loop.run(requests([11], [5]))
    assert c.reason == "stop" and len(c.tokens) <= 2
    t = c.timing
    assert t.enqueue <= t.admit <= t.prefill_done <= t.first_token <= t.done


@pytest.mark.parametrize("layout,chunked", CASES)
def test_tokens_are_conserved(lm, layout, chunked):
    loop = make_loop(lm, layout, chunked)
    drained = obs.counter("serve/tokens_drained")
    steps = obs.counter("serve/decode_steps")
    lane_steps = obs.counter("serve/lane_steps")
    before = (drained.value(), steps.value(), lane_steps.value(),
              loop._served_tokens)
    done, sp = run_traced(loop, requests([5, 19, 30, 9, 14],
                                         [6, 3, 12, 1, 20]))
    total = sum(len(c.tokens) for c in done)
    assert total == 6 + 3 + 12 + 1 + 20
    drains = [e["args"] for e in sp["serve/segment_drain"]]
    assert sum(a["tokens"] for a in drains) == total
    assert sum(a["first_tokens"] for a in drains) == len(done)
    assert drained.value() - before[0] == total
    assert loop._served_tokens - before[3] == total
    run = sum(a["steps_run"] for a in drains)
    assert steps.value() - before[1] == run
    assert lane_steps.value() - before[2] == SLOTS * run
    # no lane takes more decode tokens than the loop ran steps
    assert all(a["tokens"] - a["first_tokens"]
               <= a["lanes"] * a["steps_run"] for a in drains)


@pytest.mark.parametrize("layout,chunked", CASES)
def test_steps_run_is_the_early_exit(lm, layout, chunked):
    """Two lanes that both end on their fourth token stop the device's
    loop after three steps of the eight dispatched; a lane that lives
    through a segment makes it run them all."""
    loop = make_loop(lm, layout, chunked)
    _, sp = run_traced(loop, requests([6, 7], [4, 4]))
    [short] = [e["args"] for e in sp["serve/segment_drain"]]
    assert short["steps"] == STEPS and short["steps_run"] == 3
    assert short["lanes"] == 2 and short["first_tokens"] == 2
    _, sp = run_traced(loop, requests([6], [2 * STEPS + 2]))
    full = [e["args"] for e in sp["serve/segment_drain"]]
    assert [a["steps_run"] for a in full] == [STEPS, STEPS, 1]
    assert all(a["steps"] == STEPS for a in full)


@pytest.mark.parametrize("chunked", [False, True],
                         ids=["oneshot", "chunked"])
def test_released_lane_walks_no_page(lm, chunked):
    """The paged decode kernel walks ``ceil(cache_index / block)`` pages a
    lane, so a lane that holds no request has to enter a segment at
    length 0 whatever its last request left there; and the drained
    segment's ``pages`` is the pool's own count of what the live lanes
    held when it was dispatched."""
    from tpudist.models.serving import _index_leaves

    loop = make_loop(lm, "paged", chunked, decode_attention="flash")
    block = loop.kv_block_size
    segment, grow = loop._segment, loop.pool.grow
    entered, grown = [], []

    def spy_grow(slot, steps):
        grown.append(loop.pool.covered_pages(slot))
        return grow(slot, steps)

    def spy_segment(params, cache, tok, active, *rest):
        rec = {"active": np.asarray(active),
               "len_in": np.asarray(_index_leaves(cache)[0]),
               "host_pages": sum(grown)}
        grown.clear()
        out = segment(params, cache, tok, active, *rest)
        rec["len_out"] = np.asarray(_index_leaves(out[0])[0])
        entered.append(rec)
        return out

    loop._segment, loop.pool.grow = spy_segment, spy_grow
    walked = obs.counter("serve/decode_pages_walked")
    before = walked.value()
    # lane 0 finishes in the first segment; lane 1 decodes for three more,
    # and the third request takes the freed lane in the meantime
    _, sp = run_traced(loop, requests([20, 30, 9], [3, 3 * STEPS, 4]))
    stale = 0
    for rec in entered:
        idle = ~rec["active"]
        # inactive at entry: length 0 for the whole segment, and after it
        assert not rec["len_out"][idle].any()
        assert (rec["len_out"][~idle] >= rec["len_in"][~idle]).all()
        stale += int((rec["len_in"][idle] > 0).sum())
        device_pages = int((-(-rec["len_in"][~idle] // block)).sum())
        # the host counts a lane the device froze and it has not drained
        assert rec["host_pages"] >= device_pages
    assert stale, "no released lane with a stale length ever entered"
    assert any(rec["host_pages"] == int((-(-rec["len_in"][rec["active"]]
                                           // block)).sum()) > 0
               for rec in entered)
    drains = [e["args"] for e in sp["serve/segment_drain"]]
    assert drains and all(
        a["pages"] == entered[a["seq"]]["host_pages"] for a in drains)
    assert (walked.value() - before
            == sum(a["pages"] * a["steps_run"] for a in drains) > 0)


@pytest.mark.parametrize("layout,chunked", CASES)
def test_rows_computed_cover_the_live_rows(lm, layout, chunked):
    """``rows`` is what the paged kernel's arithmetic covers for the lanes
    live at a segment's dispatch (``walk_rows`` of each length: whole
    steps of the last tile's width), ``rows_live`` the lengths themselves;
    both counters tick ``x steps_run`` like the pages', and the dense
    layout counts none."""
    from tpudist.ops.flash_decode import paged_tile_pages, walk_rows

    loop = make_loop(lm, layout, chunked)
    computed = obs.counter("serve/decode_rows_computed")
    live = obs.counter("serve/decode_rows_live")
    before = computed.value(), live.value()
    _, sp = run_traced(loop, requests([5, 19, 30, 9], [6, 3, 2 * STEPS, 1]))
    drains = [e["args"] for e in sp["serve/segment_drain"]]
    assert drains and all("rows" in a and "rows_live" in a for a in drains)
    assert (computed.value() - before[0]
            == sum(a["rows"] * a["steps_run"] for a in drains))
    assert (live.value() - before[1]
            == sum(a["rows_live"] * a["steps_run"] for a in drains))
    if layout == "dense":
        assert not any(a["rows"] or a["rows_live"] for a in drains)
        return
    block = loop.kv_block_size
    per_tile = paged_tile_pages(block, loop.pool.max_blocks_per_slot)
    granule = walk_rows(1, block, per_tile)  # the last tile's width step
    assert walk_rows(granule + 1, block, per_tile) == 2 * granule
    for a in drains:
        assert a["rows"] >= a["rows_live"] and a["rows"] % granule == 0
        assert a["rows"] >= a["pages"] * block
        # less than a granule a lane is computed beyond its length
        assert a["rows"] - a["rows_live"] < SLOTS * granule
    assert any(a["rows_live"] > 0 for a in drains)


# (heads, K/V heads, embed, grid rows a lane): one K/V head; two of 128 that share a lane's
# grid row; three of 16, too narrow to share one (a row each)
GRID_MODELS = {"one_kv_head": (2, 1, 32, 1),
               "two_kv_heads_of_128": (2, 2, 256, 1),
               "three_narrow_heads": (3, 3, 48, 3)}


@pytest.mark.parametrize("model", GRID_MODELS)
def test_grid_rows_are_the_calls_grid(model):
    """``serve/decode_grid_rows`` is what the paged kernel's calls put on
    the grid: ``grid_rows`` of every ``serve/segment_drain`` is
    ``paged_grid_rows`` of the loop's shapes (the helper the call takes its
    ``grid=`` from, ``tests/test_paged_decode_walk.py``), and the counter
    ticks it ``x attention layers x steps_run``; a lane costs ONE row a call
    where its K/V heads share one."""
    from tpudist.ops.flash_decode import paged_grid_rows

    heads, kv_heads, embed, rows_a_lane = GRID_MODELS[model]
    layers = 2
    cfg = TransformerConfig(vocab_size=VOCAB, num_layers=layers,
                            num_heads=heads, num_kv_heads=kv_heads,
                            embed_dim=embed, max_seq_len=SEQ)
    params = TransformerLM(cfg).init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"]
    loop = make_loop((cfg, params), "paged", True)
    counter = obs.counter("serve/decode_grid_rows")
    lane_steps = obs.counter("serve/lane_steps")
    before = counter.value(), lane_steps.value()
    _, sp = run_traced(loop, requests([5, 19, 30, 9], [6, 3, 2 * STEPS, 1]))
    drains = [e["args"] for e in sp["serve/segment_drain"]]
    rows = paged_grid_rows(
        SLOTS, kv_heads, embed // heads, loop.kv_block_size,
        loop.pool.max_blocks_per_slot, itemsize=4)
    assert rows == rows_a_lane * SLOTS
    assert drains and all(a["grid_rows"] == rows for a in drains)
    ticked = counter.value() - before[0]
    assert ticked == sum(rows * layers * a["steps_run"] for a in drains) > 0
    assert ticked == rows_a_lane * layers * (lane_steps.value() - before[1])


def test_no_kernel_no_grid_rows(lm):
    """The dense layout and the paged layout's gather fallback call no paged
    kernel: the span says 0 and the counter stays."""
    counter = obs.counter("serve/decode_grid_rows")
    before = counter.value()
    for loop in (make_loop(lm, "dense", True),
                 make_loop(lm, "paged", True, decode_attention="dense")):
        _, sp = run_traced(loop, requests([5, 9], [6, 3]))
        assert all(e["args"]["grid_rows"] == 0
                   for e in sp["serve/segment_drain"])
    assert counter.value() == before


@pytest.mark.parametrize("layout,chunked", CASES)
def test_chunks_count_the_prompt(lm, layout, chunked):
    lengths = [1, CHUNK, CHUNK + 1, 3 * CHUNK + 5]
    done = make_loop(lm, layout, chunked).run(requests(lengths, [2] * 4))
    assert ({c.rid: c.timing.chunks for c in done}
            == {i: math.ceil(n / CHUNK) for i, n in enumerate(lengths)})


@pytest.mark.parametrize("layout,chunked", CASES)
def test_one_request_span_per_completion(lm, layout, chunked):
    loop = make_loop(lm, layout, chunked)
    for seed, n in ((0, 3), (1, 5)):  # the second run starts on a used loop
        done, sp = run_traced(
            loop, requests([7 + i for i in range(n)], [3] * n, seed))
        assert (sorted(e["args"]["rid"] for e in sp["serve/request"])
                == sorted(c.rid for c in done) == list(range(n)))
        for e in sp["serve/request"]:
            a, [c] = e["args"], [c for c in done if c.rid == e["args"]["rid"]]
            t = c.timing
            assert abs(e["ts"] * 1e-6 - t.enqueue) < EPS
            assert abs(e["dur"] * 1e-6 - (t.done - t.enqueue)) < EPS
            assert (a["reason"], a["tokens"], a["chunks"], a["prompt_len"]) \
                == (c.reason, len(c.tokens), t.chunks, len(c.prompt))
            assert a["slot"] in range(SLOTS)
            assert abs(a["first_token"] - (t.first_token - t.enqueue)) < EPS
            assert 0 <= a["admit"] <= a["prefill_done"] <= a["first_token"]
    # the phases of one request share its identifier
    own = {e["args"]["rid"] for name in (
        "serve/admit", "serve/admit_finish", "serve/prefill_chunk")
        for e in spans(name)}
    assert own <= set(range(5)) and (own or not chunked)


@pytest.mark.parametrize("layout,chunked", CASES)
def test_latency_starts_at_enqueue(lm, layout, chunked):
    """Both histograms run from ENQUEUE: the third request of two lanes
    waits for a lane, and its wait is in both."""
    loop = make_loop(lm, layout, chunked)
    ttft, lat = (obs.histogram("serve/ttft_s"),
                 obs.histogram("serve/request_latency"))
    t0, l0 = ttft.summary(), lat.summary()
    done = loop.run(requests([9, 9, 9], [12, 12, 4]))
    t1, l1 = ttft.summary(), lat.summary()
    assert t1["count"] - t0["count"] == l1["count"] - l0["count"] == 3
    per = [(c.timing.first_token - c.timing.enqueue,
            c.timing.done - c.timing.enqueue) for c in done]
    assert all(0 < first <= whole for first, whole in per)
    assert t1["sum"] - t0["sum"] == pytest.approx(sum(p[0] for p in per))
    assert l1["sum"] - l0["sum"] == pytest.approx(sum(p[1] for p in per))
    waited = next(c for c in done if c.rid == 2).timing
    assert waited.admit - waited.enqueue > 0.5 * (
        waited.first_token - waited.enqueue)


def test_unadmitted_completion_has_two_stamps(lm):
    loop = make_loop(lm, "dense", True, max_queue=1)
    done, sp = run_traced(loop, requests([5] * 5, [40] * 5))
    shed = [c for c in done if c.reason == "rejected"]
    assert shed and len(sp["serve/request"]) == len(done) == 5
    for c in shed:
        t = c.timing
        assert (t.admit, t.prefill_done, t.first_token) == (None,) * 3
        assert t.enqueue <= t.done and (t.chunks, t.tokens) == (0, 0)
    assert {e["args"]["slot"] for e in sp["serve/request"]
            if e["args"]["reason"] == "rejected"} == {None}


def test_wire_format_leaves_timing_out(lm):
    """``perf_counter`` means nothing in another process: the replica's
    completion record carries no stamp, and the router's own
    ``Completion`` has ``timing`` None."""
    from tpudist.runtime import wire
    from tpudist.runtime.router import _encode_completion

    [c] = make_loop(lm, "dense", True).run(requests([5], [3]))
    assert c.timing is not None
    doc = wire.decode_record(_encode_completion("r0", c),
                             expect="completion")
    assert set(doc) == {"key", "tokens", "reason", "replica"}
    assert doc["tokens"] == [int(t) for t in c.tokens]


def test_complete_records_a_span_from_two_stamps():
    tracer = obs.SpanTracer(max_events=2)
    tracer.complete("a", 1.0, 1.5, rid="x", n=3)
    [e] = tracer.events()
    assert (e["name"], e["ph"], e["ts"], e["dur"]) == ("a", "X", 1e6, 5e5)
    assert e["args"] == {"rid": "x", "n": 3}
    with tracer.span("b"):  # the ring and its overflow count are shared
        pass
    tracer.complete("c", 2.0, 2.0)
    assert [e["name"] for e in tracer.events()] == ["b", "c"]
    assert tracer.dropped == 1


def test_compile_names_the_open_span():
    reg = obs.MetricRegistry()
    obs.note_compile(0.25, registry=reg)
    with obs.span("outer"), obs.span("serve/admit"):
        obs.note_compile(0.5, registry=reg)
    assert reg.counter("xla/compiles").value() == 2
    assert reg.counter("xla/compiles~span=serve/admit").value() == 1
    assert "xla/compiles~span=outer" not in reg.metrics()
    ev = [e for e in obs.recorder.events() if e["kind"] == "xla_compile"]
    assert [e["span"] for e in ev[-2:]] == [None, "serve/admit"]
    with obs.span("a=b"):  # a name no label can hold is not a reason to
        obs.note_compile(0.1, registry=reg)  # fail a compile
    assert reg.counter("xla/compiles").value() == 3


def test_a_real_compile_lands_in_its_span():
    assert obs.install_compile_telemetry()
    name = f"test/compile_{time.monotonic_ns()}"
    unique = float(time.monotonic_ns() % 1_000_003)  # never cached
    with obs.span(name):
        jax.jit(lambda x: x * unique)(np.float32(2.0)).block_until_ready()
    assert obs.counter(f"xla/compiles~span={name}").value() >= 1
