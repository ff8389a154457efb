"""The readers of ``ServeLoop``'s own clock on a run bag and a span list
made by hand: each has a known answer, a request enqueued in the ramp and a
warm-up request are left out, and an empty window gives ``None``."""

import json
import types

import pytest

from benchmarks.layer_metrics import (
    _loop_spans as ls,
    decode_occupancy,
    flash_prefill_roofline,
    host_ms_per_segment,
    paged_decode_roofline,
    paged_decode_us_per_call,
    prefill_ms_per_chunk,
    ttft_p90_ms,
)
from benchmarks.roofline import bound, flash_prefill, paged_decode
from benchmarks.trace import reduce as tr

T0 = 1000.0     # perf_counter at the first enqueue
RAMP, WINDOW = 2.0, 10.0


def span(name, start, dur, **args):
    return {"name": name, "ph": "X", "ts": (T0 + start) * 1e6,
            "dur": dur * 1e6, "pid": 1, "tid": 1, "args": args}


def request(rid, at, admit, first, done, chunks, tokens=8):
    return span("serve/request", at, done, rid=rid, slot=0, prompt_len=100,
                chunks=chunks, tokens=tokens, reason="length", admit=admit,
                prefill_done=admit, first_token=first)


def bag(spans, rids=("a", "b", "c", "d", "ramp")):
    return {"cell": {"name": "hand", "traffic": {"ramp_s": RAMP,
                                                 "drain_limit_s": 30.0}},
            "stats": {"window_s": WINDOW}, "options": {"num_slots": 4},
            "events": [{"kind": "admit", "trace": r} for r in rids]
            + [{"kind": "segment", "trace": "a"}],
            "trace": {"by_op": {}}, "spans": spans}


SPANS = [
    request("warm0", -50.0, 0.1, 0.2, 1.0, 1),    # no admit event: warm-up
    request("ramp", 0.0, 0.001, 9.0, 9.5, 1),     # sets the clock, in the ramp
    request("a", 2.5, 0.001, 0.401, 1.0, 2),      # 200 ms a chunk
    request("b", 3.0, 0.002, 1.202, 2.0, 3),      # 400 ms a chunk
    request("c", 4.0, 0.000, 0.600, 1.0, 1),      # 600 ms a chunk
    request("d", 11.5, 0.001, None, 3.0, 0, tokens=0),   # got no token
    request("late", 12.5, 0.001, 0.1, 0.2, 1),    # after the window
    # three segments drained inside the window, one before, one after
    span("serve/segment_fetch", 1.0, 0.5, seq=0),
    span("serve/segment_drain", 1.5, 0.01, seq=0, steps=8, steps_run=8,
         lanes=4, tokens=32, first_tokens=0),
    span("serve/segment_fetch", 1.9, 0.6, seq=1),          # 0.5 s inside
    span("serve/segment_drain", 2.5, 0.01, seq=1, steps=8, steps_run=8,
         lanes=3, tokens=20, first_tokens=2),
    span("serve/segment_fetch", 3.0, 3.0, seq=2),
    span("serve/segment_drain", 6.0, 0.02, seq=2, steps=8, steps_run=4,
         lanes=2, tokens=9, first_tokens=1),
    span("serve/segment_fetch", 7.0, 4.5, seq=3),
    span("serve/segment_drain", 11.5, 0.02, seq=3, steps=8, steps_run=8,
         lanes=1, tokens=8, first_tokens=0),
    span("serve/segment_fetch", 11.9, 1.0, seq=4),         # 0.1 s inside
    span("serve/segment_drain", 12.9, 0.01, seq=4, steps=8, steps_run=8,
         lanes=4, tokens=32, first_tokens=0),
    span("serve/admit_poll", 6.1, 0.4),
    span("serve/admit", 6.2, 0.1, slot=0, rid="c"),        # inside the poll
]


def test_the_window_leaves_out_ramp_and_warm_up():
    w = ls.window(bag(SPANS))
    assert (w.lo, w.hi) == pytest.approx((T0 + RAMP, T0 + RAMP + WINDOW))
    assert sorted(a["rid"] for a in w.requests) == ["a", "b", "c", "d"]
    assert [a["seq"] for a in ls.drained(w)] == [1, 2, 3]


def test_ttft_p90_counts_a_request_without_a_token_as_slowest():
    # four requests: the 90th percentile is the fourth, which got none
    assert ttft_p90_ms.read(bag(SPANS)) == pytest.approx(
        1e3 * (WINDOW + 30.0))
    finished = [s for s in SPANS if s["args"].get("rid") != "d"]
    assert ttft_p90_ms.read(bag(finished)) == pytest.approx(1202.0)


def test_prefill_ms_per_chunk_is_the_mean_over_requests():
    assert prefill_ms_per_chunk.read(bag(SPANS)) == pytest.approx(400.0)


def test_decode_occupancy_leaves_first_tokens_out():
    # (18 + 8 + 8) decode tokens over 4 lanes x (8 + 4 + 8) steps
    assert decode_occupancy.read(bag(SPANS)) == pytest.approx(
        100.0 * 34 / 80)


def test_host_ms_per_segment_is_the_wall_less_the_fetches(capsys):
    # fetches inside the window: 0.5 + 3.0 + 4.5 + 0.1 of 10 s, 3 segments
    assert host_ms_per_segment.read(bag(SPANS)) == pytest.approx(
        1e3 * (10.0 - 8.1) / 3)
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["segments"] == 3
    by_span = said["seconds_by_span"]
    assert by_span["serve/admit_poll"] == pytest.approx(0.4)
    assert by_span["serve/admit"] == pytest.approx(0.1)
    assert by_span["serve/segment_drain"] == pytest.approx(0.05)
    # the admit inside the poll is not counted twice in the cover
    assert said["covered_share"] == pytest.approx((8.1 + 0.05 + 0.4) / 10.0)


def test_paged_decode_is_found_by_its_name(monkeypatch):
    kernel = ("%paged_flash_decode.{} = bf16[24,22,128]{{2,1,0}} "
              "custom-call(s32[24,66]{{1,0}} %a), "
              'custom_call_target="tpu_custom_call"')
    ops = [tr.Event(kernel.format(i), i, i + 250e-6) for i in range(4)]
    ops += [tr.Event("%flash_decode.3 = bf16[1] custom-call()", 9.0, 9.5),
            tr.Event("%fusion.paged_flash_decode = f32[2] fusion()", 9, 10)]
    monkeypatch.setattr(tr, "find_xplane", lambda d: "hand.xplane.pb")
    monkeypatch.setattr(tr, "load", lambda p: {
        "devices": {0: {"ops": ops, "modules": []}}, "host": []})
    assert paged_decode_us_per_call.read(bag(SPANS)) == pytest.approx(250.0)
    monkeypatch.setattr(tr, "load", lambda p: {
        "devices": {0: {"ops": ops[4:], "modules": []}}, "host": []})
    assert paged_decode_us_per_call.read(bag(SPANS)) is None


def _pallas(name: str, i: int) -> str:
    return (f"%{name}.{i} = bf16[24,22,128]{{2,1,0}} custom-call("
            's32[24,66]{1,0} %a), custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("cut", [False, True])
def test_the_serve_rooflines_count_their_kernels_events(monkeypatch, cut):
    """Each event of the kernel is one call: a run of the program that the
    trace's edge cut (here: only its last two of eight calls are left)
    brings the events it holds and is not counted as a whole run."""
    layers, peaks = 4, {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    ops, modules = [], []
    for r in range(-1 if cut else 0, 3):
        first = 6 if r < 0 else 0       # the cut run: two calls are left
        modules += [tr.Event("jit__segment_impl(7)", r + first * 0.01,
                             r + 0.09),
                    tr.Event("jit__prefill_chunk_impl(3)", r + 0.5, r + 0.6)]
        for i in range(first, 2 * layers):          # two steps a segment
            ops.append(tr.Event(_pallas("paged_flash_decode", i),
                                r + i * 0.01, r + i * 0.01 + 40e-6))
        for i in range(layers):
            ops.append(tr.Event(_pallas("flash_fwd", i), r + 0.5 + i * 0.01,
                                r + 0.5 + i * 0.01 + 300e-6))
        ops.append(tr.Event(_pallas("sparse_gqa_prefill", 1), r + 0.58,
                            r + 0.59))
    monkeypatch.setattr(tr, "find_xplane", lambda d: "hand.xplane.pb")
    monkeypatch.setattr(tr, "load", lambda p: {
        "devices": {0: {"ops": ops, "modules": modules}}, "host": []})
    run = {"cell": {"name": "hand"}, "peaks": peaks, "trace": {"by_op": {}},
           "options": {"steps_per_sync": 2},
           "dims": types.SimpleNamespace(layers=layers, heads=22,
                                         kv_heads=1, head_dim=128),
           "events": [
               {"kind": "admit", "trace": "a", "prompt_len": 1000},
               {"kind": "segment", "trace": "a", "seq": 0, "steps": 2,
                "tokens": 10},
               {"kind": "prefill_chunk", "off": 512, "width": 512}]}
    # one lane: 1010 and 1011 tokens of context at the two steps
    assert paged_decode_roofline.read(run) == pytest.approx(bound.share(
        paged_decode.flops(1010.5, 22, 128),
        paged_decode.bytes_moved(1010.5, 1, 22, 1, 128), 40e-6, peaks))
    assert flash_prefill_roofline.read(run) == pytest.approx(bound.share(
        flash_prefill.flops(512, 512, 22, 128),
        flash_prefill.bytes_moved(512, 512, 22, 1, 128), 300e-6, peaks))
    monkeypatch.setattr(tr, "load", lambda p: {
        "devices": {0: {"ops": ops[-1:], "modules": modules}}, "host": []})
    assert paged_decode_roofline.read(run) is None
    assert flash_prefill_roofline.read(run) is None


@pytest.mark.parametrize("reader", [
    ttft_p90_ms, prefill_ms_per_chunk, decode_occupancy,
    host_ms_per_segment, paged_decode_us_per_call])
def test_an_empty_window_gives_none(reader, monkeypatch):
    monkeypatch.setattr(tr, "find_xplane", lambda d: None)
    assert reader.read(bag([])) is None           # a program without spans
    assert reader.read(bag(SPANS, rids=())) is None   # no traced request
    only_ramp = [s for s in SPANS if s["args"].get("rid") in ("ramp", None)
                 and s["name"] == "serve/request"]
    assert reader.read(bag(only_ramp)) is None    # nothing inside the window
