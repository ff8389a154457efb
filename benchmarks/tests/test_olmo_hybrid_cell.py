"""The Olmo-Hybrid cell: its roofline arithmetic by hand, its readers on a
trace and a span list made by hand (and silent on a program without the
kernel, the scope or the span's argument: the parent), its configuration
against the catalog row, its traffic against ISSUE 41's laws, its ``--tiny``
rehearsal, and ``correct`` false for the three controls (float8, no decay,
``beta`` not doubled) and for a planted fault (a served model whose linear
layers take the step's tokens of lanes that do not own them).  CPU, toy
sizes."""

import json

import pytest

from benchmarks.harness import common, serve_olmo_hybrid as runner
from benchmarks.layer_metrics import (_delta_spans, delta_chunk_ms_per_call,
                                      delta_chunk_roofline,
                                      delta_step_roofline,
                                      delta_step_us_per_call,
                                      state_bytes_share, step_attn_ms,
                                      step_linear_ms, step_other_ms)
from benchmarks.roofline import bound, delta_rule
from benchmarks.tests import test_joined_readers as joined
from benchmarks.tests.test_deepseek_cell import _compared, _trace
from benchmarks.trace import reduce as tr

PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
CONFIG = common.load_json(
    common.ROOT / "benchmarks/configs/olmo-hybrid-7b.json")
DIMS = runner.model_dims(CONFIG)
CELL = "olmoh7b_doc_mixed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


# -- the arithmetic -----------------------------------------------------------

def test_delta_step_counts_by_hand():
    # one lane, one head of 96 x 192: decay 1, against the key 2, the
    # outer product 2, against the query 2 an element
    assert delta_rule.flops(1, 1, 96, 192) == 7 * 96 * 192 == 129_024
    # the float32 state in and out; q, k (96 each), v, o (192 each), alpha
    # and beta in float32
    assert delta_rule.step_bytes(1, 1, 96, 192) == (
        2 * 96 * 192 * 4 + (2 * 96 + 2 * 192 + 2) * 4) == 149_768
    # the cell's step at 16 lanes: 70.8 MB of state, 1.1 MB of vectors
    assert delta_rule.step_bytes(16, 30, 96, 192) == 16 * 30 * 149_768


def test_delta_chunk_counts_by_hand():
    assert delta_rule.flops(512, 30, 96, 192) == 512 * 30 * 129_024
    # 512 tokens' q, k, v, o and gates; ONE state in and out
    assert delta_rule.chunk_bytes(512, 30, 96, 192) == (
        512 * 30 * 578 * 4 + 2 * 30 * 96 * 192 * 4)


def test_both_forms_are_bound_by_their_bytes_on_v5e():
    for f, b in ((delta_rule.flops(16, 30, 96, 192),
                  delta_rule.step_bytes(16, 30, 96, 192)),
                 (delta_rule.flops(512, 30, 96, 192),
                  delta_rule.chunk_bytes(512, 30, 96, 192))):
        assert (f / PEAKS["flops_bf16"]) / (b / PEAKS["hbm_bytes_per_s"]) < 1


# -- the configuration and the traffic ----------------------------------------

def test_the_config_holds_the_published_widths_and_the_cut():
    assert (DIMS.embed, DIMS.heads, DIMS.head_dim, DIMS.ff) == (
        3840, 30, 128, 11008)
    assert (DIMS.lin_heads, DIMS.key_dim, DIMS.value_dim, DIMS.conv) == (
        30, 96, 192, 4)
    assert DIMS.neg_eigval and DIMS.vocab == 100352
    assert DIMS.layers == 16 and DIMS.linear == (True, True, True, False) * 4
    assert runner.count_params(DIMS) == CONFIG["sizes"]["parameters"]
    pieces = CONFIG["sizes"]["parameters_by_piece"]
    assert pieces["linear_mixer_of_one_layer"] == (
        3840 * 11520 + 3840 * 5760 + 5760 * 3840 + 3840 * 60 + 4 * 11520
        + 60 + 192)
    assert pieces["full_mixer_of_one_layer"] == 4 * 3840 * 3840 + 2 * 3840
    assert pieces["mlp_of_one_layer"] == 3 * 3840 * 11008
    assert pieces["embedding_and_head"] == 2 * 100352 * 3840
    assert runner.state_bytes_per_lane(DIMS) == 12 * (
        30 * 96 * 192 * 4 + 3 * 11520 * 2) == CONFIG["sizes"][
        "state_bytes_per_lane"]
    assert CONFIG["sizes"]["kv_bytes_per_token"] == 4 * 2 * 30 * 128 * 2


def test_every_published_number_is_the_catalogs_but_the_cut():
    row = next(r for r in map(json.loads, open(CATALOG))
               if r["name"] == "Olmo-Hybrid-7B")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            continue
        assert CONFIG[key] == value, key
    assert sorted(CONFIG["reduced"]) == ["layer_types", "num_hidden_layers"]
    assert CONFIG["published"]["num_hidden_layers"] == row["config"][
        "num_hidden_layers"] == 32
    # whole periods of the published pattern
    assert CONFIG["layer_types"] == row["config"]["layer_types"][:16]
    bench = common.load_json(common.ROOT / "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG["name"])
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])


def test_the_traffic_is_issue_41s():
    mix = common.load_json(common.ROOT / "benchmarks/traffic/doc_mixed.json")
    opts = CONFIG["program"]["options"]
    assert mix["kind"] == "closed_loop"
    assert mix["clients"] == 2 * opts["num_slots"]
    assert mix["lengths"]["prompt"] == {
        "dist": "lognormal", "median": 4096, "sigma": 0.5, "min": 1024,
        "max": 8192}
    assert mix["lengths"]["output"] == {"dist": "uniform", "min": 512,
                                        "max": 1024}
    assert mix["max_total"] == CONFIG["program"]["max_seq_len"] == 9216
    assert mix["ramp_s"] % 4 == 0
    assert opts["max_prefill_lanes"] == 2 and opts["num_slots"] % 4 == 0
    assert opts["num_slots"] <= 16
    for key in ("ramp_why", "clients_why", "max_completions_per_s_why"):
        assert mix[key]
    for key in ("num_slots", "kv_num_blocks"):
        assert any(key in line for line in CONFIG["program"]["why"])


# -- the readers ----------------------------------------------------------------

def _kernel(name, n):
    return (f"%{name}.{n} = (f32[16,1,5760]{{2,1,0}}, f32[16,96,5760]"
            f"{{2,1,0}}) custom-call(f32[16,3,96,10]{{3,2,1,0}} %a), "
            'custom_call_target="tpu_custom_call"')


def _olmo_bag(**over):
    return {"cell": {"name": "toy", "traffic": {"ramp_s": 1.0}},
            "dims": DIMS, "peaks": PEAKS, "trace": {"busy_s": 1.0},
            "options": CONFIG["program"]["options"], "events": [],
            "stats": {"window_s": 1.0}, "spans": [], **over}


def test_delta_step_is_found_by_its_name(monkeypatch):
    ops = [tr.Event(_kernel("delta_step", i), i, i + 120e-6)
           for i in range(6)]
    ops += [tr.Event(_kernel("paged_flash_decode", 9), 9, 9.5),
            tr.Event("%fusion.delta_step = f32[2] fusion()", 9, 10)]
    _trace(monkeypatch, ops)
    assert delta_step_us_per_call.read(_olmo_bag()) == pytest.approx(120.0)
    _trace(monkeypatch, ops[6:])        # a program without the kernel
    assert delta_step_us_per_call.read(_olmo_bag()) is None


STEP_OPS = {"fusion.1": ("linear_attn", 0.5),
            "delta_step.4": ("delta_step", 0.125),
            "fusion.5": ("attn/proj", 0.25),
            "paged_flash_decode.2": ("attn/core", 1.0),
            "fusion.2": ("mlp/dense", 3.0), "fusion.3": ("head", 0.5),
            "copy.9": (None, 0.25)}
LANES = {11: 16, 12: 14, 13: 9}


def _joined_bag(state=True):
    out = joined.make_bag(step=STEP_OPS,
                          texts={"delta_step.4": _kernel("delta_step", 4)})
    out["dims"] = DIMS
    if state:
        for span in out["spans"]:
            seq = span["args"]["seq"]
            span["args"]["state_lanes"] = LANES.get(seq, 3)
    return out


def test_delta_step_roofline_is_against_its_own_segments_lanes():
    least = spent = 0.0
    for seq in joined.WHOLE:
        steps = joined.RUNS[seq][2]
        least += steps * bound.least_seconds(
            delta_rule.flops(LANES[seq], 30, 96, 192),
            delta_rule.step_bytes(LANES[seq], 30, 96, 192), PEAKS)
        spent += steps * 0.125e-3
    assert delta_step_roofline.read(_joined_bag()) == pytest.approx(
        100.0 * least / spent)
    # a program whose drains carry no state_lanes (the parent)
    assert delta_step_roofline.read(_joined_bag(state=False)) is None


def test_step_linear_ms_and_the_parts_still_sum_to_the_step():
    run = _joined_bag()
    assert step_linear_ms.read(run) == pytest.approx(0.625)
    assert step_attn_ms.read(run) == pytest.approx(1.25)
    assert step_other_ms.read(run) == pytest.approx(
        joined.STEP_MS - 0.625 - 1.25 - 3.0 - 0.5)
    # a program without the scopes (any other cell's): nothing to read
    assert step_linear_ms.read(joined.make_bag()) is None


def _chunk_bag(scopes=True):
    """Four runs of the prefill-chunk program and then one of the segment
    (the trace's first and last module events may be cut: left out), each
    chunk with twelve layers' recurrence under a while (the scan over
    sub-blocks), 2 ms a layer, and other work."""
    modules, ops = [], []
    for i in range(4):
        start = i * 0.1
        modules.append(("jit__prefill_chunk_impl(3)", start, start + 0.09))
        t = start
        for layer in range(12):
            ops.append((f"%fusion.{layer} = f32[8] fusion()", t, t + 1e-3))
            # the scan: its body's time is the while's children's
            ops.append((f"%while.{layer} = (f32[8]) while()", t + 1e-3,
                        t + 3e-3))
            ops.append((f"%fusion.{100 + layer} = f32[8] fusion()",
                        t + 1e-3, t + 2.5e-3))
            ops.append((f"%fusion.{200 + layer} = f32[8] fusion()",
                        t + 3e-3, t + 6e-3))
            t += 6e-3
    modules.append(("jit__segment_impl(7)", 0.4, 0.5))
    smap = {}
    for layer in range(12):
        smap[f"fusion.{layer}"] = "linear_attn"
        smap[f"while.{layer}"] = "delta_chunk"
        smap[f"fusion.{100 + layer}"] = "delta_chunk"
        smap[f"fusion.{200 + layer}"] = "mlp/dense"
    return {"cell": {"name": "toy"}, "dims": DIMS, "peaks": PEAKS,
            "options": CONFIG["program"]["options"],
            "trace_events": {"modules": modules, "ops": ops, "host": []},
            "scope_map": {"_prefill_chunk_impl": smap if scopes else {
                k: v for k, v in smap.items() if v != "delta_chunk"}}}


def test_delta_chunk_is_read_by_its_scope_inside_whole_chunk_runs():
    run = _chunk_bag()
    # the three whole runs, twelve layers each, 2 ms a layer (the while
    # and what runs inside it, once)
    assert _delta_spans.chunk_calls(run) == (36, pytest.approx(72e-3))
    assert delta_chunk_ms_per_call.read(run) == pytest.approx(2.0)
    want = bound.share(delta_rule.flops(512, 30, 96, 192),
                       delta_rule.chunk_bytes(512, 30, 96, 192), 2e-3,
                       PEAKS)
    assert delta_chunk_roofline.read(run) == pytest.approx(want)
    assert 0 < want < 100
    # a program without the scope (the parent), and no trace at all
    assert delta_chunk_ms_per_call.read(_chunk_bag(scopes=False)) is None
    assert delta_chunk_roofline.read(_chunk_bag(scopes=False)) is None
    assert delta_chunk_ms_per_call.read(_olmo_bag(trace=None)) is None


def test_state_bytes_share_from_the_drains():
    lane, block = runner.state_bytes_per_lane(DIMS), 128
    spans = [
        {"name": "serve/request", "ts": 0.0, "dur": 5e6,
         "args": {"rid": "a"}},
        {"name": "serve/segment_drain", "ts": 1.2e6, "dur": 100.0,
         "args": {"seq": 1, "pages": 400, "state_lanes": 12,
                  "state_bytes": 12 * lane}},
        {"name": "serve/segment_drain", "ts": 1.6e6, "dur": 100.0,
         "args": {"seq": 2, "pages": 500, "state_lanes": 14,
                  "state_bytes": 14 * lane}},
        # outside the window
        {"name": "serve/segment_drain", "ts": 0.2e6, "dur": 100.0,
         "args": {"seq": 0, "pages": 1, "state_lanes": 1,
                  "state_bytes": lane}}]
    run = _olmo_bag(spans=spans, events=[
        {"kind": "admit", "trace": "a", "prompt_len": 10}])
    state = 26 * lane
    pages = 900 * block * 61440
    assert state_bytes_share.read(run) == pytest.approx(
        100.0 * state / (state + pages))
    for span in spans:      # a program whose drains carry no state_bytes
        span["args"].pop("state_bytes", None)
    assert state_bytes_share.read(run) is None


# -- the rehearsal and ``correct`` ----------------------------------------------

def test_tiny_rehearsal_is_correct_and_reads_the_state(run_tiny):
    from tpudist import obs

    obs.tracer.clear()
    out = run_tiny(CELL, seconds=4.0, trace=1)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert {"state_bytes_share", "decode_occupancy", "kv_blocks_peak_share",
            "compile_s"} <= set(out["rehearsed"])
    drains = [e["args"] for e in obs.tracer.events()
              if e["name"] == "serve/segment_drain"]
    assert drains and all(0 <= a["state_lanes"] <= 4 for a in drains)
    assert max(a["state_lanes"] for a in drains) >= 2


def test_the_three_controls_are_not_correct(run_tiny, monkeypatch):
    seen = {}
    real = runner.reference_gaps

    def all_of_them(params, dims, positions, sample, controls=()):
        seen.update(real(params, dims, positions, sample,
                         controls=runner.CONTROLS))
        return {"program": seen["program"]}

    monkeypatch.setattr(runner, "reference_gaps", all_of_them)
    out = run_tiny(CELL, seconds=4.0)
    rows = _compared(out)
    assert out["correct"] is True
    for name in runner.CONTROLS:
        assert (seen[name]["worst_gap"] > rows["worst_logit_gap"]["limit"]
                or seen[name]["mean_gap"] > rows["mean_logit_gap"]["limit"])
        assert seen[name]["mean_gap"] > 3 * rows["mean_logit_gap"]["value"]


def test_a_state_moved_by_lanes_that_do_not_own_the_step_is_not_correct(
        run_tiny, monkeypatch):
    """The planted fault: the segment stops telling the model which lanes
    own the step, so a frozen or empty lane's pad token moves its state
    (and the padded rows of a last chunk move a prompt's)."""
    from tpudist.models import transformer

    real = transformer.LinearAttention.__call__

    def every_token_counts(self, x, *, valid=None):
        return real(self, x, valid=None)

    monkeypatch.setattr(transformer.LinearAttention, "__call__",
                        every_token_counts)
    out = run_tiny(CELL, seconds=4.0)
    assert out["correct"] is False
    rows = _compared(out)
    assert (rows["mean_logit_gap"]["value"]
            > rows["mean_logit_gap"]["limit"])
