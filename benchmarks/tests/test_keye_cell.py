"""The Keye-VL 2.0 cell: its roofline arithmetic by hand, its readers on a
trace and a span list made by hand, its configuration against the catalog
row, its traffic against ISSUE 34's laws, its ``--tiny`` rehearsal, and
``correct`` false for the three controls (float8, the selection skipped, the
wrong rows) and for a planted fault (a served model whose indexer keeps
every row).  CPU, toy sizes."""

import json

import pytest

from benchmarks.harness import common, serve_keye as runner
from benchmarks.layer_metrics import (
    index_scores_us_per_call,
    index_selected_share,
    sparse_chunk_ms,
)
from benchmarks.roofline import index_scores, paged_decode, sparse_attend
from benchmarks.tests.test_deepseek_cell import _compared, _trace
from benchmarks.tests.test_layer_readers import SPANS, bag
from benchmarks.trace import reduce as tr

PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
CONFIG = common.load_json(
    common.ROOT / "benchmarks/configs/keye-vl-2.0-30b-a3b.json")
DIMS = runner.model_dims(CONFIG)
CELL = "keye2_longdoc_mixed"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


# -- the arithmetic -----------------------------------------------------------

def test_index_scores_one_lane():
    # 1000 rows, 16 heads of 64: the products and the weighted sum
    assert index_scores.flops(1000, 16, 64) == 2 * 16 * 65 * 1000
    # a key of 64 bf16 in and a float32 score out a row; the lane's
    # queries (bf16) and head weights (float32)
    assert index_scores.bytes_moved(1000, 1, 16, 64) == (
        1000 * (128 + 4) + 16 * (128 + 4))


def test_index_scores_are_bound_by_their_bytes_on_v5e():
    f = index_scores.flops(220_000, 16, 64)
    b = index_scores.bytes_moved(220_000, 12, 16, 64)
    # 16 FLOP a byte of key against the ridge of 240
    assert (f / PEAKS["flops_bf16"]) / (b / PEAKS["hbm_bytes_per_s"]) < 0.1


def test_sparse_attend_counts_the_chosen_rows_of_both_pools_once():
    assert sparse_attend.flops(2048, 32, 128) == paged_decode.flops(
        2048, 32, 128) == 4 * 32 * 128 * 2048
    # a K and a V row of 4 x 128 bf16 (2 KB) and a row id a chosen row;
    # q and o of 32 x 128 bf16
    assert sparse_attend.bytes_moved(2048, 1, 32, 4, 128) == (
        2048 * (2048 + 4) + 2 * 32 * 128 * 2)


# -- the configuration and the traffic ----------------------------------------

def test_the_config_holds_the_published_widths_and_the_cut():
    assert (DIMS.embed, DIMS.heads, DIMS.kv_heads, DIMS.head_dim) == (
        2048, 32, 4, 128)
    assert (DIMS.index_heads, DIMS.index_dim, DIMS.index_topk) == (
        16, 64, 2048)
    assert (DIMS.expert_ff, DIMS.experts, DIMS.top_k, DIMS.held) == (
        768, 128, 8, (0, 16))
    assert (DIMS.layers, DIMS.vocab, DIMS.rope_theta) == (8, 151936, 1e7)
    assert runner.count_params(DIMS) == CONFIG["sizes"]["parameters"]
    assert CONFIG["sizes"]["parameters"] == 1_397_527_552
    pieces = CONFIG["sizes"]["parameters_by_piece"]
    assert pieces["indexer_of_one_layer"] == (
        2048 * 1024 + 2048 * 64 + 2048 * 16 + 128) == 2_261_120
    assert pieces["attention_of_one_layer"] == 18_874_368 + 256
    assert 8 * sum(v for k, v in pieces.items()
                   if k.endswith("one_layer")) + pieces[
        "embedding_and_head"] + 2048 == 1_397_527_552
    assert CONFIG["reduced"] == ["num_hidden_layers", "num_experts"]
    assert CONFIG["published"] == {"num_hidden_layers": 48,
                                   "num_experts": 128}
    tiny = runner.model_dims(CONFIG, tiny=True)
    # the rehearsal selects: far fewer rows kept than a context holds
    assert tiny.index_topk * 8 <= CONFIG["tiny"]["max_seq_len"]


def test_every_catalog_key_is_in_the_file_unchanged_but_the_cut():
    try:
        rows = [json.loads(ln) for ln in open(CATALOG)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in rows if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key
    bench = common.load_json(common.ROOT / "BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "keye-vl-2.0-30b-a3b")
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == CONFIG["reduced"]


def test_the_program_takes_the_published_block():
    import jax.numpy as jnp

    cfg = runner.transformer_config(DIMS, 32768, jnp.bfloat16)
    assert cfg.head_dim == 128 and cfg.attn_dim == 4096 and cfg.qk_norm
    assert (cfg.index_heads, cfg.index_head_dim, cfg.index_topk) == (
        16, 64, 2048)
    assert cfg.index_cache_width == 128 and cfg.windows == (None,) * 8
    assert cfg.moe.held == (0, 16) and cfg.moe.scoring == "softmax"
    assert all(cfg.is_expert_layer(i) for i in range(8))
    opts = CONFIG["program"]["options"]
    assert opts["prefill_chunk"] == cfg.index_topk
    assert opts["num_slots"] % 4 == 0


def test_the_traffic_is_the_issues():
    cell = common.load_cell(CELL)
    mix = cell["traffic"]
    assert cell["chips"] == 1
    assert (mix["kind"], mix["clients"], mix["max_total"]) == (
        "closed_loop", 24, 30720)
    assert mix["lengths"]["prompt"] == {"dist": "uniform", "min": 8192,
                                        "max": 28672}
    assert mix["lengths"]["output"] == {"dist": "uniform", "min": 1024,
                                        "max": 2048}
    agent = common.load_json(common.BENCH / "traffic/agent_mixed.json")
    assert all(mix[k] == agent[k] for k in ("drain_limit_s", "trace_s"))
    assert mix["ramp_s"] % 4 == 0 and mix["ramp_s"] <= 64
    assert all(mix[f"{k}_why"] for k in (
        "ramp", "order_seed", "max_completions_per_s"))


# -- the readers ----------------------------------------------------------------

# (rows_scored, rows_selected) of the hand-made segments, by seq
ROWS = {0: (9, 9), 1: (30000, 6000), 2: (20000, 4000), 3: (45000, 6144),
        4: (9, 9)}
INDEX_SPANS = [
    dict(s, args=dict(s["args"], rows_scored=ROWS[s["args"]["seq"]][0],
                      rows_selected=ROWS[s["args"]["seq"]][1]))
    if s["name"] == "serve/segment_drain" else s for s in SPANS]


def index_bag(spans=INDEX_SPANS, **kw):
    out = bag(spans, **kw)
    out["dims"], out["peaks"] = DIMS, PEAKS
    return out


# segments 1, 2, 3 drain inside the window: 8 + 4 + 8 steps, 3, 2, 1 lanes
SCORED = (30000 * 8 + 20000 * 4 + 45000 * 8) / 20
CHOSEN = (6000 * 8 + 4000 * 4 + 6144 * 8) / 20


def test_selected_share_weighs_segments_by_their_steps():
    assert index_selected_share.read(index_bag()) == pytest.approx(
        100.0 * CHOSEN / SCORED)
    assert index_selected_share.read(index_bag(SPANS)) is None


def _kernel(name, operands, i):
    args = ", ".join(f"s32[8]{{0}} %a{j}" for j in range(operands))
    return (f"%{name}.{i} = bf16[16,8,128]{{2,1,0}} custom-call({args}), "
            'custom_call_target="tpu_custom_call"')


def _segment_ops(t0):
    """One layer of one step inside a segment run: the scores, the
    selection, the attention kernel."""
    return [
        tr.Event(_kernel("paged_index_scores", 4, int(t0)), t0,
                 t0 + 300e-6),
        tr.Event("%fusion.9 = s32[4,2048]{1,0} fusion()", t0 + 310e-6,
                 t0 + 340e-6),
        tr.Event(_kernel("sparse_gqa_attend", 4, int(t0)), t0 + 450e-6,
                 t0 + 650e-6)]


def test_the_scores_kernel_is_timed_in_the_segment_only(monkeypatch):
    ops = (_segment_ops(1.0) + _segment_ops(2.0)
           + [tr.Event(_kernel("paged_index_scores", 4, 5), 5.0, 5.0009)])
    modules = [tr.Event("jit__segment_impl(7)", 0.9, 3.0),
               tr.Event("jit__prefill_chunk_impl(3)", 4.9, 6.0)]
    _trace(monkeypatch, ops, modules)
    run = index_bag()
    # the chunk's call of the same kernel (900 us) does not count
    assert index_scores_us_per_call.read(run) == pytest.approx(300.0)
    _trace(monkeypatch, ops[6:], modules)
    assert index_scores_us_per_call.read(run) is None


def test_sparse_chunk_ms_is_the_chunk_programs_sparse_runs(monkeypatch):
    mark = ("%sparse_gqa_prefill.3 = bf16[32,2048,128]{2,1,0} custom-call("
            's32[1]{0} %a), custom_call_target="tpu_custom_call"')
    dense = ("%flash_fwd.3 = bf16[32,2048,128]{2,1,0} custom-call("
             's32[1]{0} %a), custom_call_target="tpu_custom_call"')
    ops = [tr.Event(mark, 1.05, 1.06), tr.Event(mark, 1.15, 1.16),
           tr.Event(dense, 2.05, 2.06), tr.Event(mark, 3.05, 3.06)]
    modules = [tr.Event("jit__prefill_chunk_impl(3)", 1.0, 1.2),
               tr.Event("jit__prefill_chunk_impl(3)", 2.0, 2.1),
               tr.Event("jit__prefill_chunk_impl(3)", 3.0, 3.3),
               tr.Event("jit__segment_impl(7)", 4.0, 4.4)]
    _trace(monkeypatch, ops, modules)
    # the first and the third run held the sparse kernel: 200 and 300 ms
    assert sparse_chunk_ms.read(index_bag()) == pytest.approx(250.0)
    _trace(monkeypatch, ops[2:3], modules)
    assert sparse_chunk_ms.read(index_bag()) is None


@pytest.mark.parametrize("reader", [
    index_scores_us_per_call, index_selected_share, sparse_chunk_ms])
def test_an_empty_window_or_a_program_without_the_fields_gives_none(
        reader, monkeypatch):
    monkeypatch.setattr(tr, "find_xplane", lambda d: None)
    assert reader.read(index_bag([])) is None
    assert reader.read(index_bag(INDEX_SPANS, rids=())) is None
    _trace(monkeypatch, _segment_ops(1.0),
           [tr.Event("jit__segment_impl(7)", 0.9, 3.0)])
    if reader is index_selected_share:
        # the parent's spans: no rows_scored, no rows_selected
        assert reader.read(index_bag(SPANS)) is None


# -- the rehearsal and ``correct`` ----------------------------------------------

def test_tiny_rehearsal_is_correct_and_reads_the_selection(run_tiny):
    from tpudist import obs

    obs.tracer.clear()
    out = run_tiny(CELL, seconds=4.0, trace=1)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert {"index_selected_share", "decode_occupancy",
            "kv_blocks_peak_share", "compile_s"} <= set(out["rehearsed"])
    # lanes that hold no request route one common token: the expert
    # counters would read that, not the load (PERF.md section 4)
    assert "expert_tokens_per_step" not in out["rehearsed"]
    drains = [e["args"] for e in obs.tracer.events()
              if e["name"] == "serve/segment_drain"]
    assert any(a["rows_selected"] < a["rows_scored"] for a in drains)
    chunks = [e["args"] for e in obs.tracer.events()
              if e["name"] == "serve/prefill_chunk"]
    # a prompt's first chunk is the dense program, every later one sparse
    assert chunks and all(a["sparse"] == (a["off"] > 0) for a in chunks)
    assert {a["sparse"] for a in chunks} == {True, False}


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


def test_a_program_that_keeps_every_row_is_not_correct(run_tiny,
                                                       monkeypatch):
    """The planted fault: the served model's indexer keeps as many rows as
    a context can hold, so its attention is plain grouped-query attention
    (the reference still keeps ``index_topk``)."""
    import dataclasses

    real = runner.transformer_config

    def keeps_all(dims, positions, dtype):
        return dataclasses.replace(real(dims, positions, dtype),
                                   index_topk=positions + 64)

    monkeypatch.setattr(runner, "transformer_config", keeps_all)
    out = run_tiny(CELL, seconds=4.0)
    assert out["correct"] is False
    rows = _compared(out)
    assert (rows["mean_logit_gap"]["value"]
            > rows["mean_logit_gap"]["limit"])
