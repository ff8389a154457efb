"""The Command A+ cell: its configuration against the catalog row, its
traffic file's lengths, its three new readers on a span list and a scope
split made by hand, its ``--tiny`` rehearsal, and ``correct`` false for each
of the four controls (float8; rotary put on the full layers; the block made
sequential; the shared experts summed).  CPU, toy sizes."""

import json
import statistics

import pytest

from benchmarks.harness import common, serve_command_a
from benchmarks.layer_metrics import (
    kv_heads_per_grid_row,
    step_experts_ms,
    step_shared_ms,
)
from benchmarks.reference import command_a as ref
from benchmarks.tests.test_layer_readers import SPANS, bag
from benchmarks.traffic import generator

CONFIG = common.load_json(
    common.ROOT / "benchmarks/configs/command-a-plus-05-2026.json")
DIMS = serve_command_a.model_dims(CONFIG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "cmdaplus_rag_mixed"


# -- the configuration ----------------------------------------------------------

def test_the_config_holds_the_published_widths_and_the_cut():
    assert (DIMS.embed, DIMS.heads, DIMS.kv_heads, DIMS.head_dim) == (
        4096, 128, 8, 128)
    assert (DIMS.expert_ff, DIMS.experts, DIMS.top_k, DIMS.held,
            DIMS.n_shared) == (4096, 128, 8, (0, 16), 4)
    assert (DIMS.layers, DIMS.vocab, DIMS.window) == (4, 32768, 4096)
    assert DIMS.sliding == (True, True, True, False)
    assert (DIMS.norm_eps, DIMS.rope_theta, DIMS.logit_scale) == (
        1e-5, 50000.0, 1.0)
    assert serve_command_a.count_params(DIMS) == CONFIG["sizes"][
        "parameters"] == 4_733_292_544
    assert CONFIG["reduced"] == ["num_hidden_layers", "layer_types",
                                 "num_experts", "vocab_size"]
    assert CONFIG["held"]["routed_experts"] == [0, 16]
    assert CONFIG["held"]["vocab_rows"] == [0, 32768]


def test_every_catalog_key_is_in_the_file_unchanged_but_the_cut():
    try:
        rows = [json.loads(ln) for ln in open(CATALOG)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in rows if r["name"] == "command-a-plus-05-2026")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key
    # one whole period of the published pattern, from its start
    assert CONFIG["layer_types"] == row["config"]["layer_types"][:4]


def test_the_program_takes_the_published_block():
    import jax.numpy as jnp

    cfg = serve_command_a.transformer_config(DIMS, 18432, jnp.bfloat16)
    assert cfg.head_dim == 128 and cfg.attn_dim == 16384
    assert cfg.windows == (4096, 4096, 4096, None)
    assert [cfg.layer_positions(i) for i in range(4)] == [
        "rotary", "rotary", "rotary", "none"]
    assert (cfg.norm, cfg.norm_order, cfg.tie_embeddings) == (
        "layernorm_scale", "parallel", True)
    assert cfg.moe.held == (0, 16) and cfg.moe.scoring == "sigmoid"
    assert (cfg.moe.n_shared, cfg.moe.shared_combine) == (4, "mean")
    assert all(cfg.is_expert_layer(i) for i in range(4))


def test_the_recipe_scales_the_branch_outputs_alone():
    one = dict((p, std) for p, _, std in serve_command_a.leaf_table(
        DIMS, 1.0))
    half = dict((p, std) for p, _, std in serve_command_a.leaf_table(
        DIMS, 0.5))
    out = {p for p in one if half[p] != one[p]}
    assert out == {(f"block{i}",) + tail for i in range(4) for tail in (
        ("attn", "proj", "kernel"), ("moe", "w_down"),
        ("moe", "shared", "down", "kernel"))}
    assert all(half[p] == 0.5 * one[p] for p in out)
    assert ("lm_head", "kernel") not in one


# -- the traffic ------------------------------------------------------------------

def test_the_traffic_file_gives_the_stated_lengths():
    mix = generator.load("rag_mixed")
    assert (mix["kind"], mix["clients"], mix["max_total"]) == (
        "closed_loop", 96, 18432)
    assert mix["lengths"] == {
        "prompt": {"dist": "lognormal", "median": 4096, "sigma": 0.8,
                   "min": 512, "max": 16384},
        "output": {"dist": "uniform", "min": 1024, "max": 2048}}
    items = generator.serve_items(mix, 7, 60.0, DIMS.vocab)
    prompts = [len(it.prompt) for it in items]
    outs = [it.max_new for it in items]
    assert min(prompts) == 512 and max(prompts) == 16384
    assert statistics.median(prompts) == pytest.approx(4096, rel=0.02)
    assert 1024 <= min(outs) and max(outs) <= 2048
    assert statistics.fmean(outs) == pytest.approx(1536, rel=0.02)
    assert all(p + o <= 18432 for p, o in zip(prompts, outs))
    assert all(0 <= int(it.prompt.max()) < DIMS.vocab for it in items)
    # one schedule for every seed; the seed draws the tokens
    again = generator.serve_items(mix, 8, 60.0, DIMS.vocab)
    assert [len(it.prompt) for it in again] == prompts
    assert (again[0].prompt != items[0].prompt).any()


# -- the readers ------------------------------------------------------------------

GRID = [dict(s, args=dict(s["args"], grid_rows=96, heads_per_grid_row=4))
        if s["name"] == "serve/segment_drain" else s for s in SPANS]


def test_kv_heads_per_grid_row_reads_the_drains():
    assert kv_heads_per_grid_row.read(bag(GRID)) == 4.0
    # the parent: a drain that does not say it; and an empty window
    assert kv_heads_per_grid_row.read(bag(SPANS)) is None
    assert kv_heads_per_grid_row.read(bag(GRID, rids=())) is None
    assert kv_heads_per_grid_row.read(bag([])) is None


SPLIT = {"attn/proj": 1.0, "attn/core": 2.5, "mlp/route": 0.25,
         "mlp/experts": 3.0, "mlp/shared": 1.5, "head": 0.5, "step": 9.0,
         "other": 0.25}


def test_the_expert_layers_two_parts_are_read_apart():
    run = {"_per_step": dict(SPLIT)}
    assert step_shared_ms.read(run) == 1.5
    assert step_experts_ms.read(run) == 3.0
    # a model without a shared expert, one without expert layers, and a
    # program without scopes (the parent of PR 37)
    no_shared = {"_per_step": {k: v for k, v in SPLIT.items()
                               if k != "mlp/shared"}}
    assert step_shared_ms.read(no_shared) is None
    assert step_experts_ms.read(no_shared) == 3.0
    dense = {"_per_step": {"mlp/dense": 2.0, "step": 2.0, "other": 0.0}}
    assert step_shared_ms.read(dense) is None
    assert step_experts_ms.read(dense) is None
    assert step_shared_ms.read({"_per_step": None}) is None
    assert step_experts_ms.read({"_per_step": None}) is None


# -- the rehearsal and ``correct`` ----------------------------------------------

def _compared(out: dict) -> dict:
    line = next(ln for ln in out["lines"] if ln.get("phase") == "correct")
    return {r["number"]: r for r in line["compared"]}


def test_tiny_rehearsal_is_correct_and_reads_every_listed_metric(run_tiny):
    from tpudist import obs

    obs.tracer.clear()
    out = run_tiny(CELL, seconds=4.0, trace=1)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    # the program counters of the cell's list (the device trace's readers
    # find no TPU plane in a CPU trace)
    assert {"kv_heads_per_grid_row", "window_rows_walked_share",
            "kv_window_blocks_peak_share", "kv_blocks_peak_share",
            "expert_tokens_per_step", "expert_load_max_share",
            "decode_occupancy", "decode_rows_live_share",
            "host_wait_share", "host_ms_per_segment",
            "compiles_in_window_serve", "compile_s"} <= set(
                out["rehearsed"])
    line = next(ln for ln in out["lines"] if ln.get("phase") == "correct")
    # 4 of 16 experts are held
    assert 0.1 < line["held_share"] < 0.4
    window = next(ln for ln in out["lines"] if ln.get("phase") == "window")
    assert 0 < window["kv_window_blocks_peak"] <= window[
        "kv_window_blocks_total"]


@pytest.mark.parametrize("control", sorted(ref.CONTROLS))
def test_a_control_is_not_correct(run_tiny, monkeypatch, control):
    seen = {}
    real = serve_command_a.reference_gaps

    def both(params, dims, positions, sample, **_):
        seen["control"] = real(params, dims, positions, sample,
                               ref.CONTROLS[control])
        return real(params, dims, positions, sample)

    monkeypatch.setattr(serve_command_a, "reference_gaps", both)
    out = run_tiny(CELL, seconds=4.0)
    rows = _compared(out)
    assert out["correct"] is True
    assert (seen["control"]["worst_gap"] > rows["worst_logit_gap"]["limit"]
            or seen["control"]["mean_gap"] > rows["mean_logit_gap"]["limit"])
