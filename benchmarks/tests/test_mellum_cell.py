"""The Mellum 2 cell: its roofline arithmetic by hand, its readers on a trace
and a span list made by hand, its configuration against the catalog row, its
``--tiny`` rehearsal, and ``correct`` false for the float8 control and for
the structure control (the reference with the band left out of the sliding
layers).  CPU, toy sizes."""

import json

import pytest

from benchmarks.harness import common, serve_mellum
from benchmarks.layer_metrics import (
    kv_window_blocks_peak_share,
    paged_decode_us_per_call,
    window_decode_roofline,
    window_decode_us_per_call,
    window_rows_walked_share,
)
from benchmarks.roofline import bound, window_decode
from benchmarks.tests.test_layer_readers import SPANS, bag
from benchmarks.trace import reduce as tr

PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
CONFIG = common.load_json(
    common.ROOT / "benchmarks/configs/mellum2-12b-a2.5b.json")
DIMS = serve_mellum.model_dims(CONFIG)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


# -- the arithmetic -----------------------------------------------------------

def test_window_decode_one_lane():
    # one lane whose window holds 1024 rows: 32 heads x 128 twice a row;
    # a key and a value row of 4 x 128 bf16 = 2 KB a row, q and o 16 KB
    assert window_decode.flops(1024, 32, 128) == 4 * 32 * 128 * 1024
    assert window_decode.bytes_moved(1024, 1, 32, 4, 128) == (
        1024 * 2048 + 2 * 32 * 128 * 2)


def test_window_decode_is_memory_bound_on_v5e():
    # 8 query heads share a K/V row: 8 FLOPs a byte, far under the ridge
    # of 240
    f = window_decode.flops(40 * 1024, 32, 128)
    b = window_decode.bytes_moved(40 * 1024, 40, 32, 4, 128)
    assert f / PEAKS["flops_bf16"] < b / PEAKS["hbm_bytes_per_s"]
    assert bound.least_seconds(f, b, PEAKS) == pytest.approx(
        b / PEAKS["hbm_bytes_per_s"])


# -- the configuration ----------------------------------------------------------

def test_the_config_holds_the_published_widths_and_the_cut():
    assert (DIMS.embed, DIMS.heads, DIMS.kv_heads, DIMS.head_dim) == (
        2304, 32, 4, 128)
    assert (DIMS.expert_ff, DIMS.experts, DIMS.top_k, DIMS.held) == (
        896, 64, 8, (0, 16))
    assert (DIMS.layers, DIMS.vocab, DIMS.window) == (28, 98304, 1024)
    assert DIMS.sliding == (True, True, True, False) * 7
    assert DIMS.first_k_dense == 0
    assert serve_mellum.count_params(DIMS) == CONFIG["sizes"]["parameters"]
    assert CONFIG["sizes"]["parameters"] == 3_826_319_616
    assert CONFIG["reduced"] == ["num_experts"]
    assert CONFIG["published"] == {"num_experts": 64}


def test_every_catalog_key_is_in_the_file_unchanged_but_the_cut():
    try:
        rows = [json.loads(ln) for ln in open(CATALOG)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in rows if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CONFIG["reduced"]:
            assert CONFIG["published"][key] == value
        else:
            assert CONFIG[key] == value, key


def test_the_program_takes_the_published_block():
    import jax.numpy as jnp

    cfg = serve_mellum.transformer_config(DIMS, 8192, jnp.bfloat16)
    assert cfg.head_dim == 128 and cfg.attn_dim == 4096
    assert cfg.windows == (1024, 1024, 1024, None) * 7
    assert cfg.layer_rope_scaling(0) is None
    assert cfg.layer_rope_scaling(3).factor == 16.0
    assert cfg.moe.held == (0, 16) and cfg.moe.scoring == "softmax"
    assert all(cfg.is_expert_layer(i) for i in range(28))


# -- the readers ----------------------------------------------------------------

# (rows_window, rows_window_live, rows, rows_live, blocks_released) by seq
WALKED = {0: (9, 9, 9, 9, 9), 1: (3300, 3000, 9000, 8800, 2),
          2: (2200, 2048, 7000, 6900, 0), 3: (1100, 1024, 5000, 4990, 1),
          4: (9, 9, 9, 9, 9)}
KEYS = ("rows_window", "rows_window_live", "rows", "rows_live",
        "blocks_released")
WINDOW_SPANS = [
    dict(s, args=dict(s["args"], **dict(zip(KEYS, WALKED[s["args"]["seq"]]))))
    if s["name"] == "serve/segment_drain" else s for s in SPANS]


def window_bag(spans=WINDOW_SPANS, **kw):
    out = bag(spans, **kw)
    out["dims"], out["peaks"] = DIMS, PEAKS
    out["stats"].update(kv_window_blocks_peak=330,
                        kv_window_blocks_total=400)
    return out


def test_window_rows_walked_share_is_weighted_by_the_steps_run():
    # segments 1, 2, 3 drain inside the window, having run 8, 4, 8 steps
    assert window_rows_walked_share.read(window_bag()) == pytest.approx(
        100.0 * (3300 * 8 + 2200 * 4 + 1100 * 8)
        / (8800 * 8 + 6900 * 4 + 4990 * 8))


def test_window_group_peak_share():
    assert kv_window_blocks_peak_share.read(window_bag()) == pytest.approx(
        82.5)
    run = window_bag()
    del run["stats"]["kv_window_blocks_total"]
    assert kv_window_blocks_peak_share.read(run) is None


def test_the_new_readers_find_nothing_on_a_program_without_the_fields():
    """The parent of PR 31: its spans carry no window rows, its sums no
    window group, its trace no kernel of that name."""
    assert window_rows_walked_share.read(window_bag(SPANS)) is None
    assert window_decode_roofline.read(window_bag(SPANS)) is None
    run = bag(SPANS)
    assert kv_window_blocks_peak_share.read(run) is None


def _kernel(name, i):
    args = ", ".join(f"s32[8]{{0}} %a{j}" for j in range(6))
    return (f"%{name}.{i} = bf16[160,8,128]{{2,1,0}} custom-call({args}), "
            'custom_call_target="tpu_custom_call"')


def _trace(monkeypatch, ops):
    monkeypatch.setattr(tr, "find_xplane", lambda d: "hand.xplane.pb")
    monkeypatch.setattr(tr, "load", lambda p: {
        "devices": {0: {"ops": list(ops), "modules": []}}, "host": []})


def test_the_two_paged_kernels_are_told_apart_by_name(monkeypatch):
    ops = [tr.Event(_kernel("paged_window_decode", i), i, i + 120e-6)
           for i in range(6)]
    ops += [tr.Event(_kernel("paged_flash_decode", i), 10 + i,
                     10 + i + 300e-6) for i in range(2)]
    _trace(monkeypatch, ops)
    assert window_decode_us_per_call.read(window_bag()) == pytest.approx(
        120.0)
    assert paged_decode_us_per_call.read(window_bag()) == pytest.approx(
        300.0)
    _trace(monkeypatch, ops[6:])
    assert window_decode_us_per_call.read(window_bag()) is None
    assert window_decode_roofline.read(window_bag()) is None


def test_window_decode_roofline_from_the_loops_spans(monkeypatch):
    ops = [tr.Event(_kernel("paged_window_decode", i), i, i + 120e-6)
           for i in range(6)]
    _trace(monkeypatch, ops)
    live = (3000 * 8 + 2048 * 4 + 1024 * 8) / 20
    lanes = (3 * 8 + 2 * 4 + 1 * 8) / 20
    want = bound.share(window_decode.flops(live, 32, 128),
                       window_decode.bytes_moved(live, lanes, 32, 4, 128),
                       120e-6, PEAKS)
    assert window_decode_roofline.read(window_bag()) == pytest.approx(want)
    assert want < 100.0


@pytest.mark.parametrize("reader", [
    window_decode_us_per_call, window_decode_roofline,
    window_rows_walked_share])
def test_an_empty_window_gives_none(reader, monkeypatch):
    monkeypatch.setattr(tr, "find_xplane", lambda d: None)
    assert reader.read(window_bag([])) is None
    assert reader.read(window_bag(WINDOW_SPANS, rids=())) is None


# -- the rehearsal and ``correct`` ----------------------------------------------

def _compared(out: dict) -> dict:
    line = next(ln for ln in out["lines"] if ln.get("phase") == "correct")
    return {r["number"]: r for r in line["compared"]}


def test_tiny_rehearsal_is_correct_and_reads_the_window_layers(run_tiny):
    from tpudist import obs

    obs.tracer.clear()
    out = run_tiny("mellum2_agent_mixed", seconds=4.0, trace=1)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert {"window_rows_walked_share", "kv_window_blocks_peak_share",
            "kv_blocks_peak_share", "expert_tokens_per_step",
            "decode_occupancy", "decode_rows_live_share",
            "compile_s"} <= set(out["rehearsed"])
    line = next(ln for ln in out["lines"] if ln.get("phase") == "correct")
    # 4 of 16 experts are held
    assert 0.1 < line["held_share"] < 0.4
    window = next(ln for ln in out["lines"] if ln.get("phase") == "window")
    assert 0 < window["kv_window_blocks_peak"] <= window[
        "kv_window_blocks_total"]


@pytest.mark.parametrize("control", ["fp8", "no_window"])
def test_a_control_is_not_correct(run_tiny, monkeypatch, control):
    seen = {}
    real = serve_mellum.reference_gaps
    kw = {"quant": "fp8"} if control == "fp8" else {"window": False}

    def both(params, dims, positions, sample, **_):
        seen["control"] = real(params, dims, positions, sample, **kw)
        return real(params, dims, positions, sample)

    monkeypatch.setattr(serve_mellum, "reference_gaps", both)
    out = run_tiny("mellum2_agent_mixed", seconds=4.0)
    rows = _compared(out)
    assert out["correct"] is True
    assert (seen["control"]["worst_gap"] > rows["worst_logit_gap"]["limit"]
            or seen["control"]["mean_gap"] > rows["mean_logit_gap"]["limit"])
