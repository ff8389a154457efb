"""The DeepSeek-V3 cell: its roofline arithmetic by hand, its readers on a
trace and a span list made by hand, its ``--tiny`` rehearsal, and
``correct`` false for the float8 control and for a planted fault (a served
model that skips the shared expert).  CPU, toy sizes."""

import json

import pytest

from benchmarks.harness import common, serve_deepseek
from benchmarks.layer_metrics import (
    _named_kernels as nk,
    expert_load_max_share,
    expert_tokens_per_step,
    mla_decode_roofline,
    mla_decode_us_per_call,
    moe_experts_roofline,
)
from benchmarks.roofline import bound, mla_decode, moe_experts
from benchmarks.tests.test_layer_readers import SPANS, bag
from benchmarks.trace import reduce as tr

PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
CONFIG = common.load_json(common.ROOT / "benchmarks/configs/deepseek-v3.json")
DIMS = serve_deepseek.model_dims(CONFIG)


# -- the arithmetic -----------------------------------------------------------

def test_mla_decode_one_lane():
    # 1000 live rows, 128 heads: scores contract 576, values 512
    assert mla_decode.flops(1000, 128, 512, 64) == 2 * 128 * 1088 * 1000
    # a row is read once at its stored 640; q in 640 and o out 512 a head
    assert mla_decode.bytes_moved(1000, 1, 128, 640, 512) == (
        1000 * 640 * 2 + 128 * (640 + 512) * 2)


def test_mla_decode_sits_at_the_ridge_on_v5e():
    f = mla_decode.flops(180_000, 128, 512, 64)
    b = mla_decode.bytes_moved(180_000, 128, 128, 640, 512)
    ratio = (f / PEAKS["flops_bf16"]) / (b / PEAKS["hbm_bytes_per_s"])
    # 217 FLOP a byte of a 640-wide row against the ridge of 240, and the
    # queries and outputs of 128 lanes on top: the bytes bound, barely
    assert 0.7 < ratio < 1.0


def test_moe_experts_flops_and_bytes():
    assert moe_experts.flops(64, 7168, 2048) == 3 * 2 * 64 * 7168 * 2048
    # 64 assignments over 16 experts touch 15.7 of them
    touched = moe_experts.experts_touched(64, 16)
    assert touched == pytest.approx(16 * (1 - (15 / 16) ** 64))
    assert moe_experts.experts_touched(1, 16) == pytest.approx(1.0)
    assert moe_experts.experts_touched(0, 16) == 0.0
    assert moe_experts.bytes_moved(64, 16, 7168, 2048) == pytest.approx(
        touched * 3 * 7168 * 2048 * 2 + 64 * 2 * (7168 + 2048) * 2)
    # weight streaming: far under the ridge
    assert (moe_experts.flops(64, 7168, 2048) / PEAKS["flops_bf16"]
            < moe_experts.bytes_moved(64, 16, 7168, 2048)
            / PEAKS["hbm_bytes_per_s"])


def test_the_config_holds_the_published_widths_and_the_cut():
    assert (DIMS.embed, DIMS.heads, DIMS.q_lora, DIMS.kv_lora) == (
        7168, 128, 1536, 512)
    assert (DIMS.nope, DIMS.rope, DIMS.v_head) == (128, 64, 128)
    assert (DIMS.dense_ff, DIMS.expert_ff, DIMS.experts, DIMS.top_k) == (
        18432, 2048, 256, 8)
    assert (DIMS.layers, DIMS.first_k_dense, DIMS.held, DIMS.vocab) == (
        5, 1, (0, 16), 16160)
    assert DIMS.softmax_scale == pytest.approx(0.135234, rel=1e-5)
    assert serve_deepseek.count_params(DIMS) == CONFIG["sizes"]["parameters"]


# -- the readers ----------------------------------------------------------------

# (expert_tokens, expert_tokens_max) of the hand-made segments, by seq
ROUTED = {0: (999, 99), 1: (512, 20), 2: (240, 10), 3: (528, 30),
          4: (999, 99)}
EXPERT_SPANS = [
    dict(s, args=dict(s["args"], expert_tokens=ROUTED[s["args"]["seq"]][0],
                      expert_tokens_max=ROUTED[s["args"]["seq"]][1]))
    if s["name"] == "serve/segment_drain" else s for s in SPANS]


def expert_bag(spans=EXPERT_SPANS, **kw):
    out = bag(spans, **kw)
    out["dims"], out["peaks"] = DIMS, PEAKS
    return out


def test_expert_readers_sum_the_windows_drained_segments():
    # segments 1, 2, 3 drain inside the window: 8 + 4 + 8 steps, 4 layers
    assert expert_tokens_per_step.read(expert_bag()) == pytest.approx(
        (512 + 240 + 528) / (20 * 4))
    assert expert_load_max_share.read(expert_bag()) == pytest.approx(
        100.0 * (20 + 10 + 30) / 1280)


def test_expert_readers_find_nothing_on_a_program_without_the_fields():
    assert expert_tokens_per_step.read(expert_bag(SPANS)) is None
    assert expert_load_max_share.read(expert_bag(SPANS)) is None
    assert moe_experts_roofline.read(expert_bag(SPANS)) is None


def _kernel(name, operands, i):
    args = ", ".join(f"s32[8]{{0}} %a{j}" for j in range(operands))
    return (f"%{name}.{i} = bf16[128,128,512]{{2,1,0}} custom-call({args}), "
            'custom_call_target="tpu_custom_call"')


def _trace(monkeypatch, ops, modules=()):
    monkeypatch.setattr(tr, "find_xplane", lambda d: "hand.xplane.pb")
    monkeypatch.setattr(tr, "load", lambda p: {
        "devices": {0: {"ops": list(ops), "modules": list(modules)}},
        "host": []})


def test_mla_decode_is_found_by_its_name(monkeypatch):
    ops = [tr.Event(_kernel("paged_mla_decode", 4, i), i,
                    i + 400e-6) for i in range(5)]
    ops += [tr.Event(_kernel("paged_flash_decode", 6, 9), 9, 9.5),
            tr.Event("%fusion.paged_mla_decode = f32[2] fusion()", 9, 10)]
    _trace(monkeypatch, ops)
    assert mla_decode_us_per_call.read(expert_bag()) == pytest.approx(400.0)
    _trace(monkeypatch, ops[5:])
    assert mla_decode_us_per_call.read(expert_bag()) is None


def test_mla_decode_roofline_from_the_loops_events(monkeypatch):
    ops = [tr.Event(_kernel("paged_mla_decode", 4, i), i,
                    i + 400e-6) for i in range(5)]
    _trace(monkeypatch, ops)
    run = expert_bag()
    # one lane with a 1000-token prompt, 500 tokens in, a segment of 8 steps
    run["events"] = [
        {"kind": "admit", "trace": "a", "prompt_len": 1000},
        {"kind": "segment", "trace": "a", "seq": 1, "steps": 8,
         "tokens": 500}]
    ctx = 1500 + 3.5                  # mean live rows over the 8 steps
    want = bound.share(mla_decode.flops(ctx, 128, 512, 64),
                       mla_decode.bytes_moved(ctx, 1, 128, 640, 512),
                       400e-6, PEAKS)
    assert mla_decode_roofline.read(run) == pytest.approx(want)
    run["events"] = []
    assert mla_decode_roofline.read(run) is None


def test_moe_experts_roofline_counts_the_segments_calls_only(monkeypatch,
                                                            capsys):
    pair = lambda t: [  # noqa: E731 - the two kernels of one layer's product
        tr.Event(_kernel("moe_experts_gate_up", 5, int(t)), t,
                 t + 1.2e-3),
        tr.Event(_kernel("moe_experts_down", 4, int(t)),
                 t + 1.2e-3, t + 1.8e-3)]
    ops = pair(1.0) + pair(2.0) + pair(5.0)      # the third: a prefill chunk
    modules = [tr.Event("jit__segment_impl(7)", 0.9, 3.0),
               tr.Event("jit__prefill_chunk_impl(3)", 4.9, 6.0)]
    _trace(monkeypatch, ops, modules)
    tokens = (512 + 240 + 528) / (20 * 4)
    want = bound.share(moe_experts.flops(tokens, 7168, 2048),
                       moe_experts.bytes_moved(tokens, 16, 7168, 2048),
                       1.8e-3, PEAKS)
    assert moe_experts_roofline.read(expert_bag()) == pytest.approx(want)
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["calls"] == 2 and said["tokens_per_call"] == tokens
    assert nk.calls(expert_bag(), moe_experts_roofline.KERNELS)[0] == 6


@pytest.mark.parametrize("reader", [
    mla_decode_us_per_call, mla_decode_roofline, moe_experts_roofline,
    expert_tokens_per_step, expert_load_max_share])
def test_an_empty_window_gives_none(reader, monkeypatch):
    monkeypatch.setattr(tr, "find_xplane", lambda d: None)
    assert reader.read(expert_bag([])) is None
    assert reader.read(expert_bag(EXPERT_SPANS, rids=())) is None


# -- the rehearsal and ``correct`` ----------------------------------------------

def _compared(out: dict) -> dict:
    line = next(ln for ln in out["lines"] if ln.get("phase") == "correct")
    return {r["number"]: r for r in line["compared"]}


def test_tiny_rehearsal_is_correct_and_reads_the_expert_layer(run_tiny):
    from tpudist import obs

    # the readers take the window from the ring's `serve/request` spans by
    # rid; an earlier run IN THIS PROCESS left spans of the same rids
    obs.tracer.clear()
    out = run_tiny("dsv3_reason_batch", seconds=4.0, trace=1)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert {"expert_tokens_per_step", "expert_load_max_share",
            "decode_occupancy", "host_ms_per_segment",
            "compile_s"} <= set(out["rehearsed"])
    line = next(ln for ln in out["lines"] if ln.get("phase") == "correct")
    # 4 of 16 experts are held: the reference's own router sends about a
    # quarter of the routed work here
    assert 0.1 < line["held_share"] < 0.4


def test_fp8_control_is_not_correct(run_tiny, monkeypatch):
    seen = {}
    real = serve_deepseek.reference_gaps

    def both(params, dims, positions, sample, quant=None):
        seen["control"] = real(params, dims, positions, sample, quant="fp8")
        return real(params, dims, positions, sample)

    monkeypatch.setattr(serve_deepseek, "reference_gaps", both)
    out = run_tiny("dsv3_reason_batch", seconds=4.0)
    rows = _compared(out)
    assert out["correct"] is True
    assert (seen["control"]["worst_gap"] > rows["worst_logit_gap"]["limit"]
            or seen["control"]["mean_gap"] > rows["mean_logit_gap"]["limit"])


def test_a_step_that_skips_the_shared_expert_is_not_correct(run_tiny,
                                                            monkeypatch):
    """The planted fault: the served model's expert layers leave the shared
    expert out (the reference keeps it)."""
    import dataclasses

    real = serve_deepseek.transformer_config

    def no_shared(dims, positions, dtype):
        cfg = real(dims, positions, dtype)
        return dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, n_shared=0))

    monkeypatch.setattr(serve_deepseek, "transformer_config", no_shared)
    out = run_tiny("dsv3_reason_batch", seconds=4.0)
    assert out["correct"] is False
    rows = _compared(out)
    assert (rows["worst_logit_gap"]["value"] > rows["worst_logit_gap"]["limit"]
            or rows["mean_logit_gap"]["value"]
            > rows["mean_logit_gap"]["limit"])
