"""The FLOP and byte functions against shapes worked by hand, and the train
cell's kernels found in a trace by their names."""

import gzip
import json
import pathlib
import types

import pytest

from benchmarks.layer_metrics import _named_kernels as nk
from benchmarks.layer_metrics import (flash_bwd_roofline, flash_fwd_roofline,
                                      flash_prefill_roofline)
from benchmarks.roofline import (bound, flash_bwd, flash_fwd, flash_prefill,
                                 model_flops, paged_decode)
from benchmarks.trace import reduce as tr

PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}


def test_paged_decode_one_lane():
    # one lane, 1000 tokens of context, 22 heads of 128, 1 K/V head:
    # QK^T 2*22*128*1000 and PV the same
    assert paged_decode.flops(1000, 22, 128) == 4 * 22 * 128 * 1000
    # K and V: 1000 * 128 * 2 B each; q and out: 22 * 128 * 2 B each
    assert paged_decode.bytes_moved(1000, 1, 22, 1, 128) == (
        2 * 1000 * 128 * 2 + 2 * 22 * 128 * 2)


def test_paged_decode_is_memory_bound_on_v5e():
    f = paged_decode.flops(32 * 1500, 22, 128)
    b = paged_decode.bytes_moved(32 * 1500, 32, 22, 1, 128)
    assert b / PEAKS["hbm_bytes_per_s"] > f / PEAKS["flops_bf16"]


def test_flash_prefill_first_chunk():
    # rows 0..3 see 1, 2, 3, 4 keys: 10 pairs
    assert flash_prefill.flops(0, 4, 2, 8) == 4 * 2 * 8 * 10
    # at offset 4: 5, 6, 7, 8 keys: 26 pairs
    assert flash_prefill.flops(4, 4, 2, 8) == 4 * 2 * 8 * 26
    assert flash_prefill.bytes_moved(4, 4, 2, 1, 8) == (
        2 * 8 * 1 * 8 * 2 + 2 * 4 * 2 * 8 * 2)


def test_flash_fwd_and_bwd_whole_sequences():
    pairs = 8 * 9 / 2
    assert flash_fwd.flops(3, 8, 2, 16) == 4 * 2 * 16 * 3 * pairs
    assert flash_bwd.flops(3, 8, 2, 16) == 2.5 * flash_fwd.flops(3, 8, 2, 16)
    # q, o at 2 heads; k, v at 1 head; lse f32
    assert flash_fwd.bytes_moved(1, 8, 2, 1, 16) == (
        2 * 8 * 2 * 16 * 2 + 2 * 8 * 1 * 16 * 2 + 8 * 2 * 4)


# -- the train cell's kernels in a trace ---------------------------------------

LAYERS, STEPS = 3, 2
FWD_S, DQ_S, DKV_S = 4e-3, 5e-3, 6e-3   # one event of each kernel


def _call(name: str, i: int, operands: int, outputs: str) -> str:
    args = ", ".join(f"bf16[8]{{0}} %a{j}" for j in range(operands))
    return (f"%{name}.{i} = {outputs} custom-call({args}), "
            'custom_call_target="tpu_custom_call"')


def _train_bag(monkeypatch, fwd_calls: int, fwd=_call, **options) -> dict:
    """Two traced steps of three layers: ``fwd_calls`` forward events a
    layer (two under ``remat``), then the backward pair."""
    out2 = "(bf16[32,64,16]{2,1,0}, f32[32,1,64]{2,1,0})"
    ops, t = [], 0.0

    def add(text, seconds):
        nonlocal t
        ops.append(tr.Event(text, t, t + seconds))
        t += seconds + 1e-4

    for i in range(STEPS * LAYERS):
        for j in range(fwd_calls):
            add(fwd("flash_fwd", 10 * i + j, 4, out2), FWD_S)
        add(_call("flash_bwd_dq", i, 7, out2), DQ_S)
        add(_call("flash_bwd_dkv", i, 7,
                  "(bf16[2,64,16]{2,1,0}, bf16[2,64,16]{2,1,0})"), DKV_S)
        add("%fusion.3 = bf16[2,64,128]{2,1,0} fusion(bf16[8]{0} %x)", 1e-3)
    monkeypatch.setattr(tr, "find_xplane", lambda d: "hand.xplane.pb")
    monkeypatch.setattr(tr, "load", lambda p: {
        "devices": {0: {"ops": ops, "modules": []}}, "host": []})
    return {"cell": {"name": "hand", "traffic": {"trace_steps": STEPS},
                     "config": {"program": {"options": options}}},
            "dims": types.SimpleNamespace(layers=LAYERS, heads=16,
                                          kv_heads=1, head_dim=16),
            "rows_per_chip": 2, "seq": 64, "peaks": PEAKS,
            "trace": {"by_op": tr.self_times(ops)}}


@pytest.mark.parametrize("fwd_calls", [2, 1])
def test_flash_fwd_roofline_counts_its_calls_in_the_trace(monkeypatch,
                                                          fwd_calls):
    """A step that recomputes the block calls the kernel twice a layer, one
    that keeps the output once: the same seconds an event read the same
    share, whatever the configuration's ``remat`` says."""
    want = bound.share(flash_fwd.flops(2, 64, 16, 16),
                       flash_fwd.bytes_moved(2, 64, 16, 1, 16), FWD_S, PEAKS)
    for remat in (True, False):
        run = _train_bag(monkeypatch, fwd_calls, remat=remat)
        assert flash_fwd_roofline.read(run) == pytest.approx(want)
    # the options are not opened at all
    assert flash_fwd_roofline.read(
        _train_bag(monkeypatch, fwd_calls)) == pytest.approx(want)


def test_flash_fwd_roofline_says_how_many_events_it_counted(monkeypatch,
                                                            capsys):
    flash_fwd_roofline.read(_train_bag(monkeypatch, 2))
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["events"] == 2 * LAYERS * STEPS
    assert said["events_per_traced_step"] == 2 * LAYERS
    assert said["kernel_s"] == pytest.approx(2 * LAYERS * STEPS * FWD_S)


def test_a_trace_without_the_forward_kernel_reads_none(monkeypatch):
    run = _train_bag(monkeypatch, 0)
    assert flash_fwd_roofline.read(run) is None
    assert flash_bwd_roofline.read(run) is not None
    run["trace"] = None
    assert flash_fwd_roofline.read(run) is None
    assert flash_bwd_roofline.read(run) is None


def test_flash_bwd_roofline_is_the_pair_a_layer_and_step(monkeypatch):
    assert flash_bwd_roofline.read(
        _train_bag(monkeypatch, 2)) == pytest.approx(bound.share(
            flash_bwd.flops(2, 64, 16, 16),
            flash_bwd.bytes_moved(2, 64, 16, 1, 16), DQ_S + DKV_S, PEAKS))


def test_a_kept_output_or_a_residual_more_is_still_the_kernel(monkeypatch):
    """What the old matchers counted (four and seven operands, two
    outputs) may change; the name stays."""
    def kept(name, i, operands, outputs):
        return _call(name, i, operands + 1,
                     outputs[:-1] + ", bf16[32,64,16]{2,1,0})")

    want = flash_fwd_roofline.read(_train_bag(monkeypatch, 1))
    assert flash_fwd_roofline.read(
        _train_bag(monkeypatch, 1, fwd=kept)) == pytest.approx(want)


@pytest.mark.parametrize("name,fwd,bwd", [
    ("flash_fwd", True, False), ("flash_bwd_dq", False, True),
    ("flash_bwd_dkv", False, True),
    # a sparse chunk's masked pass is a kernel of its own name: the dense
    # prefill's roofline does not count it, whatever it takes and yields
    ("sparse_gqa_prefill", False, False),
    ("paged_flash_decode", False, False),
    ("flash_fwd_wide", False, False)])
def test_the_flash_matchers_go_by_name(name, fwd, bwd):
    text = _call(name, 7, 4, "(bf16[22,512,128]{2,1,0}, "
                             "f32[22,1,512]{2,1,0})")
    op = tr.parse_op(text)
    assert op["pallas"] and op["name"] == name
    for reader in (flash_fwd_roofline, flash_prefill_roofline):
        assert bool(reader.KERNEL.match(text)) is fwd
    assert flash_bwd.is_kernel(op) is bwd


def test_the_recorded_train_trace_holds_two_forward_events_a_layer(
        monkeypatch, tmp_path):
    """The cell's two traced steps as recorded on the v5e (PR 38; 12
    layers, ``remat``): 2 x 12 x 2 events of ``flash_fwd``, and the shares
    the run itself printed."""
    data = pathlib.Path(__file__).parent / "data"
    path = tmp_path / "train.xplane.pb"
    path.write_bytes(gzip.decompress(
        (data / "train.xplane.pb.gz").read_bytes()))
    monkeypatch.setattr(tr, "find_xplane", lambda d: str(path))
    recorded = json.loads((data / "train.readings.json").read_text())
    run = {"cell": {"name": "sc1b_train_8k", "traffic": {"trace_steps": 2}},
           "dims": types.SimpleNamespace(layers=12, heads=16, kv_heads=1,
                                         head_dim=128),
           "rows_per_chip": 2, "seq": 8192, "peaks": PEAKS,
           "trace": tr.reduce(str(path), chips=1)}
    events, _ = nk.calls(run, flash_fwd_roofline.KERNEL)
    assert events == 2 * 12 * 2
    assert flash_fwd_roofline.read(run) == pytest.approx(
        recorded["flash_fwd_roofline"], rel=1e-6)
    assert flash_bwd_roofline.read(run) == pytest.approx(
        recorded["flash_bwd_roofline"], rel=1e-6)


def test_train_flops_per_token_starcoder_1b_cut():
    # 8 layers of 2048: q 2048^2, kv 2048*256, proj 2048^2, mlp 2*2048*8192;
    # head 2048*49152
    layer = 2 * 2048 ** 2 + 2048 * 256 + 2 * 2048 * 8192
    matmul = 8 * layer + 2048 * 49152
    got = model_flops.train_flops_per_token(matmul, 8, 8192, 16, 128)
    attention = 3.5 * 4 * 16 * 128 * 8193 / 2 * 8
    assert got == pytest.approx(6 * matmul + attention)
    assert 3.5e9 < got < 3.7e9


def test_roofline_share_picks_the_larger_bound():
    assert bound.least_seconds(197e12, 1.0, PEAKS) == pytest.approx(1.0)
    assert bound.least_seconds(1.0, 819e9, PEAKS) == pytest.approx(1.0)
    assert bound.share(197e12, 1.0, 2.0, PEAKS) == pytest.approx(50.0)


def test_peaks_table_names_its_source_and_the_v5e():
    table = json.loads((pathlib.Path(__file__).parents[1] / "roofline"
                        / "peaks.json").read_text())
    assert "TPU v5e" in table["source"]
    v5e = table["devices"]["TPU v5 lite"]
    assert v5e["flops_bf16"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_is_an_error():
    from benchmarks.harness import common

    with pytest.raises(common.NoChip):
        common.peaks_for("cpu")
