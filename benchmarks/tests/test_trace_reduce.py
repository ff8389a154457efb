"""The reduction from a trace to numbers, on events made by hand and on a
small trace recorded on the chip."""

import pathlib

import pytest

from benchmarks.trace import reduce as tr

E = tr.Event
RECORDED = pathlib.Path(__file__).parent / "data"


def test_union_merges_overlaps():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]


def test_self_times_do_not_count_a_loop_body_twice():
    events = [E("while", 0, 10), E("fusion.1", 1, 4), E("fusion.2", 5, 9),
              E("copy", 11, 12)]
    out = tr.self_times(events)
    assert out == pytest.approx({"while": 3, "fusion.1": 3, "fusion.2": 4,
                                 "copy": 1})


def test_gaps_go_to_the_host_span_that_covers_them():
    busy = [(1.0, 2.0), (4.0, 5.0)]
    host = [E("serve/admit", 2.0, 3.5), E("serve/segment", 3.5, 4.0)]
    gaps = tr.gaps_by_host_span(busy, 0.0, 6.0, host)
    assert gaps == pytest.approx({"(none)": 2.0, "serve/admit": 2.0})


DQ = ('%flash_bwd_dq.53 = (bf16[32,8192,128]{2,1,0:T(8,128)(2,1)}, f32[32,1,8192]'
      '{2,1,0:T(1,128)S(1)}) custom-call(s32[1,2]{1,0:T(1,128)S(1)} '
      '%copy-done.215, bf16[32,8192,128]{2,1,0} %bitcast.1, bf16[2,8192,128]'
      '{2,1,0} %bitcast.2, bf16[2,8192,128]{2,1,0} %bitcast.3, '
      'bf16[32,8192,128]{2,1,0} %bitcast.4, f32[32,1,8192]{2,1,0} '
      '%pallas_call.122, bf16[32,8192,128]{2,1,0} %bitcast.5), '
      'custom_call_target="tpu_custom_call", operand_layout_constraints={}')


def test_an_op_is_parsed_from_its_hlo_text():
    op = tr.parse_op(DQ)
    assert op == {"name": "flash_bwd_dq", "opcode": "custom-call",
                  "outputs": ("bf16[32,8192,128]", "f32[32,1,8192]"),
                  "operands": 7, "pallas": True}
    assert tr.label(DQ) == (
        "flash_bwd_dq:pallas/7->bf16[32,8192,128],f32[32,1,8192]")
    fusion = ("%fusion.24 = f32[2048,49152]{1,0:T(8,128)} fusion("
              "f32[2048,49152]{1,0} %p.1, f32[]{:T(128)} %sub.3), "
              "kind=kOutput, calls=%fused_computation")
    assert tr.label(fusion) == "fusion:fusion->f32[2048,49152]"


def test_the_flash_kernels_are_told_by_their_names():
    from benchmarks.roofline import flash_bwd, paged_decode

    op = tr.parse_op(DQ)
    assert flash_bwd.is_kernel(op) and not paged_decode.is_kernel(op)
    # neither the operands nor the outputs decide: a dQ that kept one
    # residual more, or came without its delta, is still the kernel
    assert flash_bwd.is_kernel(dict(op, operands=8, outputs=op["outputs"][:1]))
    assert not flash_bwd.is_kernel(dict(op, name="attn"))
    assert tr.pallas_seconds({DQ: 2.0, "%copy.1 = f32[2]{0} copy(f32[2]{0} "
                                        "%x)": 5.0},
                             flash_bwd.is_kernel) == 2.0


@pytest.mark.parametrize("name", ["serve", "train"])
def test_recorded_trace(name, tmp_path):
    """Traces recorded on the v5e, kept gzipped: half a second of the
    steady serving cell (PR 24, before the kernels had names) and the train
    cell's two traced steps (PR 38: ``flash_fwd``, ``flash_bwd_dq`` and
    ``flash_bwd_dkv`` by name)."""
    import gzip

    packed = RECORDED / f"{name}.xplane.pb.gz"
    path = tmp_path / f"{name}.xplane.pb"
    path.write_bytes(gzip.decompress(packed.read_bytes()))
    out = tr.reduce(str(path), chips=1)
    assert out is not None
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["by_module"] and out["by_op"]
    assert len(out["breakdown"]["device_ops"]) <= 10
    # self times add up to the busy time (nothing counted twice)
    assert sum(out["by_op"].values()) == pytest.approx(out["busy_s"],
                                                       rel=0.02)
    from benchmarks.roofline import flash_bwd, flash_fwd, paged_decode

    is_kernel = {"train": flash_bwd.is_kernel,
                 "serve": paged_decode.is_kernel}[name]
    assert tr.pallas_seconds(out["by_op"], is_kernel) > 0
    if name == "train":
        assert tr.pallas_seconds(
            out["by_op"], lambda op: op["name"] in flash_fwd.NAMES) > 0
