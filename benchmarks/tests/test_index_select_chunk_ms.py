"""``index_select_chunk_ms_per_call`` on a trace made by hand: two
programs call the kernel, and only the prefill chunk's calls count."""

import pytest

from benchmarks.layer_metrics import index_select_chunk_ms_per_call
from benchmarks.tests.test_deepseek_cell import _trace
from benchmarks.tests.test_layer_readers import SPANS, bag
from benchmarks.trace import reduce as tr


def _kernel(name, i, shape):
    return (f"%{name}.{i} = (s32[{shape},1]{{1,0}}, s32[{shape},1]{{1,0}}) "
            f"custom-call(s32[1]{{0}} %a, f32[{shape},32768]{{1,0}} %b), "
            'custom_call_target="tpu_custom_call"')


def test_the_kernel_counts_inside_a_prefill_chunk_only(monkeypatch):
    ops = [
        # two layers of one chunk: 1.0 and 2.0 ms
        tr.Event(_kernel("index_select_threshold", 3, 2048), 1.010, 1.011),
        tr.Event(_kernel("index_select_threshold", 4, 2048), 1.050, 1.052),
        # a decode step's call, and another kernel inside the chunk
        tr.Event(_kernel("index_select_threshold", 9, 12), 2.010, 2.0101),
        tr.Event(_kernel("paged_index_scores", 2, 2048), 1.020, 1.030)]
    modules = [tr.Event("jit__prefill_chunk_impl(3)", 1.0, 1.2),
               tr.Event("jit__segment_impl(7)", 2.0, 2.2)]
    _trace(monkeypatch, ops, modules)
    assert index_select_chunk_ms_per_call.read(bag(SPANS)) == pytest.approx(
        1.5)
    # the parent's chunk: XLA's passes under names of the compiler's
    _trace(monkeypatch, ops[2:], modules)
    assert index_select_chunk_ms_per_call.read(bag(SPANS)) is None
    monkeypatch.setattr(tr, "find_xplane", lambda d: None)
    assert index_select_chunk_ms_per_call.read(bag(SPANS)) is None
