"""The readers that join a trace's runs to their segments (PR 37) on a
trace made by hand: five runs of the segment program of which the trace's
edges cut the first and the last, their annotations on a host plane whose
clock lies a thousand seconds from the ring's and jitters by microseconds,
a warm-up in the ring under the same ``seq`` numbers, and operations under
routine scopes.  One case a metric; and two programs that move the chosen
rows otherwise (one wide gather; no gather at all), which the readers tell
by the scopes and the kernels' names alone."""

import types

import pytest

from benchmarks.layer_metrics import (_joined, _scopes,
                                      index_scores_roofline_joined,
                                      segment_ms_per_step_joined,
                                      sparse_attend_roofline_joined,
                                      step_attn_ms, step_head_ms,
                                      step_index_ms, step_mlp_ms,
                                      step_other_ms, step_rows_ms)
from benchmarks.roofline import bound, index_scores, sparse_attend

OFFSET = -1000.0          # trace time = perf_counter + OFFSET
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
DIMS = types.SimpleNamespace(index_heads=16, index_dim=64, heads=32,
                             kv_heads=4, head_dim=128)
STEPS = 32                # dispatched a segment
# seq -> (run start, run end, steps_run): 10 was under way when the trace
# began, 14 when it ended; 13 froze after 20 steps
RUNS = {10: (0.0, 0.15, 32), 11: (0.15, 0.45, 32), 12: (0.45, 0.75, 32),
        13: (0.75, 1.0, 20), 14: (1.0, 1.2, 32)}
ROWS = {10: 9000, 11: 10000, 12: 20000, 13: 30000, 14: 31000}
WHOLE = (11, 12, 13)
# an instruction of the segment program -> (routine, ms a step)
OPS = {"fusion.1": ("attn/proj", 1.0), "fusion.8": ("attn/cache", 0.25),
       "paged_index_scores.3": ("attn/index", 0.5),
       "gather.5": ("attn/rows", 2.0),
       "sparse_gqa_attend.7": ("attn/core", 0.75),
       "paged_flash_decode.2": ("attn/core", 0.125),
       "fusion.2": ("mlp/dense", 3.0), "fusion.3": ("head", 0.5),
       "copy.9": (None, 0.25)}
SCOPES = {"_segment_impl": {k: v for k, (v, _) in OPS.items() if v}}


def _op(name: str) -> str:
    tail = (', custom_call_target="tpu_custom_call"'
            if name.split(".")[0] in ("paged_index_scores",
                                      "sparse_gqa_attend",
                                      "paged_flash_decode") else "")
    return f"%{name} = bf16[16,128]{{1,0}} fusion(bf16[16,128] %x){tail}"


def _host(name, start, end, jitter_us, **stats):
    """An annotation as the profiler shows it: on the trace's clock, with
    the span's ``pc_us`` (taken a moment before the annotation began)."""
    pc = start - OFFSET - jitter_us * 1e-6
    return (name, start, end, {"pc_us": pc * 1e6, **stats})


def make_bag(stamps: bool = True, step=OPS, texts=None) -> dict:
    """``step``: the instructions every step runs; ``texts``: an
    instruction's whole text where ``_op``'s will not do."""
    texts = texts or {}
    modules, ops, host, ring = [], [], [], []
    for seq, (start, end, steps_run) in RUNS.items():
        modules.append((f"jit__segment_impl({7})", start, end))
        # the loop's operations under a while that covers the run: every
        # step the same instructions, back to back from the run's start
        ops.append(("%while.1 = (s32[]) while((s32[]) %t)", start, end))
        t = start
        for _ in range(steps_run if seq != 10 else 12):
            for name, (_, ms) in step.items():
                ops.append((texts.get(name) or _op(name), t,
                            t + ms * 1e-3))
                t += ms * 1e-3
        fetch_end = end + 200e-6
        if seq >= 12:       # 10 and 11 were dispatched before the trace
            at = RUNS[seq - 1][0] + 0.01
            host.append(_host("serve/segment_plan", at - 2e-3, at, 1.0,
                              seq=seq))
            host.append(_host("serve/segment", at, at + 1e-3,
                              (-3.0, 0.0, 2.5)[seq % 3], steps=STEPS,
                              seq=seq))
        if seq <= 13:       # 14's fetch ended after the trace
            host.append(_host("serve/segment_fetch", start + 0.02,
                              fetch_end, (1.5, -2.0)[seq % 2], seq=seq))
        for warm in (True, False):
            begin = fetch_end - OFFSET - (500.0 if warm else 0.0)
            ring.append({
                "name": "serve/segment_drain", "ts": begin * 1e6 + 3.0,
                "dur": 400.0, "args": {
                    "seq": seq, "steps": STEPS,
                    "steps_run": 1 if warm else steps_run, "lanes": 12,
                    "rows_scored": 7 if warm else ROWS[seq],
                    "rows_selected": 7 if warm else 12 * 2048}})
    if not stamps:          # the parent: the names alone reach the plane
        host = [(n, s, e, {}) for n, s, e, _ in host]
    return {"cell": {"name": "toy"}, "dims": DIMS, "peaks": PEAKS,
            "trace_events": {"modules": modules, "ops": ops, "host": host},
            "spans": ring, "scope_map": {"_segment_impl": {
                k: v for k, (v, _) in step.items() if v}}}


STEPS_RUN = sum(RUNS[s][2] for s in WHOLE)
STEP_MS = 1e3 * sum(RUNS[s][1] - RUNS[s][0] for s in WHOLE) / STEPS_RUN


def _under(*routines) -> float:
    """Every whole run's every step runs every instruction once."""
    return sum(ms for scope, ms in OPS.values() if scope in routines)


def _attend_share(ms: float) -> float:
    call = bound.least_seconds(
        sparse_attend.flops(12 * 2048, 32, 128),
        sparse_attend.bytes_moved(12 * 2048, 12, 32, 4, 128), PEAKS)
    return 100.0 * call / (ms * 1e-3)


def test_cut_runs_are_left_out_and_seqs_joined():
    joined = _joined.segments(make_bag())
    assert [r.seq for r in joined] == list(WHOLE)
    assert [(r.start, r.end) for r in joined] == [RUNS[s][:2] for s in WHOLE]
    # each run carries ITS drain, not the warm-up's of the same seq
    assert [r.drain["steps_run"] for r in joined] == [32, 32, 20]
    assert [r.drain["rows_scored"] for r in joined] == [ROWS[s]
                                                        for s in WHOLE]


def test_clock_offset_is_recovered_from_pc_us_under_jitter():
    offset, residual = _joined.clock(make_bag()["trace_events"]["host"])
    assert offset == pytest.approx(OFFSET, abs=3e-6)
    assert 0.0 < residual < 3e-6


def test_segment_ms_per_step_joined_divides_by_steps_run():
    got = segment_ms_per_step_joined.read(make_bag())
    assert got == pytest.approx(STEP_MS)
    # what the runs times steps_per_sync rule reads of the same trace
    old = 1e3 * sum(e - s for s, e, _ in RUNS.values()) / (5 * STEPS)
    assert abs(old - got) / got > 0.15


def test_step_attn_ms():
    assert step_attn_ms.read(make_bag()) == pytest.approx(_under(
        "attn/proj", "attn/cache", "attn/index", "attn/rows", "attn/core"))


def test_step_mlp_ms():
    assert step_mlp_ms.read(make_bag()) == pytest.approx(_under("mlp/dense"))


def test_step_head_ms():
    assert step_head_ms.read(make_bag()) == pytest.approx(_under("head"))


def test_step_other_ms_and_the_four_sum_to_the_step():
    bag = make_bag()
    other = step_other_ms.read(bag)
    # the unscoped copy and the while's own time (the runs' idle tails)
    assert other > _under(None)
    assert (step_attn_ms.read(bag) + step_mlp_ms.read(bag)
            + step_head_ms.read(bag) + other) == pytest.approx(
        segment_ms_per_step_joined.read(bag))


def test_step_index_ms():
    assert step_index_ms.read(make_bag()) == pytest.approx(
        _under("attn/index"))


def test_step_rows_ms():
    assert step_rows_ms.read(make_bag()) == pytest.approx(_under("attn/rows"))


def test_index_scores_roofline_joined_reads_its_own_segments_rows():
    least = sum(RUNS[s][2] * bound.least_seconds(
        index_scores.flops(ROWS[s], 16, 64),
        index_scores.bytes_moved(ROWS[s], 12, 16, 64), PEAKS)
        for s in WHOLE)
    seconds = STEPS_RUN * OPS["paged_index_scores.3"][1] * 1e-3
    assert index_scores_roofline_joined.read(make_bag()) == pytest.approx(
        100.0 * least / seconds)


def test_sparse_attend_roofline_joined_times_rows_and_core():
    # the gathers and the kernel; the every-row kernel is left out by name
    assert sparse_attend_roofline_joined.read(make_bag()) == pytest.approx(
        _attend_share(OPS["gather.5"][1] + OPS["sparse_gqa_attend.7"][1]))


def test_one_wide_gather_reads_as_two_narrow_ones():
    """K and V of a token in ONE pool row: one gather of ``[lanes, topk,
    2 x kv_heads x head_dim]`` and a kernel with one operand fewer.  No
    reader looks at a shape: ``attn/rows`` is what the program scoped so,
    the kernel is known by its name, and the chosen rows' bytes are K's
    and V's either way."""
    wide = {
        "gather.5": "%gather.5 = bf16[12,2048,1024]{2,1,0} fusion("
                    "bf16[307200,1024]{1,0} %pool, s32[12,2048]{1,0} %ids)",
        "sparse_gqa_attend.7":
            "%sparse_gqa_attend.7 = bf16[48,8,128]{2,1,0} custom-call("
            "s32[12,3]{1,0} %meta, bf16[48,8,128]{2,1,0} %q, "
            "bf16[12,2048,1024]{2,1,0} %gather.5), "
            'custom_call_target="tpu_custom_call"'}
    bag, narrow = make_bag(texts=wide), make_bag()
    assert step_rows_ms.read(bag) == pytest.approx(step_rows_ms.read(narrow))
    share = sparse_attend_roofline_joined.read(bag)
    assert share == pytest.approx(
        sparse_attend_roofline_joined.read(narrow))
    assert share == pytest.approx(_attend_share(2.0 + 0.75)) and share < 100


def test_a_kernel_that_reads_the_rows_itself_is_the_core_alone():
    """No instruction under ``attn/rows``: the routine is ``attn/core``,
    and the gathers off the path show in the share."""
    step = {k: v for k, v in OPS.items() if v[0] != "attn/rows"}
    bag = make_bag(step=step)
    assert step_rows_ms.read(bag) == 0.0
    assert step_attn_ms.read(bag) == pytest.approx(_under(
        "attn/proj", "attn/cache", "attn/index", "attn/core"))
    assert sparse_attend_roofline_joined.read(bag) == pytest.approx(
        _attend_share(0.75))
    assert sparse_attend_roofline_joined.read(bag) == pytest.approx(
        sparse_attend_roofline_joined.read(make_bag()) * 2.75 / 0.75)


READERS = (segment_ms_per_step_joined, step_attn_ms, step_mlp_ms,
           step_head_ms, step_other_ms, step_index_ms, step_rows_ms,
           index_scores_roofline_joined, sparse_attend_roofline_joined)


@pytest.mark.parametrize("reader", READERS,
                         ids=[r.__name__.rsplit(".", 1)[1] for r in READERS])
def test_none_without_stamps(reader):
    """The parent's trace: annotations by name alone, no ``seq``, no
    ``pc_us``."""
    assert reader.read(make_bag(stamps=False)) is None


@pytest.mark.parametrize("reader", READERS[1:],
                         ids=[r.__name__.rsplit(".", 1)[1]
                              for r in READERS[1:]])
def test_none_without_scopes_or_peaks(reader):
    bag = make_bag()
    bag["scope_map"] = {}   # a program that opens no routine scope
    bag["peaks"] = None     # a rehearsal
    assert reader.read(bag) is None


def test_none_with_fewer_than_two_whole_runs():
    bag = make_bag()
    ev = bag["trace_events"]
    ev["host"] = [h for h in ev["host"]
                  if h[3].get("seq") not in (11, 12)
                  or h[0] != "serve/segment_fetch"]
    assert _joined.segments(bag) is None
    assert segment_ms_per_step_joined.read(bag) is None


def test_none_without_a_trace():
    assert _joined.segments({"trace": None, "cell": {"name": "toy"}}) is None
    assert _scopes.per_step({"trace": None, "cell": {"name": "toy"},
                             "scope_map": SCOPES}) is None
