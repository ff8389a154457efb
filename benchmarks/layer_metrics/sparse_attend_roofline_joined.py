"""The chosen rows' attention's share of its roofline in the decode step,
like for like: the self-time under the routine scopes ``attn/rows`` (the
gathers of the chosen rows, the staged rows patched in) and ``attn/core``
(the kernel ``sparse_gqa_attend``; the every-row kernel of a step whose
lanes all hold fewer rows than a query attends is left out by its name)
inside the JOINED runs of the segment program, against the operations and
bytes of the rows THOSE segments attend (``rows_selected`` and ``lanes`` of
each run's own drain, once a call of the kernel).  The scopes are the
program's own and the kernels are known by their names: no buffer's shape
is looked at, so a program that gathers K and V as one wide row, or whose
kernel reads the rows itself (nothing under ``attn/rows``), is timed as
what it is.  K's and V's bytes of each chosen row are counted once,
wherever they are stored: a route that gathers first reads as a third or
less."""

from benchmarks.layer_metrics import _index_spans as ix
from benchmarks.layer_metrics import _joined, _scopes
from benchmarks.layer_metrics import _named_kernels as nk
from benchmarks.roofline import bound, sparse_attend

ROUTINE = ("attn/rows", "attn/core")
EVERY_ROW = nk.kernel_pattern("paged_flash_decode")


def read(run: dict):
    joined = _joined.segments(run)
    if joined is None or not run.get("peaks"):
        return None
    scopes = _scopes.scope_map(run)
    if not scopes:
        return None
    dims = run["dims"]
    least = seconds = 0.0
    for r, times in zip(joined, _scopes.by_run(run, joined)):
        if "rows_selected" not in r.drain:
            return None
        call = bound.least_seconds(
            sparse_attend.flops(r.drain["rows_selected"], dims.heads,
                                dims.head_dim),
            sparse_attend.bytes_moved(r.drain["rows_selected"],
                                      r.drain["lanes"], dims.heads,
                                      dims.kv_heads, dims.head_dim),
            run["peaks"])
        for name, (spent, calls) in times.items():
            if ix.ATTEND.match(name):
                least += call * calls
            if (scopes.get(_scopes.instruction(name)) in ROUTINE
                    and not EVERY_ROW.match(name)):
                seconds += spent
    return 100.0 * least / seconds if least and seconds else None
