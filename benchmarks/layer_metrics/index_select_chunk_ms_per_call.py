"""Device time of one call of the exact top-k's threshold kernel in a
prefill chunk (one layer of one sparse chunk): the kernel
``index_select_threshold`` found BY NAME (``_named_kernels``), inside runs
of the prefill-chunk program only.  There it searches the ``k``-th largest
score and the cut among equal ones of 2048 queries over the rows cached so
far, a block of queries at a time in VMEM; the decode step calls the same
kernel on its lanes' rows (part of ``step_index_ms``), which does not
count here.  ``None`` where the trace holds none (a program whose
selection is XLA's passes, or a model without an indexer)."""

from benchmarks.layer_metrics import _named_kernels as nk
from benchmarks.layer_metrics import _serve_trace as st

KERNEL = nk.kernel_pattern("index_select_threshold")


def read(run: dict):
    calls, seconds = nk.calls(run, KERNEL, module=st.PREFILL)
    return 1e3 * seconds / calls if calls else None
