"""Device time of one call of the gated delta rule's decode kernel (one
linear-attention layer of one decode step: every lane's state read once and
written once), found in the trace BY ITS NAME, ``delta_step``
(``_delta_spans``); the mean over the traced seconds."""

from benchmarks.layer_metrics import _delta_spans as ds
from benchmarks.layer_metrics import _named_kernels as nk


def read(run: dict):
    calls, seconds = nk.calls(run, ds.STEP)
    return 1e6 * seconds / calls if calls else None
