"""The gated grouped expert product's share of its roofline in the decode
step: the device time of ``moe_experts_gate_up`` and ``moe_experts_down``
(found by name, inside runs of the segment program only: a prefill chunk
calls them too, on more rows) per call of the pair, against the bytes of
the held experts such a call touches and the operations of the tokens it
was given (``expert_tokens`` of the window's drained segments)."""

from benchmarks.harness import common
from benchmarks.layer_metrics import _expert_spans as es
from benchmarks.layer_metrics import _named_kernels as nk
from benchmarks.layer_metrics import _serve_trace as st
from benchmarks.roofline import bound, moe_experts

KERNELS = nk.kernel_pattern("moe_experts_gate_up", "moe_experts_down")


def read(run: dict):
    dims, sums = run["dims"], es.sums(run)
    if sums is None:
        return None
    events, seconds = nk.calls(run, KERNELS, module=st.SEGMENT)
    if not events:
        return None
    calls = events / 2  # the two kernels of one layer's product
    tokens = sums[0] / (sums[2] * es.expert_layers(run))
    held = dims.held[1]
    common.say(phase="moe_experts_roofline", calls=calls, kernel_s=seconds,
               tokens_per_call=tokens,
               experts_touched=moe_experts.experts_touched(tokens, held))
    return bound.share(
        moe_experts.flops(tokens, dims.embed, dims.expert_ff),
        moe_experts.bytes_moved(tokens, held, dims.embed, dims.expert_ff),
        seconds / calls, run["peaks"])
