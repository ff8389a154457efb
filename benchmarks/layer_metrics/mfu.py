"""Model FLOP/s utilisation: FLOPs a token needs from shapes, times the
tokens per second of the run, over chips times the bf16 peak."""

from benchmarks.harness import weights
from benchmarks.roofline import model_flops


def read(run: dict):
    if not run["peaks"]:
        return None
    dims = run["dims"]
    per_token = model_flops.train_flops_per_token(
        weights.matmul_params(dims), dims.layers, run["seq"], dims.heads,
        dims.head_dim)
    return 100.0 * per_token * run["stats"]["train_tok_s"] / (
        run["chips"] * run["peaks"]["flops_bf16"])
