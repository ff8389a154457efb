"""``ServeLoop(max_prefill_lanes=)``: a bound on the lanes in chunked
admission at once (each holds a batch-1 prefill cache from its first chunk
to its finish).  Requests wait in the queue for a finish; tokens do not
change."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist import obs
from tpudist.models import Request, ServeLoop, TransformerConfig, TransformerLM

CFG = TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                        embed_dim=32, max_seq_len=96)
PROMPTS = [(40, 6), (33, 5), (25, 7), (48, 4), (9, 8), (30, 5)]


@pytest.fixture(scope="module")
def params():
    return TransformerLM(CFG).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]


def _run(params, bound, layout):
    loop = ServeLoop(CFG, params, num_slots=4, steps_per_sync=4,
                     prefill_chunk=8, decode_attention="dense",
                     cache_layout=layout, kv_block_size=8,
                     max_prefill_lanes=bound)
    rng = np.random.default_rng(0)
    reqs = [Request(rng.integers(0, 64, n).astype(np.int32), m, rid=f"r{i}")
            for i, (n, m) in enumerate(PROMPTS)]
    obs.tracer.clear()
    done = {c.rid: list(c.tokens) for c in loop.run(reqs)}
    # a lane is in admission from its `serve/admit` span to its
    # `serve/admit_finish` span: the most open at once
    most = held = 0
    for e in sorted(obs.tracer.events(), key=lambda e: e["ts"]):
        if e["name"] == "serve/admit":
            held += 1
        elif e["name"] == "serve/admit_finish":
            held -= 1
        most = max(most, held)
    return done, most


@pytest.mark.parametrize("layout", ["dense", "paged"])
@pytest.mark.parametrize("bound", [1, 2])
def test_bound_holds_and_tokens_do_not_change(params, bound, layout):
    free, most = _run(params, None, layout)
    assert most > 2                       # unbounded: every lane at once
    held, most = _run(params, bound, layout)
    assert most <= bound
    assert held == free


def test_bound_must_be_positive(params):
    with pytest.raises(ValueError, match="max_prefill_lanes"):
        ServeLoop(CFG, params, num_slots=2, max_prefill_lanes=0)
