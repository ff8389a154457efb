"""The device trace in the program's own terms (PR 37): what ``obs.span``
hands the profiler's annotation, the span around ``_stamp_table``, the
vocabulary of routine scopes and ``ServeLoop.scope_map()`` on the backend
at hand.  The scopes on the programs compiled for a described v5e (each
kernel under its routine, the programs with their metadata stripped equal
to the parent's) are in ``tests/test_aot_tpu_compile.py``, the one file
that may describe a topology."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpudist import obs
from tpudist.models import (Request, ServeLoop, TransformerConfig,
                            TransformerLM)

# -- obs.span and the profiler's host plane ---------------------------------


class FakeAnnotation:
    seen: list = []
    session = True      # a profiler trace is running

    def __init__(self, name, **kwargs):
        FakeAnnotation.seen.append((name, kwargs))

    @classmethod
    def is_enabled(cls):
        return cls.session

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def annotations(monkeypatch):
    FakeAnnotation.seen = []
    monkeypatch.setattr(FakeAnnotation, "session", True)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    return FakeAnnotation.seen


def test_span_hands_the_annotation_its_scalar_args_and_pc_us(annotations):
    tracer = obs.SpanTracer()
    with tracer.span("serve/segment", steps=32, seq=7, rid="r1"):
        pass
    (event,) = tracer.events()
    ((name, kwargs),) = annotations
    assert name == "serve/segment"
    assert set(kwargs) == {"steps", "seq", "rid", "pc_us"}
    # the entry stamp the ring keeps, to the microsecond's fraction
    assert kwargs["pc_us"] == event["ts"]
    # handed over as they came: nothing is formatted on the span's side
    assert kwargs["steps"] == 32 and type(kwargs["steps"]) is int
    assert kwargs["rid"] == "r1" and type(kwargs["pc_us"]) is float


def test_span_hands_over_the_name_alone_while_no_trace_runs(
        annotations, monkeypatch):
    """Nothing is picked, multiplied or packed for a profiler that is not
    listening: the annotation gets what it got before PR 37."""
    monkeypatch.setattr(FakeAnnotation, "session", False)
    tracer = obs.SpanTracer()
    with tracer.span("serve/segment", steps=32, seq=7):
        pass
    assert annotations == [("serve/segment", {})]
    assert tracer.events()[0]["args"]["seq"] == 7


def test_span_without_args_hands_pc_us_alone(annotations):
    tracer = obs.SpanTracer()
    with tracer.span("serve/admit_poll"):
        pass
    assert [set(k) for _, k in annotations] == [{"pc_us"}]


def test_span_keeps_what_is_not_a_scalar_for_the_ring(annotations):
    tracer = obs.SpanTracer()
    with tracer.span("x", lanes=[1, 2], version=None, seq=3):
        pass
    ((_, kwargs),) = annotations
    assert set(kwargs) == {"seq", "pc_us"}
    assert tracer.events()[0]["args"]["lanes"] == [1, 2]


def test_complete_spans_are_not_annotated(annotations):
    tracer = obs.SpanTracer()
    tracer.complete("serve/segment_drain", 1.0, 2.0, seq=1)
    assert annotations == [] and len(tracer.events()) == 1


def test_scope_of_reads_the_innermost_routine():
    stack = ("jit(_segment_impl)/while/body/TransformerLM/block0/attn/"
             "attn/index/index_select/mul")
    assert obs.scope_of(stack) == "attn/index"
    assert obs.scope_of("a/attn/index/cond/attn/rows/gather") == "attn/rows"
    assert obs.scope_of("TransformerLM/head/lm_head/dot_general") == "head"
    assert obs.scope_of("TransformerLM/lm_head/dot_general") is None
    assert obs.scope_of("jit(f)/attention/core") is None
    with pytest.raises(ValueError, match="attn/kernels"):
        obs.routine("attn/kernels")


# -- serve/segment_stamp ----------------------------------------------------


def test_segment_stamp_opens_once_a_dispatch_with_its_copies():
    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                            num_kv_heads=1, embed_dim=32, max_seq_len=128)
    params = TransformerLM(cfg).init(
        jax.random.key(0), np.zeros((1, 8), np.int32))["params"]
    loop = ServeLoop(cfg, params, num_slots=2, steps_per_sync=8,
                     prefill_chunk=8, cache_layout="paged", kv_block_size=16,
                     prefix_sharing=False)
    rng = np.random.default_rng(0)
    obs.tracer.clear()
    loop.run([Request(rng.integers(1, 64, n).astype(np.int32), 12, rid=i)
              for i, n in enumerate((5, 19, 30))])
    by = {n: [e for e in obs.tracer.events() if e["name"] == n]
          for n in ("serve/segment", "serve/segment_plan",
                    "serve/segment_stamp")}
    stamps, plans = by["serve/segment_stamp"], by["serve/segment_plan"]
    assert len(stamps) == len(by["serve/segment"]) == len(plans) > 0
    for stamp, plan in zip(stamps, plans):
        # a table a layer, under the plan of the same dispatch
        assert stamp["args"]["copies"] == cfg.num_layers
        assert stamp["args"]["seq"] == plan["args"]["seq"]
        assert stamp["args"]["depth"] == plan["args"]["depth"] + 1
        assert plan["ts"] <= stamp["ts"]
        assert stamp["ts"] + stamp["dur"] <= plan["ts"] + plan["dur"] + 1.0


# -- ServeLoop.scope_map ----------------------------------------------------

PROGRAMS = ("_segment_impl", "_prefill_chunk_impl", "_admit_finish_impl")


def test_scope_map_is_the_programs_own(monkeypatch):
    """``ServeLoop.scope_map()`` on the backend at hand: the three
    programs, every value a routine scope, the head's instructions under
    ``head``."""
    cfg = TransformerConfig(vocab_size=64, num_layers=2, num_heads=2,
                            num_kv_heads=1, embed_dim=32, max_seq_len=128)
    params = jax.eval_shape(TransformerLM(cfg).init, jax.random.key(0),
                            jnp.ones((1, 8), jnp.int32))["params"]
    loop = ServeLoop(cfg, params, num_slots=2, steps_per_sync=8,
                     prefill_chunk=8, cache_layout="paged", kv_block_size=16)
    scopes = loop.scope_map()
    assert set(scopes) == set(PROGRAMS)
    for program in PROGRAMS:
        assert scopes[program]
        assert set(scopes[program].values()) <= set(obs.ROUTINE_SCOPES)
    assert {"attn/proj", "attn/cache", "attn/core", "mlp/dense",
            "head"} <= set(scopes["_segment_impl"].values())
