"""``correct`` comes out true on a sound run and false when the timed path
is broken underneath or computed in the next lower precision.  Tiny sizes on
the CPU; the same comparisons ran at the cells' own sizes on the chip
(PERF.md, section 2)."""

import json

import numpy as np

from benchmarks.harness import serve, train


def _compared(out: dict) -> dict:
    line = next(ln for ln in out["lines"] if ln.get("phase") == "correct")
    return {r["number"]: r for r in line["compared"]}


def test_serve_sound_run_is_correct(run_tiny):
    out = run_tiny("sc3b_code_steady")
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0


def test_serve_altered_token_is_not_correct(run_tiny, monkeypatch):
    real = serve.Load.sink

    def altered(self, comp):
        if len(comp.tokens):
            comp.tokens = np.asarray(comp.tokens).copy()
            comp.tokens[:] = (comp.tokens + 1) % 512
        real(self, comp)

    monkeypatch.setattr(serve.Load, "sink", altered)
    out = run_tiny("sc3b_code_steady")
    assert out["correct"] is False
    row = _compared(out)["worst_logit_gap"]
    assert row["value"] > row["limit"]


def test_serve_fp8_control_is_not_correct(run_tiny, monkeypatch):
    """The control: the reference's own float8 tokens, scored like served
    ones, lie farther below the float32 best than the limit allows."""
    seen = {}
    real = serve.reference_gaps

    def both(params, dims, sample, quant=None):
        seen["control"] = real(params, dims, sample, quant="fp8")
        return real(params, dims, sample)

    monkeypatch.setattr(serve, "reference_gaps", both)
    out = run_tiny("sc3b_code_steady", seconds=6.0)
    limit = _compared(out)["worst_logit_gap"]["limit"]
    assert out["correct"] is True
    assert seen["control"]["worst_gap"] > limit


def test_train_sound_run_is_correct(run_tiny):
    out = run_tiny("sc1b_train_8k", seconds=2.0)
    assert out["correct"] is True and out["failed"] == 0


def test_train_step_that_returns_its_state_is_not_correct(run_tiny,
                                                          monkeypatch):
    real = train.build

    def broken(*args):
        from tpudist.parallel import make_composed_train_step

        spec, mesh, model, tx, _ = real(*args)
        keep = make_composed_train_step(spec, mesh, train.lm_loss_fn(model),
                                        donate=False)

        def unchanged(state, x, y):
            _, metrics = keep(state, x, y)
            return state, metrics

        return spec, mesh, model, tx, unchanged

    monkeypatch.setattr(train, "build", broken)
    out = run_tiny("sc1b_train_8k", seconds=2.0)
    assert out["correct"] is False
    row = _compared(out)["param_change_norm_worst_leaf"]
    assert row["value"] > row["limit"]


def test_train_row_left_out_is_not_correct(run_tiny, monkeypatch):
    """The step trains on the batch's first row only: the loss is then the
    mean over a part of the batch."""
    real = train.build

    def broken(*args):
        spec, mesh, model, tx, step = real(*args)

        def first_row_only(state, x, y):
            return step(state, x.at[1:].set(x[:1]), y.at[1:].set(y[:1]))

        return spec, mesh, model, tx, first_row_only

    monkeypatch.setattr(train, "build", broken)
    out = run_tiny("sc1b_train_8k", seconds=2.0)
    assert out["correct"] is False
    row = _compared(out)["loss_step1"]
    assert row["value"] > row["limit"]


def test_train_bf16_state_control_is_not_correct(run_tiny, monkeypatch):
    import jax.numpy as jnp

    from benchmarks.harness import weights

    real = weights.make_params
    calls = {"n": 0}

    def lower(seed, dims, dtype):
        calls["n"] += 1
        # calls 1 and 2 are the reference's (start, and start again);
        # every later one is the program's
        return real(seed, dims, jnp.bfloat16 if calls["n"] > 2 else dtype)

    monkeypatch.setattr(weights, "make_params", lower)
    out = run_tiny("sc1b_train_8k", seconds=2.0)
    assert out["correct"] is False
    row = _compared(out)["param_change_norm_worst_leaf"]
    assert row["value"] > row["limit"]


def test_the_numbers_compared_end_the_result_line_and_stderr(capsys):
    """``print_result`` repeats what the runner said under ``phase:
    correct``: last in the result's line, and as standard error's last
    lines."""
    from benchmarks.harness import common

    rows = [{"number": "worst_logit_gap", "value": 0.25, "limit": 0.12},
            {"number": "failed_requests", "value": 0, "limit": 0}]
    common.say(phase="correct", compared=rows, worst_gap=0.25)
    common.print_result({"correct": False, "attempted": 3, "failed": 0,
                         "metrics": {}, "device": {}})
    cap = capsys.readouterr()
    line = json.loads(cap.out.strip().splitlines()[-1])
    assert list(line)[-1] == "compared"
    assert line["compared"] == {
        "worst_logit_gap": {"value": 0.25, "limit": 0.12},
        "failed_requests": {"value": 0, "limit": 0}}
    assert cap.err.strip().splitlines()[-2:] == [
        "compared worst_logit_gap: 0.25 limit 0.12",
        "compared failed_requests: 0 limit 0"]
