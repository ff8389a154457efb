"""Device time of a decode step split by ROUTINE: the self-time
(``trace/reduce.self_times``' rule: an operation's time less what its
nested operations cover) of the operations inside the joined runs of the
segment program (``_joined.segments``), summed by the routine scope each
instruction carries in the program's own words (``tpudist.obs
.ROUTINE_SCOPES``, opened with ``jax.named_scope`` since PR 37), per decode
step those runs made (the sum of THEIR ``steps_run``).

A device event names an instruction by its text and carries no ``op_name``
(my chip run, PR 37: an ``XLA Ops`` event's stats are its offset and
duration alone), so the program hands out the map: ``ServeLoop.scope_map()``
gives ``{program: {instruction name: scope}}`` from its compiled HLO.  The
traced loop is gone when the readers run, so a second one is built from the
cell's own files as ``tools/aot_compile.py`` builds one (abstract
parameters; the programs it compiles are in the compilation cache already).
A fusion counts to the routine of the instruction it is named after.

On a program without ``scope_map`` (the parent of PR 37), without a trace
or with fewer than two joined runs every reader returns ``None``.
"""

from __future__ import annotations

import bisect
import collections
import importlib

from benchmarks.layer_metrics import _joined
from benchmarks.layer_metrics import _serve_trace as st

_MAPS: dict = {}


def scope_map(run: dict):
    """The segment program's ``{instruction name: scope}``; a run bag may
    carry the whole map under ``"scope_map"`` (the readers' tests)."""
    if "scope_map" in run:
        return run["scope_map"].get(st.SEGMENT)
    from tpudist.models import ServeLoop

    if not hasattr(ServeLoop, "scope_map"):
        return None
    cell = run["cell"]
    if cell["name"] not in _MAPS:
        import jax
        import jax.numpy as jnp

        from benchmarks.harness import weights

        runner = importlib.import_module(
            f"benchmarks.harness.{cell['config']['runner']}")
        make = getattr(runner, "make_params", weights.make_params)
        params = jax.eval_shape(
            lambda: make(0, run["dims"], jnp.bfloat16))
        loop = runner.build_loop(cell["config"], run["dims"], params,
                                 run["peaks"] is None)
        _MAPS.clear()
        _MAPS[cell["name"]] = loop.scope_map()
    return _MAPS[cell["name"]].get(st.SEGMENT)


def instruction(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.partition(" = ")[0].lstrip("%")


def by_run(run: dict, joined: list) -> list[dict]:
    """For each joined run, ``{event name: (self seconds, events)}`` of
    the operations that began inside it."""
    from benchmarks.trace import reduce as tr

    if "_by_run" in run:
        return run["_by_run"]
    starts = [r.start for r in joined]
    inside: list[list] = [[] for _ in joined]
    for name, start, end in _joined.trace_events(run)["ops"]:
        i = bisect.bisect_right(starts, start) - 1
        if i >= 0 and start < joined[i].end:
            inside[i].append(tr.Event(name, start, end))
    out = []
    for events in inside:
        count = collections.Counter(e.name for e in events)
        out.append({name: (seconds, count[name])
                    for name, seconds in tr.self_times(events).items()})
    run["_by_run"] = out
    return out


def per_step(run: dict):
    """``{scope: ms}`` of one decode step over the joined runs, with
    ``"step"`` (the runs' device time over their steps) and ``"other"``
    (what no scope covers: the step less the scopes' sum, so that the
    parts add up to the step by construction); ``None`` where nothing can
    be read.  The first reading of a bag also prints the whole split as
    an earlier output line (``"phase": "step_by_routine"``)."""
    if "_per_step" not in run:
        run["_per_step"] = _per_step(run)
    return run["_per_step"]


def _per_step(run: dict):
    joined = _joined.segments(run)
    if joined is None:
        return None
    scopes = scope_map(run)
    if not scopes:
        return None
    from benchmarks.trace import reduce as tr

    steps = sum(r.drain["steps_run"] for r in joined)
    out: dict = {}
    bare: dict = {}     # what no scope covers, by the breakdown's labels
    for times in by_run(run, joined):
        for name, (seconds, _) in times.items():
            scope = scopes.get(instruction(name))
            if scope:
                out[scope] = out.get(scope, 0.0) + 1e3 * seconds / steps
            else:
                label = tr.label(name)
                bare[label] = bare.get(label, 0.0) + 1e3 * seconds / steps
    out["step"] = 1e3 * sum(r.seconds for r in joined) / steps
    out["other"] = out["step"] - sum(
        v for k, v in out.items() if k != "step")
    from benchmarks.harness import common

    common.say(phase="step_by_routine", ms_per_step=out,
               unscoped_top=sorted(bare.items(), key=lambda kv: -kv[1])[:8],
               joined_runs=len(joined), steps_run=steps,
               seqs=[r.seq for r in joined],
               # what the joined segments held, a step: the rooflines'
               # divisors (a model without an indexer has the lanes alone)
               **{k: sum(r.drain[k] * r.drain["steps_run"]
                         for r in joined) / steps
                  for k in ("lanes", "rows_scored", "rows_selected")
                  if all(k in r.drain for r in joined)},
               clock_residual_us=1e6 * _joined.clock(
                   _joined.trace_events(run)["host"])[1])
    return out


def under(run: dict, *prefixes: str):
    """Milliseconds a step under the scopes that begin with one of
    ``prefixes`` (``"attn/"``: the level; ``"head"``: the one)."""
    split = per_step(run)
    if split is None:
        return None
    return sum(v for k, v in split.items() if k.startswith(prefixes)
               and k not in ("step", "other"))
