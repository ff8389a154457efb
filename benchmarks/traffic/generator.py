"""One general traffic generator; a mix is a JSON file of parameters.

Copied in spirit from ``tpudist/sim/workload.py`` (arrival thinning, length
draws), with one change that the benchmark's bounds need: a mix has ONE
schedule of (gap to the next arrival, prompt length, output budget), the
mid-quantiles of its distributions shuffled once by the mix's own
``order_seed``; a run's seed draws the tokens (and the harness draws the
weights from it).  Every seed therefore offers the same work at the same
moments, as a replayed trace would: a tail such as a 90th percentile does not
depend on whether a seed happened to put its three longest prompts side by
side (with the order drawn from the seed, six seeds spread that tail by 4.6%
on the chip, PR 24; two runs of one seed differ by 0.03%).  Another arrival
pattern is another mix, with an ``order_seed`` of its own.

Serving mixes (``kind`` ``open_loop`` / ``closed_loop``)::

    {"kind": "open_loop", "rate_per_s": 3.2,
     "ramp_s": 4.0, "drain_limit_s": 30.0,
     "lengths": {"prompt": {"dist": "lognormal", "median": 1024,
                            "sigma": 0.8, "min": 64, "max": 6144},
                 "output": {"dist": "lognormal", "median": 64,
                            "sigma": 0.7, "min": 8, "max": 512}},
     "max_total": 8192}

``closed_loop`` replaces the rate by ``"clients": 64``.  Training mixes
(``kind`` ``train``) give ``seq_len``, ``rows_per_chip`` and ``ring``.
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def load(name: str) -> dict:
    path = pathlib.Path(__file__).parent / f"{name}.json"
    with open(path) as f:
        return json.load(f)


def _quantile(dist: dict, u: float) -> int:
    kind = dist["dist"]
    if kind == "uniform":
        v = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "lognormal":
        v = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(u))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(round(min(max(v, dist.get("min", v)), dist.get("max", v))))


def _stratified(dist: dict, n: int, rng: np.random.Generator) -> list[int]:
    """The ``n`` mid-quantiles of ``dist`` in the order ``rng`` gives."""
    vals = [_quantile(dist, (i + 0.5) / n) for i in range(n)]
    return [vals[i] for i in rng.permutation(n)]


@dataclasses.dataclass(frozen=True)
class Item:
    """One request: when it is due (seconds from the start of the offered
    load; ``None`` in a closed loop), its prompt and its output budget."""

    rid: int
    at: float | None
    prompt: np.ndarray
    max_new: int


def _arrival_times(mix: dict, horizon_s: float,
                   rng: np.random.Generator) -> list[float]:
    """Poisson arrivals at the mix's mean rate: the gaps are the quantiles
    of the exponential law in the mix's order."""
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * horizon_s)))
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    scale = horizon_s / sum(gaps)
    out, t = [], 0.0
    for i in rng.permutation(n):
        t += gaps[i] * scale
        out.append(t)
    return [a for a in out if a < horizon_s]


def _lengths(mix: dict, n: int, rng: np.random.Generator):
    """``n`` (prompt, output) pairs: the quantiles of the two laws, each
    in an order of its own, and ``rng`` shuffles the pairs."""
    laws = mix["lengths"]
    cap = int(mix["max_total"])
    pairs = [(p, min(o, cap - p)) for p, o in zip(
        _stratified(laws["prompt"], n, rng),
        _stratified(laws["output"], n, rng))]
    return [pairs[i] for i in rng.permutation(n)]


def serve_items(mix: dict, seed: int, horizon_s: float, vocab: int,
                scale: float = 1.0) -> list[Item]:
    """The requests of one run.  ``horizon_s`` covers ramp and window;
    a closed loop gets as many as its clients could finish (an upper
    estimate: the surplus is never sent).  ``scale`` shrinks every length
    for the CPU rehearsal."""
    order = np.random.default_rng([int(mix.get("order_seed", 0)), 0x0DE7])
    rng = np.random.default_rng([int(seed), 0x7AFF1C])
    if mix["kind"] == "open_loop":
        times = _arrival_times(mix, horizon_s, order)
        n = len(times)
    elif mix["kind"] == "closed_loop":
        n = int(mix["clients"]) + int(math.ceil(
            horizon_s * float(mix["max_completions_per_s"])))
        times = [None] * n
    else:
        raise ValueError(f"not a serving mix: {mix['kind']!r}")
    pairs = _lengths(mix, n, order)
    items = []
    for rid, (at, (p_len, o_len)) in enumerate(zip(times, pairs)):
        p_len = max(1, int(p_len * scale))
        o_len = max(1, int(o_len * scale))
        prompt = rng.integers(0, vocab, p_len).astype(np.int32)
        items.append(Item(rid, at, prompt, o_len))
    return items


def train_batches(mix: dict, seed: int, rows: int, vocab: int,
                  seq_len: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """A ring of distinct host batches ``(x, y) [rows, seq_len]`` of
    seeded uniform tokens; ``y`` is ``x`` shifted by one."""
    rng = np.random.default_rng([int(seed), 0x7A11])
    out = []
    for _ in range(int(mix["ring"])):
        x = rng.integers(0, vocab, (rows, seq_len + 1)).astype(np.int32)
        out.append((x[:, :-1], x[:, 1:]))
    return out
