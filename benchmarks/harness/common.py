"""What every runner shares: the cell's files, the device check, counters
read as differences, and the one result line."""

from __future__ import annotations

import importlib
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks"


class NoChip(RuntimeError):
    """No accelerator, too few chips, or a device without known peaks."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, tiny: bool = False) -> dict:
    """Everything ``BENCHMARK.json`` and the cell's own files say about one
    workload: its configuration, its traffic mix, its limits, and the
    metrics it reports."""
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    here = lambda m: name in m.get("workloads", [name])  # noqa: E731
    limits = BENCH / "limits" / f"{name}.json"
    return {
        "name": name, "chips": cell["chips"],
        "config": load_json(ROOT / configs[cell["config"]]["file"]),
        "traffic": load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
        "limits": _limits(limits, tiny),
        "end_to_end": [m for m in bench["end_to_end"] if here(m)],
        "per_layer": [m for m in bench["per_layer"] if here(m)],
    }


def _limits(path: pathlib.Path, tiny: bool) -> dict:
    """The cell's limits for ``correct``; the rehearsal at toy sizes has
    limits of its own under ``tiny``."""
    if not path.exists():
        return {}
    doc = load_json(path)
    return doc.get("tiny", {}) if tiny else doc


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH / "roofline" / "peaks.json")
    if kind not in table["devices"]:
        raise NoChip(f"device kind {kind!r} is not in roofline/peaks.json "
                     f"({sorted(table['devices'])}): no peak, no benchmark")
    return table["devices"][kind]


def device_info(chips: int, tiny: bool) -> dict:
    """The devices JAX reports; raises :class:`NoChip` unless they are
    ``chips`` TPUs of a known kind (``tiny`` rehearses anywhere)."""
    import jax

    devices = jax.devices()
    d0 = devices[0]
    info = {"platform": d0.platform, "kind": d0.device_kind,
            "count": chips}
    if len(devices) < chips:
        raise NoChip(f"{chips} chips needed, {len(devices)} found")
    if tiny:
        return info
    if d0.platform != "tpu":
        raise NoChip(f"platform is {d0.platform!r}, not 'tpu'")
    peaks_for(d0.device_kind)
    return info


def memory_peak(chips: int) -> int | None:
    """``peak_bytes_in_use`` on the fullest of the chips used."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def compile_stats() -> tuple[float, float]:
    """(compiles, compile seconds) so far in this process, from the
    listener ``enable_compilation_cache`` installs; cache hits do not
    count."""
    from tpudist import obs

    summary = obs.histogram("xla/compile_seconds", unit="s").summary()
    return (float(obs.counter("xla/compiles", unit="compiles").value()),
            float(summary.get("sum") or 0.0))


def hist_totals(name: str) -> tuple[float, float]:
    """(sum, count) of an obs histogram so far."""
    from tpudist import obs

    s = obs.histogram(name).summary()
    return float(s.get("sum") or 0.0), float(s.get("count") or 0.0)


def start_caches() -> str:
    """JAX's persistent compilation cache at the program's fixed place in
    the checkout (or where the environment says), for every program however
    quickly it compiled."""
    import jax

    from tpudist.runtime.cache import enable_compilation_cache

    cache_dir = enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def weighted_quantile(values, weights, q: float) -> float:
    """The smallest value at which the cumulative weight reaches ``q`` of
    the total."""
    pairs = sorted(zip(values, weights))
    total = sum(w for _, w in pairs)
    acc = 0.0
    for v, w in pairs:
        acc += w
        if acc >= q * total:
            return v
    return pairs[-1][0]


def read_layer_metrics(cell: dict, run: dict) -> dict:
    """Call each per-layer metric's own reader
    (``layer_metrics/<name>.py``: ``read(run) -> value or None``); a reader
    that finds nothing to read leaves its metric out."""
    out = {}
    for m in cell["per_layer"]:
        mod = importlib.import_module(
            f"benchmarks.layer_metrics.{m['name'].replace('.', '_')}")
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


# the rows of the run's ``say(phase="correct", compared=...)``: every runner
# says them just before it returns its result
_compared: list = []


def say(**fields) -> None:
    """An earlier output line (the last line is the result's)."""
    if fields.get("phase") == "correct":
        _compared[:] = fields.get("compared", [])
    print(json.dumps(fields), flush=True)


def print_result(result: dict) -> None:
    """The result as the last line of standard output.  The numbers that
    decided ``correct``, each beside its limit, come last in it under
    ``compared`` and are the last lines of standard error: of a run that is
    not correct the driver's record keeps the end of both."""
    sys.stdout.flush()
    if _compared:
        result = {**result, "compared": {
            r["number"]: {"value": r["value"], "limit": r["limit"]}
            for r in _compared}}
        for r in _compared:
            print(f"compared {r['number']}: {r['value']} limit {r['limit']}",
                  file=sys.stderr)
        sys.stderr.flush()
    print(json.dumps(result), flush=True)
