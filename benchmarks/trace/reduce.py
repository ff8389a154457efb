"""From a profiler trace (``.xplane.pb``) to numbers: device busy time, time
by compiled program and by operation, kernel time by pattern, and the idle
gaps of the device attributed to what the host was doing.

Reads the file with ``jax.profiler.ProfileData`` and nothing else.  A device
plane is one named ``/device:TPU:<n>``; on it the line ``XLA Modules`` has
one event per run of a compiled program and the line ``XLA Ops`` one event
per operation, nested where an operation (a ``while``, a ``conditional``)
contains others.  Host planes hold the threads' ``TraceAnnotation`` spans
(the program's ``obs.span`` names) on the same clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host spans worth naming in a gap, in the order they win a tie
HOST_SPANS = ("serve/segment", "serve/prefill_chunk", "serve/admit_finish",
              "serve/admit", "serve/swap", "bench/")


@dataclasses.dataclass
class Event:
    name: str
    start: float  # seconds
    end: float


def find_xplane(trace_dir: str) -> str | None:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def _events(line) -> list[Event]:
    return [Event(e.name, e.start_ns * 1e-9,
                  (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]


def load(path: str) -> dict:
    """``{"devices": {index: {"ops": [...], "modules": [...]}},
    "host": [...]}`` with times in seconds."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[int, dict] = {}
    host: list[Event] = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(
                int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] += _events(line)
                elif line.name == MODULES_LINE:
                    dev["modules"] += _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [e for e in _events(line)
                         if e.name.startswith(HOST_SPANS)]
    return {"devices": devices, "host": host}


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` pairs."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events: list[Event]) -> dict[str, float]:
    """Seconds by name, each event's time less what its nested children
    cover (a ``while`` does not count its body twice)."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [event, seconds covered by children]

    def close(upto: float) -> None:
        while stack and stack[-1][0].end <= upto:
            ev, covered = stack.pop()
            dur = ev.end - ev.start
            out[ev.name] = out.get(ev.name, 0.0) + max(dur - covered, 0.0)
            if stack:
                stack[-1][1] += dur

    for ev in sorted(events, key=lambda e: (e.start, -e.end)):
        close(ev.start)
        stack.append([ev, 0.0])
    close(float("inf"))
    return out


def gaps_by_host_span(busy: list[tuple[float, float]], lo: float, hi: float,
                      host: list[Event]) -> dict[str, float]:
    """Idle seconds of one device inside ``[lo, hi]``, each gap given to
    the host span that covers most of it (``(none)`` where no span does)."""
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    out: dict[str, float] = {}
    for i in range(0, len(edges), 2):
        s, e = edges[i], edges[i + 1]
        if e <= s:
            continue
        cover: dict[str, float] = {}
        for h in host:
            ov = min(h.end, e) - max(h.start, s)
            if ov > 0:
                cover[h.name] = cover.get(h.name, 0.0) + ov
        name = max(cover, key=cover.get) if cover else "(none)"
        out[name] = out.get(name, 0.0) + (e - s)
    return out


_SHAPE = re.compile(r"\b(pred|[a-z]+\d+)\[([\d,]*)\]")
_OPCODE = re.compile(r"\)?\s([a-z][a-z\-]*)\(")


def parse_op(name: str) -> dict:
    """An ``XLA Ops`` event is named by its whole HLO instruction:
    ``%attn.53 = (bf16[32,8192,128]{...}, f32[32,1,8192]{...})
    custom-call(s32[1,2]{...} %copy-done.1, ...), custom_call_target=...``.
    Returns its name without the instance number, its opcode, its output
    shapes, how many operands it has, and whether it is a Pallas kernel."""
    head, eq, rest = name.partition(" = ")
    if not eq:
        return {"name": name, "opcode": "", "outputs": (), "operands": 0,
                "pallas": False}
    m = _OPCODE.search(rest)
    opcode = m.group(1) if m else ""
    left = rest[: m.start() + 1] if m else rest
    args = rest[m.end():] if m else ""
    depth, end = 1, len(args)
    for i, ch in enumerate(args):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            end = i
            break
    return {
        "name": re.sub(r"[.\d]+$", "", head.lstrip("%")),
        "opcode": opcode,
        "outputs": tuple(f"{t}[{d}]" for t, d in _SHAPE.findall(left)),
        "operands": args[:end].count("%"),
        "pallas": "tpu_custom_call" in rest,
    }


def label(name: str) -> str:
    """A short stable name for the breakdown: the instruction's name without
    its instance number, its opcode and what it produces, so that the same
    operation of every layer falls under one name."""
    op = parse_op(name)
    if not op["opcode"]:
        return name[:80]
    outs = ",".join(op["outputs"][:3])
    kind = "pallas" if op["pallas"] else op["opcode"]
    tail = f"/{op['operands']}" if op["pallas"] else ""
    return f"{op['name']}:{kind}{tail}->{outs}"[:120]


def pallas_seconds(by_op: dict[str, float], is_kernel) -> float:
    """Seconds of the Pallas custom calls that ``is_kernel(parsed op)``
    accepts (by the instruction's name, which is the ``pallas_call``'s
    ``name=``; ``roofline/paged_decode`` still tells its kernel by operands
    and outputs)."""
    total = 0.0
    for name, sec in by_op.items():
        op = parse_op(name)
        if op["pallas"] and is_kernel(op):
            total += sec
    return total


def reduce(path: str, chips: int, top: int = 10) -> dict | None:
    """The numbers of one trace, averaged over the ``chips`` devices used;
    ``None`` if no operation ran on a device."""
    data = load(path)
    devs = [data["devices"][i] for i in sorted(data["devices"])][:chips]
    devs = [d for d in devs if d["ops"]]
    if not devs:
        return None
    lo = min(e.start for d in devs for e in d["ops"])
    hi = max(e.end for d in devs for e in d["ops"])
    busy_s, by_op, by_module, gaps = 0.0, {}, {}, {}
    for d in devs:
        busy = union((e.start, e.end) for e in d["ops"])
        busy_s += sum(e - s for s, e in busy) / len(devs)
        for name, sec in self_times(d["ops"]).items():
            by_op[name] = by_op.get(name, 0.0) + sec / len(devs)
        for e in d["modules"]:
            by_module[e.name] = (by_module.get(e.name, 0.0)
                                 + (e.end - e.start) / len(devs))
        for name, sec in gaps_by_host_span(
                busy, lo, hi, data["host"]).items():
            gaps[name] = gaps.get(name, 0.0) + sec / len(devs)
    grouped: dict[str, float] = {}
    for name, sec in by_op.items():
        short = label(name)
        grouped[short] = grouped.get(short, 0.0) + sec
    rank = lambda d: [[k, v] for k, v in sorted(  # noqa: E731
        d.items(), key=lambda kv: -kv[1])[:top]]
    return {"busy_s": busy_s, "window_s": hi - lo, "by_op": by_op,
            "by_module": by_module, "gaps": gaps,
            "breakdown": {"device_ops": rank(grouped),
                          "idle_gaps": rank(gaps)}}


def seconds_matching(by_name: dict[str, float], pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(sec for name, sec in by_name.items() if rx.search(name))
