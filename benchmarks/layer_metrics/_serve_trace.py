"""What the serving readers share: the traced programs' names and the
window's events reduced to sums."""

from benchmarks.trace import reduce as tr

SEGMENT = r"_segment_impl"
PREFILL = r"_prefill_chunk_impl"
FINISH = r"_admit_finish_impl"


def module_seconds(trace: dict, pattern: str) -> float:
    return tr.seconds_matching(trace["by_module"], pattern)


def decode_context(run: dict) -> tuple[float, float]:
    """(mean lanes, mean sum of context tokens) per decode step over the
    window, from the loop's ``segment`` events: each says how many tokens a
    lane had before ``steps`` more; its prompt is in its ``admit`` event."""
    prompt = {e["trace"]: e["prompt_len"] for e in run["events"]
              if e["kind"] == "admit"}
    steps = lanes = ctx = 0.0
    seen = set()
    for e in run["events"]:
        if e["kind"] != "segment" or e["trace"] not in prompt:
            continue
        n = e["steps"]
        lanes += n
        ctx += n * (prompt[e["trace"]] + e["tokens"]) + n * (n - 1) / 2
        if e["seq"] not in seen:
            seen.add(e["seq"])
            steps += n
    if not steps:
        return 0.0, 0.0
    return lanes / steps, ctx / steps
