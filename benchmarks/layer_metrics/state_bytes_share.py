"""Of the bytes the cache manager holds for the decoding lanes, the part
that is STATE: ``state_bytes`` (the lanes whose state the slot cache held
times what the linear layers keep for one) over ``state_bytes`` plus the
bytes of the pages under those lanes' lengths (``pages`` a layer times the
block's rows times the full layers' K and V bytes a token), summed over the
segments drained inside the window (``serve/segment_drain``'s args).  A
model whose every layer keeps rows reports no ``state_bytes`` and the
reader returns ``None``."""

from benchmarks.layer_metrics import _loop_spans as ls


def read(run: dict):
    w = ls.window(run)
    if w is None:
        return None
    drains = [a for a in ls.drained(w) if "state_bytes" in a]
    if not drains:
        return None
    dims, block = run["dims"], run["options"]["kv_block_size"]
    token_bytes = (sum(1 for lin in dims.linear if not lin)
                   * 2 * dims.heads * dims.head_dim * 2)
    state = sum(a["state_bytes"] for a in drains)
    pages = sum(a["pages"] for a in drains) * block * token_bytes
    return 100.0 * state / (state + pages) if state + pages else None
