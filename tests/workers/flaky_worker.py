"""Minimal (jax-free) gang worker for launcher blacklist tests: records
its stable spawn id / attempt / world, then fails iff
``WORKER_FAIL_SPAWN_IDS`` lists its spawn id — either bare (``"1"``, a
persistently bad "host") or pinned to one attempt (``"1@0"``, a host
that is bad only then — lets tests steer exactly which attempts fail).
A worker that fails first waits until all ``world`` workers of its
attempt have recorded theirs, so the record never depends on how fast
the healthy ones start."""

import json
import os
import sys
import time

sid = os.environ.get("TPUDIST_SPAWN_ID", "?")
attempt = int(os.environ["TPUDIST_RESTART_ATTEMPT"])
world = int(os.environ["TPUDIST_NUM_PROCESSES"])
out = os.environ.get("WORKER_OUT_DIR")
if out:
    with open(os.path.join(out, "events.jsonl"), "a") as fh:
        fh.write(json.dumps({
            "sid": sid,
            "attempt": attempt,
            "world": world,
            "rank": int(os.environ["TPUDIST_PROCESS_ID"]),
        }) + "\n")
fail_ids = os.environ.get("WORKER_FAIL_SPAWN_IDS", "").split(",")
if not (sid in fail_ids or f"{sid}@{attempt}" in fail_ids):
    sys.exit(0)
if out:
    # A failing exit makes the launcher tear the gang down at once.  Hold
    # it until every peer of this attempt has recorded its event, or a
    # slow-starting healthy worker is SIGTERMed before it wrote anything.
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        with open(os.path.join(out, "events.jsonl")) as fh:
            seen = sum(json.loads(line)["attempt"] == attempt for line in fh)
        if seen >= world:
            break
        time.sleep(0.01)
sys.exit(3)
