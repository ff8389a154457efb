"""Turnkey real-MNIST: fetch (or explain how to mount) the IDX files.

The reference trains on torchvision MNIST to >=97% test accuracy
(`/root/reference/pytorch_elastic/mnist_ddp_elastic.py:166-171`); this
image has no bundled dataset, so real-MNIST parity is a gate that arms
itself the moment data exists (``tests/test_real_mnist.py``).  Run this
script to make that happen:

    python scripts/fetch_mnist.py [--dest data/MNIST/raw]

It tries the public mirrors in order and verifies the download by
actually parsing the IDX files.  In a zero-egress environment it exits
with the mount instructions instead (copy the four
``train-images-idx3-ubyte[.gz]``-family files into the dest directory, or
point ``TPUDIST_MNIST_DIR`` at an existing copy).
"""

from __future__ import annotations

import argparse
import sys
import urllib.error
import urllib.request
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

MIRRORS = (
    "https://ossci-datasets.s3.amazonaws.com/mnist/",
    "https://storage.googleapis.com/cvdf-datasets/mnist/",
    "https://yann.lecun.com/exdb/mnist/",
)
FILES = (
    "train-images-idx3-ubyte.gz",
    "train-labels-idx1-ubyte.gz",
    "t10k-images-idx3-ubyte.gz",
    "t10k-labels-idx1-ubyte.gz",
)


def fetch(dest: Path, timeout_s: float = 30.0, quiet: bool = False) -> bool:
    """Download the four IDX archives into ``dest``; returns success.
    Files already present (and parseable) are kept."""
    dest.mkdir(parents=True, exist_ok=True)
    from tpudist.data.mnist import load_mnist_idx

    try:
        load_mnist_idx(dest, "train")
        load_mnist_idx(dest, "test")
        if not quiet:
            print(f"already complete: {dest}")
        return True
    except Exception:  # noqa: BLE001 - missing OR corrupt: re-fetch below
        pass
    for name in FILES:
        out = dest / name
        if out.exists() and _valid_idx_bytes(out.read_bytes()):
            continue
        for mirror in MIRRORS:
            url = mirror + name
            try:
                if not quiet:
                    print(f"fetching {url} ...", flush=True)
                with urllib.request.urlopen(url, timeout=timeout_s) as r:
                    data = r.read()
                # validate BEFORE accepting: a captive portal answers 200
                # with an HTML page (and a truncated transfer is not a
                # dataset either) — accepting bad bytes here would poison
                # this file and skip the healthy mirrors behind it
                if not _valid_idx_bytes(data):
                    if not quiet:
                        print(f"  {url}: not a complete gzip/IDX file "
                              f"(captive portal?) — trying next mirror",
                              file=sys.stderr)
                    continue
                out.write_bytes(data)
                break
            except (urllib.error.URLError, OSError, TimeoutError) as e:
                if not quiet:
                    print(f"  {type(e).__name__}: {e}", file=sys.stderr)
        else:
            return False
    try:  # final verification: fully parse the dataset
        load_mnist_idx(dest, "train")
        load_mnist_idx(dest, "test")
    except Exception as e:  # noqa: BLE001 - any parse failure = bad download
        if not quiet:
            print(f"downloaded files failed to parse: {e}", file=sys.stderr)
        # per-file validation passed but the SET doesn't parse (e.g. an
        # images/labels count mismatch across files) — no way to tell
        # which file is the odd one out, so clear all four; every accepted
        # file was individually validated, so a retry re-fetches cleanly
        for name in FILES:
            (dest / name).unlink(missing_ok=True)
        return False
    return True


def _valid_idx_bytes(data: bytes) -> bool:
    """Full standalone validation of one (possibly gzipped) IDX file:
    decompresses, checks the IDX magic (``\\x00\\x00\\x08`` + dim count
    1 or 3), and verifies the payload length matches the declared dims —
    catching captive-portal pages AND truncated transfers."""
    import gzip
    import struct

    try:
        if data[:2] == b"\x1f\x8b":
            data = gzip.decompress(data)
        if len(data) < 8 or data[:3] != b"\x00\x00\x08":
            return False
        ndim = data[3]
        if ndim not in (1, 3):
            return False
        header = 4 + 4 * ndim
        dims = struct.unpack(f">{ndim}I", data[4:header])
        count = 1
        for d in dims:
            count *= d
        return len(data) == header + count
    except Exception:  # noqa: BLE001 - any decode failure = invalid
        return False


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--dest", default="data/MNIST/raw",
                    help="directory for the IDX files (the default is on "
                         "load_mnist's search path)")
    args = ap.parse_args()
    dest = Path(args.dest)
    if fetch(dest):
        print(f"real MNIST ready in {dest} — the parity gate "
              "(tests/test_real_mnist.py) is now armed")
        return 0
    print(
        "\nNo egress (or all mirrors unreachable).  To arm the real-MNIST\n"
        "parity gate, mount the four IDX files (gz or raw) into\n"
        f"  {dest}\n"
        "or set TPUDIST_MNIST_DIR to an existing MNIST/raw directory.",
        file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
