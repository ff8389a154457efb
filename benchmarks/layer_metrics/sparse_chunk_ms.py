"""Device time of the prefill-chunk program a SPARSE chunk in the traced
seconds: the mean over the program's runs inside which the kernel
``sparse_gqa_prefill`` ran (every chunk after a prompt's first: index
scores of the rows cached so far, the exact selection as a mask, one masked
flash pass, a layer).  Half of a loop iteration in the long-document cell,
where an iteration is one such chunk and one segment.  ``None`` where no
such run was traced (a program or a model without an indexer)."""

from benchmarks.layer_metrics import _index_spans as ix


def read(run: dict):
    n, seconds = ix.sparse_chunks(run)
    return 1e3 * seconds / n if n else None
