"""One number for ROADMAP S10 (a), run once on the chip: at what rate does a
Pallas kernel issue ROW copies?  24,576 rows of a ``[300000, 512]`` bf16
array (a decode step's chosen rows of one K/V pool in the long-document
cell) are copied into VMEM by ascending row ids, 16 and 32 copies in
flight, as single rows and, where those do not lower, as the aligned 2-,
8- and 16-row pieces that hold them (and one tile of an 8-row piece, 2 KB a
copy: the descriptors' rate and not the memory's); against ``jnp.take`` of
the same rows.  Prints ns a row of each.

``jnp.take`` itself is timed over four sources (ISSUE 42, step 1): the K/V
pool row of today (bf16 ``[., 512]``), K and V of a token as ONE row (bf16
``[., 1024]``), and the same bytes as 32-bit words (uint32 ``[., 256]`` and
``[., 512]``: a bf16 row shares its sublanes with its neighbour, a 32-bit
row does not).  ``--take-only`` stops after those four.

    python scripts/row_copy_rate.py [--take-only]
"""

import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS, SOURCE, WIDTH, STEP = 24576, 300000, 512, 2048
PIECES = ((1, 512), (2, 512), (8, 512), (16, 512), (8, 128))
INFLIGHT = (16, 32)
# jnp.take's sources: (name, dtype, columns); each of 300,000 rows
TAKE_SOURCES = (("bf16_512", jnp.bfloat16, 512), ("bf16_1024", jnp.bfloat16, 1024),
                ("u32_256", jnp.uint32, 256), ("u32_512", jnp.uint32, 512))


def _kernel(ids_ref, src, out_ref, buf, sems, *, piece, cols, inflight):
    n = STEP // piece                    # row copies a grid step
    first = pl.program_id(0) * n

    def copy(j):
        row = ids_ref[first + j]
        at = row if piece == 1 else pl.multiple_of(row // piece * piece, piece)
        return pltpu.make_async_copy(
            src.at[pl.ds(at, piece), pl.ds(0, cols)],
            buf.at[pl.ds(j * piece, piece), pl.ds(0, cols)],
            sems.at[j % inflight])

    def one(j, _):
        pl.when(j >= inflight)(lambda: copy(j - inflight).wait())
        copy(j).start()

    jax.lax.fori_loop(0, n, one, None)
    jax.lax.fori_loop(n - inflight, n, lambda j, _: copy(j).wait(), None)
    out_ref[0] = buf[pl.ds(0, 16)].astype(jnp.float32)


def row_copies(src, ids, *, piece, cols, inflight):
    steps = ROWS // (STEP // piece)
    return pl.pallas_call(
        functools.partial(_kernel, piece=piece, cols=cols, inflight=inflight),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 16, WIDTH), lambda g, ids: (g, 0, 0)),
            scratch_shapes=[pltpu.VMEM((STEP, WIDTH), src.dtype),
                            pltpu.SemaphoreType.DMA((inflight,))]),
        out_shape=jax.ShapeDtypeStruct((steps, 16, WIDTH), jnp.float32),
        name="row_copies")(ids, src)


def seconds(fn, *args, n=30):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    jax.block_until_ready([fn(*args) for _ in range(n)])
    return (time.perf_counter() - t0) / n


def main():
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    rng = np.random.default_rng(0)
    src = jnp.asarray(rng.normal(size=(SOURCE, WIDTH)), jnp.bfloat16)
    ids = jnp.sort(jnp.asarray(rng.choice(SOURCE, ROWS, False), jnp.int32))
    take = jax.jit(lambda s, i: jnp.take(s, i, axis=0))
    for name, dtype, cols in TAKE_SOURCES:
        wide = src if (dtype, cols) == (src.dtype, WIDTH) else jnp.asarray(
            rng.integers(0, 2 ** 16, size=(SOURCE, cols)), dtype)
        exact = bool((np.asarray(take(wide, ids))
                      == np.asarray(wide)[np.asarray(ids)]).all())
        took = seconds(take, wide, ids)
        print(json.dumps({"jnp_take": name, "exact": exact,
                          "ns_a_row": 1e9 * took / ROWS,
                          "gb_s": ROWS * cols * wide.dtype.itemsize * 1e-9
                          / took}), flush=True)
        del wide
    if "--take-only" in sys.argv[1:]:
        return
    plain = np.asarray(src.astype(jnp.float32))
    for piece, cols, inflight in [(*p, n) for p in PIECES for n in INFLIGHT]:
        fn = jax.jit(functools.partial(row_copies, piece=piece, cols=cols,
                                       inflight=inflight))
        try:
            got = np.asarray(fn(src, ids))
        except Exception as e:  # noqa: BLE001 - what the compiler refuses
            print(json.dumps({"piece": [piece, cols], "no": str(e)[-160:]}))
            continue
        # a grid step hands back the 16 buffer rows its first copies fill
        heads = np.asarray(ids).reshape(len(got), -1)[:, : 16 // piece]
        rows = (heads // piece * piece)[..., None] + np.arange(piece)
        exact = bool((got[..., :cols] == plain[
            rows.reshape(len(got), 16), :cols]).all())
        s = seconds(fn, src, ids)
        print(json.dumps({"piece": [piece, cols], "inflight": inflight,
                          "exact": exact, "ns_a_copy": 1e9 * s / ROWS,
                          "gb_s": ROWS * piece * cols * 2e-9 / s}), flush=True)


if __name__ == "__main__":
    main()
