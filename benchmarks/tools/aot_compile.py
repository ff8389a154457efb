#!/usr/bin/env python3
"""Compile a cell's programs at full size for a DESCRIBED v5e (no chip is
attached and nothing runs) and print the compiler's memory analysis.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/aot_compile.py --workload <name> [--layers N] [--rows N]

A compile that passes is not a chip run: it finds what the chip's compiler
refuses (a kernel's tiling, a program that does not fit) at no chip time.
"""

from __future__ import annotations

import argparse
import os
import pathlib
import sys
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmarks.harness import common, serve, train, weights  # noqa: E402

GIB = 2 ** 30


def _on(sharding, tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


def _report(name: str, jitted, *args, **static) -> None:
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        compiled = jitted.lower(*args, **static).compile()
    m = compiled.memory_analysis()
    print(f"{name}: arguments {m.argument_size_in_bytes / GIB:.2f} GiB, "
          f"outputs {m.output_size_in_bytes / GIB:.2f}, aliased "
          f"{m.alias_size_in_bytes / GIB:.2f}, temporaries "
          f"{m.temp_size_in_bytes / GIB:.2f}, kernels "
          f"{compiled.as_text().count('tpu_custom_call')}", flush=True)


def serve_cell(cell: dict, topo) -> None:
    one = SingleDeviceSharding(topo.devices[0])
    config = cell["config"]
    dims = weights.ModelDims.from_config(config)
    params = jax.eval_shape(
        lambda: weights.make_params(0, dims, jnp.bfloat16))
    loop = serve.build_loop(config, dims, params, tiny=False)
    opts = serve.loop_options(config, False)
    _report("segment", loop._segment, *_on(one, (
        loop.params, loop.cache, loop._tok, loop._active, loop._remaining,
        loop._first, loop._key, jnp.int32(opts["steps_per_sync"]),
        jnp.bool_(False))))
    chunk = opts["prefill_chunk"]
    _report("prefill_chunk", loop._prefill_chunk, *_on(one, (
        loop.params, loop._blank1, jnp.zeros((1, chunk), jnp.int32),
        jnp.int32(0))), chunk=chunk)


def train_cell(cell: dict, topo, layers, rows) -> None:
    from tpudist.parallel import MeshSpec
    from tpudist.train.state import TrainState

    config, mix = dict(cell["config"]), dict(cell["traffic"])
    if layers:
        config["n_layer"] = layers
    if rows:
        mix["rows_per_chip"] = rows
    dims = weights.ModelDims.from_config(config)
    n = MeshSpec.parse(mix["mesh"]).n_devices
    with mock.patch.object(jax, "devices", lambda *a: topo.devices[:n]):
        spec, mesh, model, tx, step = train.build(config, dims, mix, False)
    params = jax.eval_shape(
        lambda: weights.make_params(0, dims, jnp.float32))
    state = jax.eval_shape(
        lambda p: TrainState.create(model.apply, p, tx, rng=0), params)
    total = int(mix["rows_per_chip"]) * n
    batch = jax.ShapeDtypeStruct((total, int(mix["seq_len"])), jnp.int32)
    print(f"layers {dims.layers}, rows/chip {mix['rows_per_chip']}, chips "
          f"{n}, parameters {weights.count_params(dims) / 1e6:.0f} M, state "
          f"{weights.count_params(dims) * 16 / GIB:.2f} GiB", flush=True)
    _report("train_step", step,
            _on(NamedSharding(mesh, P()), state),
            *_on(NamedSharding(mesh, spec.batch_spec()), (batch, batch)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--rows", type=int, default=0)
    args = ap.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cell = common.load_cell(args.workload)
    if cell["config"]["runner"] == "serve":
        serve_cell(cell, topo)
    else:
        train_cell(cell, topo, args.layers, args.rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
