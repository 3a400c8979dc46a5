#!/usr/bin/env python3
"""Compile each cell's round program at its real sizes for a described
TPU v5e, without a chip, and print what the compiler reports.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/compile_check.py [cell ...]

For each cell: the program's step at the cell's shapes (one chip, or a
2x2 v5e mesh for a four-chip cell), lowered and compiled for the described
topology; prints the memory analysis, the count of Pallas kernels
(``tpu_custom_call``) and of collective-permutes.  Nothing runs, so this
says nothing about results or times.
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import bench  # noqa: E402

sys.path.insert(0, os.path.join(bench.ROOT, "src"))


def _report(name, compiled):
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    print(name, {"argument_bytes": ma.argument_size_in_bytes,
                 "output_bytes": ma.output_size_in_bytes,
                 "temp_bytes": ma.temp_size_in_bytes,
                 "tpu_custom_calls": text.count("tpu_custom_call"),
                 "collective_permutes": text.count("collective-permute-start")
                 or text.count("collective-permute(")}, flush=True)


def trainer(cell, devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    T = cell.driver().Trainer(cell, devices)
    mine = jax.eval_shape(T.init, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s, h: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=h), mine, T.shardings)
    rep = NamedSharding(T.mesh, PartitionSpec())
    state = T.steps_lib.TrainState(
        params, jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep))
    shape = (T.P, T.L, T.b, T.S)
    batch = {k: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rep)
             for k in ("tokens", "labels")}
    with T.mesh:
        return T.step.lower(state, batch).compile()


def main(argv=None) -> int:
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench_json = bench.load_json(os.path.join(bench.ROOT, "BENCHMARK.json"))
    cells = (argv if argv else [w["name"] for w in bench_json["workloads"]])
    for name in cells:
        cell = bench.Cell(name, bench_json)
        devices = topo.devices[:cell.chips]
        _report(name, trainer(cell, devices))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
