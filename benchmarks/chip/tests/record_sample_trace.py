"""Record the small device trace that tests/test_trace.py reads.

    python benchmarks/chip/tests/record_sample_trace.py OUT_DIR

Run on a TPU host.  It traces a few steps of a jitted matmul and of the
fused round-fold and graph-combine kernels at small shapes, with one
benchmark-side host span around each step, writes the profiler's
``.xplane.pb`` under OUT_DIR and prints each plane and line with its first
events, so a reader can see how devices and kernels are named.
"""
from __future__ import annotations

import glob
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    if jax.devices()[0].platform != "tpu":
        print(f"no TPU: platform {jax.devices()[0].platform}",
              file=sys.stderr)
        return 1
    from repro.kernels import ops

    mm = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    P, L, D = 8, 8, 4096
    grads = jax.random.normal(jax.random.PRNGKey(0), (P, L, D))
    w = jnp.zeros((P, D))
    A = jnp.full((P, P), 1.0 / P)

    def fold():
        psi, _ = ops.round_fold(w, grads, mu=0.1, bound=10.0)
        return ops.graph_combine(A, psi, psi)

    jax.block_until_ready((mm(x), fold()))
    jax.profiler.start_trace(out_dir)
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.round", round=i):
            jax.block_until_ready((mm(x), fold()))
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(
        out_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    print(path, os.path.getsize(path))
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for e in evs[:4]:
                print("    ", repr(e.name), e.start_ns, e.duration_ns,
                      dict(e.stats))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
