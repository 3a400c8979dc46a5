"""Record the small scoped device trace that tests/test_trace_scopes.py reads.

    python benchmarks/chip/tests/record_scoped_trace.py OUT_DIR

Run on a TPU host.  It traces three rounds of a jitted step with two named
scopes (``gfl.client_grads`` around a ``lax.scan``, ``gfl.combine`` after
it) fed by an eager draw inside the program's ``gfl.input`` span, each
round inside a ``bench.round`` host span.  It writes OUT_DIR/scoped.xplane.pb
and the step's compiled HLO text, OUT_DIR/scoped.hlo.txt, and prints each
plane and line with its first events.
"""
from __future__ import annotations

import glob
import os
import shutil
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
    from repro.telemetry import trace_span

    @jax.jit
    def step(x, w):
        with jax.named_scope("gfl.client_grads"):
            def body(c, xi):
                return c + jnp.tanh(xi @ w).astype(jnp.float32).sum(), None
            acc, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), x)
        with jax.named_scope("gfl.combine"):
            return (w * (1.0 + 1e-6 * acc)).astype(w.dtype)

    key = jax.random.PRNGKey(0)
    w = jnp.full((512, 512), 0.01, jnp.bfloat16)

    def draw(i):
        with trace_span("gfl.input", round=i):
            return jax.random.normal(jax.random.fold_in(key, i),
                                     (4, 512, 512), jnp.bfloat16)

    w = jax.block_until_ready(step(draw(0), w))
    raw = os.path.join(out_dir, "raw")
    # host spans and device ops only: Python function events would
    # outweigh them in the file
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(raw, profiler_options=opts)
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.round", round=i):
            w = jax.block_until_ready(step(draw(i), w))
    jax.profiler.stop_trace()
    hlo = step.lower(draw(0), w).compile().as_text()
    path = sorted(glob.glob(os.path.join(
        raw, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = os.path.join(out_dir, "scoped.xplane.pb")
    shutil.copy(path, out)
    with open(os.path.join(out_dir, "scoped.hlo.txt"), "w") as f:
        f.write(hlo)
    print(out, os.path.getsize(out), "hlo", len(hlo))
    for plane in ProfileData.from_file(out).planes:
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
