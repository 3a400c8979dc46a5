#!/usr/bin/env python3
"""On-chip benchmark: run one cell of BENCHMARK.json and print its result.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

It refuses to run without a TPU (or with fewer chips than the cell asks
for), makes its inputs and weights from ``--seed``, warms up every shape
the window uses, measures for ``--seconds``, checks what the timed path
produced against the cell's plain reference, and prints as its last line
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
end-to-end metrics, or with ``--trace 1`` the per-layer ones), ``device``,
with ``--trace 1`` a ``breakdown``, and last the ``checks`` compared, each
with its limit.  Earlier lines carry the set-up breakdown and the rest.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
# the TPU runtime logs to a fixed path under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, HERE)
import bench  # noqa: E402

sys.path.insert(0, os.path.join(bench.ROOT, "src"))


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def read_metrics(cell, rec: dict, trace: bool) -> dict:
    out = {}
    kind = "layer_metrics" if trace else "e2e_metrics"
    for m in (cell.per_layer if trace else cell.e2e):
        mod = bench.load_module(os.path.join(HERE, kind, m["name"] + ".py"),
                                m["name"])
        value = mod.read(rec)
        if value is None:
            if not trace:
                raise RuntimeError(f"end-to-end metric {m['name']} read "
                                   "nothing")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(argv=None, *, require_tpu: bool = True,
             overrides: dict | None = None) -> dict:
    """Run a cell and return its result object.  ``require_tpu=False``
    and ``overrides`` (partial traffic, config, limits and peaks) serve the
    harness's own tests on the CPU."""
    args = parse(argv)
    overrides = dict(overrides or {})
    peaks_override = overrides.pop("peaks", None)
    cell = bench.Cell(args.workload, overrides=overrides)
    import jax
    if require_tpu:
        bench.use_cache(jax)
    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise SystemExit(f"no TPU: JAX finds platform {platform!r} "
                         f"({len(devices)} device(s)); this benchmark runs "
                         "only on the chip")
    if len(devices) < cell.chips:
        raise SystemExit(f"{args.workload} needs {cell.chips} chips; JAX "
                         f"finds {len(devices)}")
    devices = devices[:cell.chips]
    info = bench.device_info(devices)
    bench.log("device", json.dumps(info))
    peaks = peaks_override or bench.peaks_for(info["kind"])

    clock = bench.CompileClock()
    setup = bench.Setup(T_PROCESS)
    rec = cell.driver().run(cell=cell, seed=args.seed, seconds=args.seconds,
                            trace=bool(args.trace), setup=setup,
                            devices=devices, clock=clock)
    rec.update(peaks=peaks, chips=len(devices))
    bench.log("setup", json.dumps({"setup_s": rec["setup_s"],
                                   "compile_s": rec["compile_s"],
                                   **{k: round(v, 6)
                                      for k, v in setup.parts.items()}}))
    bench.log("window", json.dumps({
        "rounds": rec["rounds"], "window_s": rec["window_s"],
        "compiles_in_window": rec["compiles_in_window"],
        "cache_loads_in_window": rec["cache_loads_in_window"]}))
    if rec["compiles_in_window"]:
        bench.log("warning: the window compiled",
                  rec["compiles_in_window"], "program(s)")

    result = {
        "correct": bool(all(math.isfinite(v) and v <= lim
                            for _, v, lim in rec["checks"])
                        and rec["failed"] == 0),
        "attempted": rec["attempted"],
        "failed": rec["failed"],
        "metrics": read_metrics(cell, rec, bool(args.trace)),
        "device": {**info, "memory_peak_bytes": rec["memory_peak_bytes"]},
    }
    summary = rec["trace"]
    if args.trace and (summary is None or summary.busy_s <= 0):
        if require_tpu:
            raise RuntimeError("the traced window holds no device op")
    elif args.trace:
        result["device"].update(busy_s=summary.busy_s,
                                window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.idle_gaps(10)}
    result["checks"] = {name: {"value": float(v), "limit": lim}
                        for name, v, lim in rec["checks"]}
    return result


def main(argv=None) -> int:
    result = run_cell(argv)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
