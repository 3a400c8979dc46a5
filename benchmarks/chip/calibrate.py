#!/usr/bin/env python3
"""Read the numbers a cell compares, for setting its limits.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11,12,... [--control-seeds 3] [--faults half_batch,...] \
        [--fault-seeds 3]

In one process (the compiled program is built once): for each seed, the
program's first rounds against the plain reference (the lower readings);
for the first ``--control-seeds`` seeds, the control — the reference in
the precision below the configuration's, put in the program's place —
against the same reference (the upper readings); and for each fault of
``faults.py``, the program with that fault planted, on ``--fault-seeds``
seeds.  One JSON line per reading.  Runs on the chip, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, HERE)
import bench  # noqa: E402

sys.path.insert(0, os.path.join(bench.ROOT, "src"))

def readings(cell, devices, seeds, n_control, fault=None):
    """Print one JSON line per seed: the program's gaps to the reference
    and, for the first ``n_control`` seeds, the control's."""
    import contextlib

    import faults
    drv = cell.driver()
    mu = cell.config["gfl"]["mu"]
    with faults.planted(fault) if fault else contextlib.nullcontext():
        program = drv.Trainer(cell, devices)
        for j, seed in enumerate(seeds):
            state, prog = program.start(seed)
            del state
            ref = program.reference(seed)
            out = {"seed": seed, "fault": fault,
                   "gaps": drv.compare(prog, ref, mu),
                   "program": drv.readings(prog),
                   "reference": drv.readings(ref)}
            if fault is None and j < n_control:
                ctl = program.reference(seed, control=True)
                out["control_gaps"] = drv.compare(ctl, ref, mu)
                out["control"] = drv.readings(ctl)
            print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    cell = bench.Cell(args.workload)
    import jax
    bench.use_cache(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        raise SystemExit(f"needs {cell.chips} TPU chips; JAX finds "
                         f"{len(devices)} {devices[0].platform} device(s)")
    devices = devices[:cell.chips]
    seeds = [int(s) for s in args.seeds.split(",")]
    readings(cell, devices, seeds, args.control_seeds)
    for fault in filter(None, args.faults.split(",")):
        readings(cell, devices, seeds[:args.fault_seeds], 0, fault)
    return 0


if __name__ == "__main__":
    sys.exit(main())
