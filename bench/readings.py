#!/usr/bin/env python3
"""The readings a configuration's limits are set from (a tool; the
benchmark's runs do not call it):

    python bench/readings.py --workload resnet50-224.offline \
        --seeds 1 2 3 --fault-seeds 1 2 3 --seconds 3 --out bench/out/readings

Per seed, one run of the cell as ``run.py`` makes it past its look for a
chip, with a window of ``--seconds``, printing the numbers compared.  Per
fault seed, one more run under each fault of ``faults.py`` planted where the
program produces its answers, and one with the program's own
``quantize.calibrate`` in ``quantize.py``'s place (its pools read at the
wrong scale, PERF.md).  Then the int4 control (``control.py``) on the fault
seeds.  One JSON line per reading; the compared rows of every run go to
``<out>/<workload>.npz``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np                       # noqa: E402

from bench import control, faults, run, spec   # noqa: E402
import bench.quantize                    # noqa: E402


def program_quantizer(g, specs, params, calib):
    """The program's own calibration of ``g`` on ``calib``."""
    import jax
    from repro.core import executor, quantize

    return quantize.calibrate(g, jax.device_get(params), calib,
                              executor.run_float)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=os.path.join(ROOT, "bench", "out",
                                                  "readings"))
    args = ap.parse_args(argv)
    import jax

    devices = jax.devices()
    print(f"platform {devices[0].platform}; device_kind "
          f"{devices[0].device_kind}", flush=True)
    cell = spec.cell(spec.benchmark(), args.workload)
    cfg, mix = spec.config(cell["config"]), spec.traffic(cell["traffic"])
    rows = {}

    def one(seed, plant, setup=None):
        undo = setup() if setup else None
        try:
            res = run.run_cell(args.workload, cfg, mix, [], seed=seed,
                               seconds=args.seconds, trace=False,
                               devices=devices[:cell["chips"]])
        finally:
            if undo:
                undo()
        line = {"seed": seed, "plant": plant, "correct": res["correct"],
                "attempted": res["attempted"], "failed": res["failed"]}
        line.update({k: v["value"] for k, v in res["check"].items()})
        print(json.dumps(line), flush=True)
        for k, v in res.get("rows", {}).items():
            rows[f"{plant}.{seed}.{k}"] = np.asarray(v)

    def calibrate_in_place():
        orig = bench.quantize.quantize
        bench.quantize.quantize = program_quantizer

        def undo():
            bench.quantize.quantize = orig
        return undo

    for seed in args.seeds:
        one(seed, "sound")
    for seed in args.fault_seeds:
        for name, fault in faults.FAULTS.items():
            one(seed, name, lambda fault=fault: faults.plant(fault))
        one(seed, "program_calibrate", calibrate_in_place)
    if args.fault_seeds:
        for seed, r in zip(args.fault_seeds,
                           control.readings(cfg, args.fault_seeds)):
            print(json.dumps({"seed": seed, "plant": "int4_control",
                              **r[4]}), flush=True)
            print(json.dumps({"seed": seed, "plant": "int8_reference",
                              **r[8]}), flush=True)
    os.makedirs(args.out, exist_ok=True)
    np.savez_compressed(os.path.join(args.out, f"{args.workload}.npz"),
                        **{k: (v.astype(np.float32) if v.dtype.kind == "f"
                               else v) for k, v in rows.items()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
