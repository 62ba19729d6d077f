#!/usr/bin/env python3
"""The check's control: the reference computed one precision lower (int4
for the configuration's int8) in the program's place, read by the same
number as a run (a tool for setting a configuration's limit; the benchmark's
runs do not call it).

    python bench/control.py --config resnet50-224 --seeds 1 2 3

Per seed it draws the run's weights, image pool and calibration batch, takes
as many images as a run compares, and prints the numbers a run compares
(``check.numbers``) for the int4 network (and, beside it, an int8 one)
against the float32 reference.  The int4 network has to fail a limit.
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

from bench import check, model, reference, spec   # noqa: E402

POOL = 256


def readings(cfg: dict, seeds, bits=(4, 8)) -> list:
    """Per seed, {bits: {number: value}} over the images a run with that
    seed would compare (drawn from its pool), each against the float32
    reference.  The forwards take the weights as arguments, so every seed
    reuses one compile."""
    import jax

    specs = spec.reference(cfg["reference"]).layers(cfg)
    s = cfg["image_size"]
    fns = {b: jax.jit(lambda p, x, c, b=b: reference.forward(
        specs, p, x, bits=b, calib=c)) for b in (None,) + tuple(bits)}
    probs = bool(cfg["softmax"])
    out = []
    for seed in seeds:
        params = reference.make_params(specs, (1, s, s, cfg["channels"]),
                                       seed)
        pool = model.images(cfg, POOL, seed)
        calib = model.as_float(cfg, pool[:cfg["calib_images"]])
        xs = model.as_float(cfg, pool[check.sample(POOL, seed)])
        got = {b: check.reference_outputs(
            lambda x, b=b: fns[b](params, x, calib), xs) for b in fns}
        out.append({b: check.numbers(got[b], got[None], probs)
                    for b in bits})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import jax

    d = jax.devices()[0]
    print(f"platform {d.platform}; device_kind {d.device_kind}", flush=True)
    cfg = spec.config(args.config)
    rows = [{"seed": seed, "int4": r[4], "int8": r[8]}
            for seed, r in zip(args.seeds, readings(cfg, args.seeds))]
    print(json.dumps({"config": args.config, "platform": d.platform,
                      "limits": cfg["check"], "rows": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
