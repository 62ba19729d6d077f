#!/usr/bin/env python3
"""Chip smoke test: ResNet-50 @224 served through the fused Pallas path.

    python3 chip_smoke.py                # one TPU chip
    python3 chip_smoke.py --four-chips   # 4 one-chip Fleet replicas vs 1

One chip: ResNet-50 at its published widths (224x224x3 input, 1000 classes,
all 50 convs; random weights and inputs from ``--seed``) is calibrated,
planned for TPU v5e (default all-accelerator partition), opened as a
``Session`` on the fused Pallas backend and served through a dynamic-batching
``Server``.  The run fails unless every conv, pool, eltwise and fc runs as a
compiled fused launch (no ``RefFallback``, no interpreter) and every served
output is bit-exact with the int8 reference executor on the same chip.

``--four-chips`` runs only the fleet phase: a ``Fleet`` of four replicas, one
per chip, against a one-replica ``Fleet`` on the same requests.  Every
replica's outputs must live on its own device, and all outputs must be
bit-exact with the one-replica fleet.

The last line of stdout is one JSON object naming the device.  It is printed
only when every check passed on a TPU.  Times printed are smoke readings, not
benchmark metrics.  The compile cache lives in $JAX_COMPILATION_CACHE_DIR when
set, else in ``.jax_cache`` of the checkout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

TIMEOUT_S = 600.0           # per served request (covers a cold compile)


class SmokeError(AssertionError):
    """A check of the smoke run failed."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


def build_model(model: str, img: int, num_classes: int, seed: int):
    """(graph, quantized model) with random float weights from ``seed``."""
    from repro.cnn import build, init_params
    from repro.core import executor, quantize

    g = build(model, img=img, num_classes=num_classes, softmax=False)
    params = init_params(g, seed=seed)
    rng = np.random.default_rng(seed)
    calib = rng.standard_normal(g.shape("data")).astype(np.float32)
    return g, quantize.calibrate(g, params, calib, executor.run_float)


def make_requests(g, qm, n: int, seed: int) -> list:
    """``n`` seeded single-image int8 requests."""
    from repro.core import quantize

    rng = np.random.default_rng(seed + 1)
    shape = (1,) + tuple(g.shape("data")[1:])
    return [quantize.quantize_to(rng.standard_normal(shape).astype(np.float32),
                                 qm.f_a["data"]) for _ in range(n)]


def _same(a: dict, b: dict) -> bool:
    return a.keys() == b.keys() and all(
        np.asarray(a[k]).shape == np.asarray(b[k]).shape
        and np.array_equal(a[k], b[k]) for k in a)


def serve_one_chip(g, qm, xs, log=print) -> dict:
    """The served path, once: search -> Session(pallas) -> Server on the
    requests ``xs``, checked bit for bit against the int8 reference executor
    on the same device."""
    from repro.core import pathsearch
    from repro.core.executor import Int8Executor
    from repro.hw import TPU_V5E
    from repro.kernels.conv_fused.ops import interpret_mode
    from repro.runtime import Server, Session

    t0 = time.perf_counter()
    strategy = pathsearch.search(g, TPU_V5E)
    sess = Session(g, strategy, TPU_V5E, qm, backend="pallas")
    prog = sess.executor.program
    for b in (1, 4):                     # the served batch shapes
        sess.run_batch(xs[:b], pad_to=b)
    compile_s = time.perf_counter() - t0
    n_launches = len(prog.launches())
    n_fallbacks = len(prog.fallbacks())
    log(f"set-up: cold compile (search + lower + XLA/Mosaic compile of "
        f"batch 1 and 4) {compile_s:.1f} s")
    log(f"program: {n_launches} fused launches "
        f"{dict(prog.meta['kinds'])}, {n_fallbacks} fallbacks, "
        f"interpret={interpret_mode()}")
    _check(n_fallbacks == 0, f"lowered program holds fallbacks: "
           f"{[(f.nodes, f.reason) for f in prog.fallbacks()]}")
    covered = {n for it in prog.launches() for n in it.nodes}
    missing = [n for n in g.compute_nodes() if n not in covered]
    _check(not missing, f"nodes outside fused launches: {missing}")

    reps = []
    for _ in range(5):
        t = time.perf_counter()
        sess.run_batch(xs[:4], pad_to=4)
        reps.append(time.perf_counter() - t)
    warm_ms = float(np.median(reps)) * 1e3
    log(f"smoke reading (not a metric): warm batch-4 latency "
        f"{warm_ms:.2f} ms (median of {len(reps)}, host clock)")

    with Server(sess, max_batch=4, allowed_sizes=(1, 4),
                max_latency_s=0.005) as server:
        futs = [server.submit(x) for x in xs]
        served = [f.result(timeout=TIMEOUT_S) for f in futs]
        stats = server.stats()
    log(f"served {len(served)} requests, batches "
        f"{stats['batch_histogram']}")

    ref = Int8Executor(g, qm, backend="ref")
    want = [ref(x) for x in xs]
    bad = [i for i, (a, b) in enumerate(zip(served, want)) if not _same(a, b)]
    _check(not bad, f"served outputs differ from the int8 reference on "
           f"requests {bad}")
    top = sess.outputs[-1]
    shapes = {np.asarray(o[top]).shape for o in served}
    _check(len({np.asarray(o[top]).tobytes() for o in served}) > 1,
           "every request produced the same output")
    log(f"bit-exact with the int8 reference: {len(served)}/{len(served)} "
        f"requests, output {top!r} {sorted(shapes)} int8")
    return {"n_launches": n_launches, "n_fallbacks": n_fallbacks,
            "compile_s": compile_s, "warm_ms": warm_ms,
            "interpret": interpret_mode(), "n_served": len(served),
            "devices": sorted(str(d) for d in sess.executor.devices_seen)}


def serve_fleet(devices, g, qm, xs, log=print) -> dict:
    """A Fleet of one replica per device against a one-replica Fleet, both
    serving the requests ``xs``."""
    from repro import asm
    from repro.core import pathsearch
    from repro.hw import TPU_V5E
    from repro.runtime import Fleet

    art, _ = asm.PLAN_CACHE.get_or_compile(
        g, pathsearch.search(g, TPU_V5E), TPU_V5E, qm=qm)
    _check(not art.program.fallbacks(), "lowered program holds fallbacks")
    # generous health limits: a replica's first batches include compiles
    kw = dict(backend="pallas", attempt_timeout_s=TIMEOUT_S,
              request_deadline_s=4 * TIMEOUT_S,
              heartbeat_timeout_s=TIMEOUT_S, straggler_factor=1e9,
              server_kw=dict(max_batch=4, allowed_sizes=(1, 4)))

    def run(devs):
        t0 = time.perf_counter()
        with Fleet(art, devices=devs, **kw) as fleet:
            setup_s = time.perf_counter() - t0
            futs = [fleet.submit(x) for x in xs]
            outs = [f.result(timeout=4 * TIMEOUT_S) for f in futs]
            placed = {rid: (r.device, set(r.session.executor.devices_seen),
                            r.session.images_served)
                      for rid, r in fleet.replicas().items()}
            evicted = [rid for rid, r in fleet.replicas().items()
                       if r.evictions]
        log(f"fleet of {len(devs)}: set-up {setup_s:.1f} s, served "
            f"{len(outs)} requests, per replica "
            f"{ {rid: n for rid, (_, _, n) in placed.items()} }")
        _check(not evicted, f"replicas evicted: {evicted}")
        for rid, (dev, seen, _) in placed.items():
            _check(seen == {dev}, f"replica {rid} placed on {dev} produced "
                   f"outputs on {sorted(map(str, seen))}")
        return outs, placed

    many, placed = run(list(devices))
    one, _ = run(list(devices)[:1])
    bad = [i for i, (a, b) in enumerate(zip(many, one)) if not _same(a, b)]
    _check(not bad, f"fleet outputs differ from one replica on {bad}")
    idle = [rid for rid, (_, _, n) in placed.items() if n == 0]
    log(f"placement: every replica's outputs on its own device "
        f"({len(placed)} replicas, idle: {idle or 'none'}); "
        f"{len(many)}/{len(many)} outputs bit-exact with one replica")
    return {"n_replicas": len(placed), "n_served": len(many),
            "idle_replicas": idle}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-replica Fleet phase")
    args = ap.parse_args(argv)

    import jax

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"jax {jax.__version__}; platform {dev['platform']}; "
          f"device_kind {dev['kind']}; device count {dev['count']}",
          flush=True)
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (platform {dev['platform']!r}); "
              f"nothing was run", file=sys.stderr)
        return 2

    from repro.jax_cache import enable_compile_cache
    from repro.kernels.conv_fused.ops import interpret_mode

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    _check(not interpret_mode(), "kernels would run in interpret mode")
    log = lambda s: print(s, flush=True)            # noqa: E731
    g, qm = build_model("resnet50", 224, 1000, args.seed)
    if args.four_chips:
        _check(len(devs) >= 4, f"--four-chips needs 4 devices, have "
               f"{len(devs)}")
        serve_fleet(devs[:4], g, qm, make_requests(g, qm, 16, args.seed),
                    log=log)
    else:
        serve_one_chip(g, qm, make_requests(g, qm, 8, args.seed), log=log)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
