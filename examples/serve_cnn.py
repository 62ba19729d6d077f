"""Serving quickstart: compile a CNN once, serve it with dynamic batching.

    PYTHONPATH=src python examples/serve_cnn.py --model vgg16 --img 32
    PYTHONPATH=src python examples/serve_cnn.py --model resnet50 --requests 16
    PYTHONPATH=src python examples/serve_cnn.py --model googlenet --img 64

Walks the served path: calibrate -> path-search -> open a Session (which
lowers the strategy once) -> submit requests to the dynamic-batching Server
-> print throughput, latency percentiles and the batch histogram.  Then the
object-file path: compile the same strategy to a DNNVM artifact and print
its time-wheel engine schedule (modeled cross-request overlap and per-engine
utilization).
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="vgg16",
                    choices=["vgg16", "resnet50", "googlenet"])
    ap.add_argument("--img", type=int, default=32)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-latency-ms", type=float, default=20.0)
    ap.add_argument("--backend", default="ref", choices=["ref", "pallas"])
    args = ap.parse_args()

    from repro import asm
    from repro.cnn import build, init_params
    from repro.core import executor, partition, pathsearch, quantize
    from repro.hw import ZU2
    from repro.runtime import Session, pipeline_report

    print(f"== compile {args.model}@{args.img} ==")
    g = build(args.model, img=args.img, num_classes=10)
    params = init_params(g)
    rng = np.random.default_rng(0)
    calib = rng.standard_normal(g.shape("data")).astype(np.float32)
    qm = quantize.calibrate(g, params, calib, executor.run_float)
    dv = partition.device_of(g, "paper")
    strategy = pathsearch.search(g, ZU2, device_of=dv)

    t0 = time.perf_counter()
    sess = Session(g, strategy, ZU2, qm, backend=args.backend)
    print(f"session ({args.backend}): {time.perf_counter() - t0:.2f}s")

    print(f"== serve {args.requests} requests "
          f"(max_batch={args.max_batch}, "
          f"max_latency={args.max_latency_ms}ms) ==")
    reqs = [quantize.quantize_to(
        rng.standard_normal((1,) + tuple(g.shape('data')[1:])).astype(np.float32),
        qm.f_a["data"]) for _ in range(args.requests)]
    with sess.serve(max_batch=args.max_batch,
                    max_latency_s=args.max_latency_ms * 1e-3) as server:
        t0 = time.perf_counter()
        futs = [server.submit(x) for x in reqs]
        outs = [f.result(timeout=600) for f in futs]
        wall = time.perf_counter() - t0
        stats = server.stats()
    top = sess.outputs[-1]
    print(f"served {len(outs)} requests in {wall:.2f}s "
          f"({len(outs) / wall:.2f} img/s)")
    print(f"latency p50={stats['p50_ms']:.1f}ms p99={stats['p99_ms']:.1f}ms, "
          f"batches {stats['batch_histogram']} "
          f"(mean {stats['mean_batch']:.1f})")
    print(f"output {top!r} of request 0: "
          f"{np.asarray(outs[0][top]).ravel()[:4]} ...")

    print("== engine-level schedule of the object file (time wheel) ==")
    art = asm.compile_strategy(g, strategy, ZU2, qm=qm)
    print(f"fused coverage {art.fused_coverage:.2f}, "
          f"peak DDR {art.peak_ddr_bytes / 1e6:.2f} MB")
    rep = pipeline_report(art, min(args.requests, 8), ddr_slots=4)
    util = ", ".join(f"{e}={u:.0%}" for e, u in rep.utilization().items())
    print(f"modeled cross-request speedup {rep.modeled_speedup:.3f}x "
          f"(overlap {rep.overlap:.1%}), bottleneck {rep.bottleneck}")
    print(f"per-engine utilization: {util}")
    lat = rep.request_latency_cycles()
    print(f"request latency (cycles): first {lat[0]}, steady-state ~{lat[-1]}")
    # show the software pipeline directly: request 1's LOADs issued while
    # request 0's CONVs were still running
    conv0 = [w for w in rep.engine_timeline["CONV"] if w[3].startswith("r0:")]
    load1 = [w for w in rep.engine_timeline["DDR_RD"]
             if w[3].startswith("r1:")]
    overlapped = [l for l in load1
                  if any(l[0] < c[1] and c[0] < l[1] for c in conv0)]
    print(f"LOAD(r1) windows overlapping CONV(r0): "
          f"{len(overlapped)}/{len(load1)}, e.g. "
          + "; ".join(f"{t}@[{s},{e})" for s, e, _, t in overlapped[:2]))


if __name__ == "__main__":
    main()
