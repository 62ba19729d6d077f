"""Serving benchmark: dynamic batching vs sequential per-request execution.

What Table 3 is to the compiler, this is to the runtime supporter: build a
model, compile it once to a DNNVM object file, then serve R requests from
it two ways —

* **sequential**: one `Session.run` per request, back to back (the naive
  host loop every toolflow starts with);
* **batched**: all requests submitted to the dynamic-batching `Server`
  (optionally at a paced offered load), which flushes them as batched
  launches — ONE executor call covers a whole batch.

Reported per mode: wall-clock images/s, p50/p99 request latency, and the
batch-size histogram.  Every served output is audited bit-exact against the
unfused int8 oracle (the validation environment's contract extends to the
serving path), and the artifact's addressed instruction stream is pipelined
across requests on the time wheel (`runtime.pipeline_report`) to report the
modeled per-engine utilization / overlap next to the measured wall clock.

--smoke asserts the acceptance criteria (batched > sequential throughput,
bit-exactness, hazard-free pipelined stream) and is wired into `make ci`.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def build_session(model: str, img: int, backend: str, use_host_partition: bool,
                  profile=None):
    """(session, the artifact it serves, the resolved profile or None,
    search/compile seconds)."""
    from repro import asm
    from repro.cnn import build, init_params
    from repro.core import executor, partition, pathsearch, quantize
    from repro.hw import ZU2
    from repro.runtime import Session
    from repro.tune.profile import resolve_profile

    g = build(model, img=img, num_classes=10) if img != 224 else build(model)
    params = init_params(g)
    rng = np.random.default_rng(0)
    x = rng.standard_normal(g.shape("data")).astype(np.float32)
    qm = quantize.calibrate(g, params, x, executor.run_float)
    dv = partition.device_of(g, "paper") if use_host_partition else None
    profile = resolve_profile(profile)
    evaluator = None
    if profile is not None:
        from repro.tune import CalibratedEvaluator
        evaluator = CalibratedEvaluator(g, ZU2, profile)
    t0 = time.perf_counter()
    strategy = pathsearch.search(g, ZU2, evaluator=evaluator, device_of=dv)
    t_search = time.perf_counter() - t0
    t0 = time.perf_counter()
    art = asm.compile_strategy(g, strategy, ZU2, qm=qm, profile=profile)
    sess = Session.from_artifact(art, backend=backend)
    t_compile = time.perf_counter() - t0
    return sess, art, profile, {"search_s": t_search, "compile_s": t_compile}


def drift_summary(sess, art, profile) -> dict:
    """Modeled-vs-measured drift of the served plan, when it was compiled
    under a device profile (see ``repro.obs.drift``); cheap to skip when it
    wasn't — serve_bench's default analytic run has nothing to drift from."""
    if profile is None:
        return {"available": False, "reason": "no device profile"}
    from repro.obs import DriftProfiler

    dp = DriftProfiler.from_session(sess, art, profile, every=1)
    dp.prepare()
    dp.sample()
    rep = dp.report()
    return {"available": True, **rep.to_json()}


def make_requests(sess, n: int, seed: int = 1):
    from repro.core import quantize

    g, qm = sess.graph, sess.qm
    rng = np.random.default_rng(seed)
    shape = g.shape("data")
    return [quantize.quantize_to(
        rng.standard_normal((1,) + tuple(shape[1:])).astype(np.float32),
        qm.f_a["data"]) for _ in range(n)]


def run_sequential(sess, reqs) -> dict:
    sess.run(reqs[0])                      # warm the batch-1 trace
    lat = []
    t0 = time.perf_counter()
    outs = []
    for x in reqs:
        t1 = time.perf_counter()
        outs.append(sess.run(x))
        lat.append(time.perf_counter() - t1)
    wall = time.perf_counter() - t0
    lat.sort()
    return {"outputs": outs, "wall_s": wall,
            "images_per_s": len(reqs) / wall,
            "p50_ms": lat[len(lat) // 2] * 1e3,
            "p99_ms": lat[min(len(lat) - 1, int(0.99 * (len(lat) - 1)))] * 1e3}


def run_batched(sess, reqs, *, max_batch: int, max_latency_s: float,
                offered_load: float | None = None,
                target_p99_ms: float | None = None) -> dict:
    server = sess.serve(max_batch=max_batch, max_latency_s=max_latency_s,
                        target_p99_ms=target_p99_ms)
    try:
        t0 = time.perf_counter()
        futs = []
        for i, x in enumerate(reqs):
            futs.append(server.submit(x))
            if offered_load and i + 1 < len(reqs):  # paced; None = burst
                time.sleep(1.0 / offered_load)
        outs = [f.result(timeout=120) for f in futs]
        wall = time.perf_counter() - t0
        stats = server.stats()
    finally:
        server.close()
    return {"outputs": outs, "wall_s": wall,
            "images_per_s": len(reqs) / wall,
            "p50_ms": stats["p50_ms"], "p99_ms": stats["p99_ms"],
            "batch_histogram": stats["batch_histogram"],
            "mean_batch": stats["mean_batch"],
            "target_p99_ms": stats["target_p99_ms"],
            "effective_max_batch": stats["effective_max_batch"],
            "slo_shrinks": stats["slo_shrinks"],
            "slo_grows": stats["slo_grows"]}


def audit_bit_exact(sess, reqs, *out_lists) -> list[bool]:
    """Each list of served outputs must match the unfused int8 oracle
    exactly; the oracle runs ONCE per request however many lists compare."""
    from repro.core.executor import Int8Executor

    oracle = Int8Executor(sess.graph, sess.qm, strategy=None, backend="ref")
    keys = set(sess.outputs)
    refs = [oracle(x) for x in reqs]
    return [all(np.array_equal(ref[k], got[k])
                for ref, got in zip(refs, outs) for k in keys)
            for outs in out_lists]


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="vgg16",
                    choices=["vgg16", "resnet50", "googlenet"])
    ap.add_argument("--img", type=int, default=32)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--max-latency-ms", type=float, default=5.0)
    ap.add_argument("--backend", default="ref", choices=["ref", "pallas"])
    ap.add_argument("--loads", type=float, nargs="*", default=None,
                    help="offered loads (req/s) to sweep; always includes "
                         "an unpaced burst")
    ap.add_argument("--ddr-slots", type=int, nargs="*", default=[2, 4])
    ap.add_argument("--host-partition", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="deploy fc layers on the host (paper §6.1)")
    ap.add_argument("--target-p99-ms", type=float, default=None,
                    help="latency SLO: shrink the effective max batch while "
                         "the observed p99 exceeds this target")
    ap.add_argument("--profile", default=None,
                    help="calibrated device profile (name or JSON path) to "
                         "search/compile under; also enables the drift "
                         "summary in the JSON output")
    ap.add_argument("--json", dest="json_path", default=None,
                    help="bare names land in benchmarks/out/ (gitignored)")
    ap.add_argument("--repeats", type=int, default=1,
                    help="alternate sequential/batched trials this many "
                         "times and keep the best of each (controls for "
                         "clock-speed drift on throttled boxes)")
    ap.add_argument("--smoke", action="store_true",
                    help="assert batched beats sequential + bit-exactness")
    args = ap.parse_args(argv)
    import outdir
    args.json_path = outdir.resolve(args.json_path)
    if args.smoke and args.repeats < 3:
        args.repeats = 3

    sess, art, profile, compile_times = build_session(
        args.model, args.img, args.backend, args.host_partition,
        profile=args.profile)
    reqs = make_requests(sess, args.requests)
    print(f"{args.model}@{args.img} backend={args.backend} "
          f"requests={args.requests} fused_coverage="
          f"{art.fused_coverage:.2f} "
          f"(search {compile_times['search_s']:.2f}s, "
          f"compile {compile_times['compile_s']:.2f}s)")

    # alternate the two modes so slow clock drift (thermal throttling) hits
    # both equally, then keep each mode's best trial
    seq = burst = None
    for _ in range(max(1, args.repeats)):
        got = run_sequential(sess, reqs)
        if seq is None or got["images_per_s"] > seq["images_per_s"]:
            seq = got
        got = run_batched(sess, reqs, max_batch=args.max_batch,
                          max_latency_s=args.max_latency_ms * 1e-3,
                          target_p99_ms=args.target_p99_ms)
        if burst is None or got["images_per_s"] > burst["images_per_s"]:
            burst = got
    print(f"sequential : {seq['images_per_s']:8.2f} img/s  "
          f"p50={seq['p50_ms']:.2f}ms p99={seq['p99_ms']:.2f}ms")
    sweeps = [{"offered_load": None, **{k: v for k, v in burst.items()
                                        if k != "outputs"}}]
    print(f"batched    : {burst['images_per_s']:8.2f} img/s  "
          f"p50={burst['p50_ms']:.2f}ms p99={burst['p99_ms']:.2f}ms  "
          f"batches={burst['batch_histogram']} (burst)")
    for load in (args.loads or []):
        got = run_batched(sess, reqs, max_batch=args.max_batch,
                          max_latency_s=args.max_latency_ms * 1e-3,
                          offered_load=load)
        sweeps.append({"offered_load": load,
                       **{k: v for k, v in got.items() if k != "outputs"}})
        print(f"batched@{load:6.0f}/s: {got['images_per_s']:8.2f} img/s  "
              f"p50={got['p50_ms']:.2f}ms p99={got['p99_ms']:.2f}ms  "
              f"batches={got['batch_histogram']}")

    exact_seq, exact_bat = audit_bit_exact(sess, reqs, seq["outputs"],
                                           burst["outputs"])
    print(f"bit-exact vs oracle: sequential={exact_seq} batched={exact_bat}")

    # pinned-input variant of the same plan: the input's DDR region leaves
    # the reuse pool, the cross-request pre-load guard disappears
    from repro import asm
    from repro.runtime.schedule import choose_ddr_slots
    from repro.runtime.schedule import pipeline_report as _pipe_report
    pinned_art = asm.compile_strategy(sess.graph, art, sess.device,
                                      qm=sess.qm, pin_input=True)
    auto_rep = _pipe_report(art, min(args.requests, 8), ddr_slots=None,
                            profile=profile)
    print(f"auto ddr_slots: {auto_rep.ddr_slots} "
          f"(source={auto_rep.ddr_slots_source}, DRAM/compute ratio decides "
          f"the double-buffer depth)")
    pipe = {"auto": {"ddr_slots": auto_rep.ddr_slots,
                     "ddr_slots_source": auto_rep.ddr_slots_source,
                     "modeled_speedup": auto_rep.modeled_speedup,
                     "overlap": auto_rep.overlap}}
    for slots in args.ddr_slots:
        rep = _pipe_report(art, min(args.requests, 8), ddr_slots=slots)
        repp = _pipe_report(pinned_art, min(args.requests, 8),
                            ddr_slots=slots)
        pipe[slots] = {
            "modeled_speedup": rep.modeled_speedup,
            "overlap": rep.overlap,
            "utilization": rep.utilization(),
            "bottleneck": rep.bottleneck,
            "single_request_cycles": rep.single_request_cycles,
            "total_cycles": rep.total_cycles,
            "n_preload_guards": rep.n_preload_guards,
            "pinned": {"overlap": repp.overlap,
                       "modeled_speedup": repp.modeled_speedup,
                       "n_preload_guards": repp.n_preload_guards,
                       "peak_ddr_bytes": pinned_art.peak_ddr_bytes},
        }
        u = {k: round(v, 2) for k, v in rep.utilization().items()}
        print(f"time-wheel pipeline (ddr_slots={slots}): "
              f"modeled speedup {rep.modeled_speedup:.3f}x, "
              f"overlap {rep.overlap:.1%}, bottleneck {rep.bottleneck}, "
              f"util {u} (hazard-free)")
        print(f"  pin_input: overlap {rep.overlap:.2%} -> {repp.overlap:.2%}, "
              f"pre-load guards {rep.n_preload_guards} -> "
              f"{repp.n_preload_guards}, peak DDR "
              f"{art.peak_ddr_bytes} -> {pinned_art.peak_ddr_bytes}B")

    # observability payload: the shared metrics registry has been counting
    # the whole run (plan cache, executor launches, serve histograms); the
    # drift summary compares measured unit times against the profile's
    # predictions when the session was compiled under one
    from repro.obs import REGISTRY
    metrics_snapshot = REGISTRY.snapshot()
    drift = drift_summary(sess, art, profile)
    if drift["available"]:
        print(f"drift: aggregate={drift['aggregate_deviation']:.3f} "
              f"band={drift['band']:.3f} drifted={drift['drifted']}")

    out = {
        "model": args.model, "img": args.img, "backend": args.backend,
        "requests": args.requests, "max_batch": args.max_batch,
        "max_latency_ms": args.max_latency_ms,
        "fused_coverage": art.fused_coverage,
        **compile_times,
        "sequential": {k: v for k, v in seq.items() if k != "outputs"},
        "batched": sweeps,
        "bit_exact": {"sequential": exact_seq, "batched": exact_bat},
        "pipeline": pipe,
        "batched_vs_sequential": burst["images_per_s"] / seq["images_per_s"],
        "metrics": metrics_snapshot,
        "drift": drift,
    }
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(out, f, indent=2, default=str)
        print(f"wrote {args.json_path}")

    if args.smoke:
        assert exact_seq and exact_bat, "served outputs diverged from oracle"
        assert burst["images_per_s"] > seq["images_per_s"], (
            f"dynamic batching must beat sequential serving: "
            f"{burst['images_per_s']:.2f} <= {seq['images_per_s']:.2f} img/s")
        assert all(p["utilization"] for p in pipe.values()
                   if "utilization" in p)
        assert pipe["auto"]["ddr_slots"] >= 1
        for slots, p in pipe.items():
            if "pinned" not in p:
                continue
            assert p["pinned"]["n_preload_guards"] == 0, (
                "pinned input plan must carry zero pre-load guards")
            assert p["pinned"]["overlap"] >= p["overlap"] - 1e-3, (
                f"pin_input regressed modeled overlap at ddr_slots={slots}: "
                f"{p['pinned']['overlap']:.4f} < {p['overlap']:.4f}")
        print("SMOKE OK: batched > sequential, bit-exact, hazard-free "
              "pipeline, pin_input guard-free")
    return out


if __name__ == "__main__":
    main()
