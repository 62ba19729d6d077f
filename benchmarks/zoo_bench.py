"""Model-zoo benchmark: multi-tenant co-resident serving vs swap-per-model.

The staged-pipeline/zoo counterpart of ``serve_bench``: compile THREE nets
once through the staged compile pipeline into a content-addressed zoo, then
serve a skewed mixed-traffic request stream (default 60/30/10) two ways —

* **swapped**: the one-model-at-a-time baseline — for each model in turn,
  open a fresh session from its zoo artifact (paying the swap-in) and run
  its requests back to back;
* **co-resident**: all models admitted to one ``MultiServer`` (per-tenant
  SLO classes gold/silver/best_effort, per-model DDR partition, labelled
  metrics), the mixed stream routed per request.

Also measured, via the stage-cache metrics counters: a warm recompile of
every model must hit all four stage caches (0 stages built), and a zoo
reopen from a COLD stage cache must build nothing past the trivial wrap
(the artifact comes off disk, search/lower/plan/compile never run).

--smoke asserts the acceptance gates (cross-model bit-exactness against the
unfused int8 oracle, co-resident > swapped throughput, warm reopen compiles
0 stages) and is wired into `make ci`.
"""
from __future__ import annotations

import argparse
import json
import tempfile
import time

import numpy as np

from serve_bench import audit_bit_exact, make_requests

SLO_ORDER = ("gold", "silver", "best_effort")


def build_model(model: str, img: int):
    from repro.cnn import build, init_params
    from repro.core import executor, quantize

    g = build(model, img=img, num_classes=10) if img != 224 else build(model)
    params = init_params(g)
    rng = np.random.default_rng(0)
    x = rng.standard_normal(g.shape("data")).astype(np.float32)
    qm = quantize.calibrate(g, params, x, executor.run_float)
    return g, qm


def _stage_counts(reg, what: str) -> dict:
    from repro.stages import STAGE_NAMES
    return {s: (reg.get(f"stages.{s}.{what}").value
                if reg.get(f"stages.{s}.{what}") else 0.0)
            for s in STAGE_NAMES}


def _delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def make_traffic(models: list[str], weights: list[float], n: int, seed=7):
    """Skewed mixed stream: n (model, request-index) draws, weights-shuffled
    but deterministic."""
    rng = np.random.default_rng(seed)
    w = np.asarray(weights, float)
    draws = rng.choice(len(models), size=n, p=w / w.sum())
    # every model serves at least one request, whatever the skew
    for i in range(len(models)):
        if not (draws == i).any():
            draws[i] = i
    counts = {m: int((draws == i).sum()) for i, m in enumerate(models)}
    return list(draws), counts


def run_swapped(artifacts: dict, reqs_by_model: dict, backend: str) -> dict:
    """One model at a time: swap in (fresh session from the zoo artifact),
    drain that tenant's requests sequentially, swap out."""
    from repro.runtime import Session

    outs = {m: [] for m in artifacts}
    swap_s = {}
    t0 = time.perf_counter()
    for m, art in artifacts.items():
        t1 = time.perf_counter()
        sess = Session.from_artifact(art, backend=backend)
        sess.run(reqs_by_model[m][0])          # trace, as a swap-in would
        swap_s[m] = time.perf_counter() - t1
        for x in reqs_by_model[m]:
            outs[m].append(sess.run(x))
    wall = time.perf_counter() - t0
    n = sum(len(v) for v in reqs_by_model.values())
    return {"outputs": outs, "wall_s": wall, "images_per_s": n / wall,
            "swap_s": swap_s}


def run_multiserver(artifacts: dict, stream, reqs_by_model: dict, *,
                    backend: str, max_batch: int,
                    max_latency_s: float) -> dict:
    from repro.runtime import MultiServer

    names = list(artifacts)
    ms = MultiServer()
    for name, slo in zip(names, SLO_ORDER):
        ms.add_model(name, artifacts[name], slo=slo, backend=backend,
                     max_batch=max_batch, max_latency_s=max_latency_s)
    try:
        cursors = {m: 0 for m in names}
        futs = []
        t0 = time.perf_counter()
        for i in stream:
            name = names[i]
            x = reqs_by_model[name][cursors[name]]
            cursors[name] += 1
            futs.append((name, ms.submit(name, x)))
        outs = {m: [] for m in names}
        for name, f in futs:
            outs[name].append(f.result(timeout=120))
        wall = time.perf_counter() - t0
        stats = ms.stats()
    finally:
        ms.close()
    n = len(futs)
    return {"outputs": outs, "wall_s": wall, "images_per_s": n / wall,
            "stats": stats}


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--models", nargs="*",
                    default=["vgg16", "resnet50", "googlenet"])
    ap.add_argument("--img", type=int, default=32)
    ap.add_argument("--requests", type=int, default=30,
                    help="total requests across all tenants")
    ap.add_argument("--mix", type=float, nargs="*", default=[60, 30, 10],
                    help="traffic skew across --models (normalized)")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-latency-ms", type=float, default=2.0)
    ap.add_argument("--backend", default="ref", choices=["ref", "pallas"])
    ap.add_argument("--zoo-dir", default=None,
                    help="zoo root (default: a fresh temp dir)")
    ap.add_argument("--json", dest="json_path", default=None,
                    help="bare names land in benchmarks/out/ (gitignored)")
    ap.add_argument("--repeats", type=int, default=1,
                    help="alternate swapped/co-resident trials and keep the "
                         "best of each (controls for clock drift)")
    ap.add_argument("--smoke", action="store_true",
                    help="assert bit-exactness, co-resident > swapped, and "
                         "warm reopen compiles 0 stages")
    args = ap.parse_args(argv)
    import outdir
    args.json_path = outdir.resolve(args.json_path)
    if args.smoke and args.repeats < 3:
        args.repeats = 3
    assert len(args.mix) == len(args.models)

    from repro.hw import ZU2
    from repro.obs import REGISTRY
    from repro.stages import StageCache, compile_model
    from repro.zoo import ModelZoo

    zoo = ModelZoo(args.zoo_dir or tempfile.mkdtemp(prefix="dnnvm-zoo-"))
    sc = StageCache()

    # ---- phase 1: compile once into the zoo (cold) ----------------------
    built, compiled, compile_s = {}, {}, {}
    for m in args.models:
        g, qm = build_model(m, args.img)
        built[m] = (g, qm)
        t0 = time.perf_counter()
        compiled[m] = compile_model(g, qm, ZU2, zoo=zoo, name=m, cache=sc)
        compile_s[m] = time.perf_counter() - t0
        print(f"compiled {m}@{args.img}: key={compiled[m].key} "
              f"({compile_s[m]:.2f}s, fused_coverage="
              f"{compiled[m].artifact.fused_coverage:.2f})")
    assert len(zoo) == len(args.models)

    # ---- warm recompile: all four stage caches must hit -----------------
    miss0, hit0 = _stage_counts(REGISTRY, "misses"), _stage_counts(REGISTRY,
                                                                   "hits")
    t0 = time.perf_counter()
    for m in args.models:
        g, qm = built[m]
        co = compile_model(g, qm, ZU2, cache=sc)
        assert co.key == compiled[m].key
    warm_s = time.perf_counter() - t0
    warm_miss = _delta(_stage_counts(REGISTRY, "misses"), miss0)
    warm_hit = _delta(_stage_counts(REGISTRY, "hits"), hit0)
    print(f"warm recompile x{len(args.models)}: {warm_s:.3f}s, "
          f"stage hits {warm_hit}, misses {warm_miss}")

    # ---- zoo reopen from a COLD stage cache: nothing rebuilt ------------
    from repro.obs.metrics import MetricsRegistry
    reopen_reg = MetricsRegistry()
    zoo_hits0 = (REGISTRY.get("zoo.hits").value
                 if REGISTRY.get("zoo.hits") else 0.0)
    t0 = time.perf_counter()
    for m in args.models:
        g, qm = built[m]
        co = compile_model(g, qm, ZU2, zoo=zoo,
                           cache=StageCache(registry=reopen_reg))
        assert co.key == compiled[m].key
    reopen_s = time.perf_counter() - t0
    reopen_miss = _stage_counts(reopen_reg, "misses")
    zoo_hits = ((REGISTRY.get("zoo.hits").value
                 if REGISTRY.get("zoo.hits") else 0.0) - zoo_hits0)
    print(f"zoo reopen x{len(args.models)}: {reopen_s:.3f}s, "
          f"zoo hits {zoo_hits:.0f}, stages rebuilt past wrap: "
          f"{ {k: v for k, v in reopen_miss.items() if k != 'wrapped'} }")

    # ---- phase 2: mixed skewed traffic ----------------------------------
    from repro.runtime import Session
    sessions = {m: Session.from_artifact(compiled[m].artifact,
                                         backend=args.backend)
                for m in args.models}
    stream, counts = make_traffic(args.models, args.mix, args.requests)
    reqs_by_model = {m: make_requests(sessions[m], counts[m])
                     for m in args.models}
    print(f"traffic: {counts} (mix {args.mix}, {args.requests} total)")

    artifacts = {m: compiled[m].artifact for m in args.models}
    swapped = multi = None
    for _ in range(max(1, args.repeats)):
        got = run_swapped(artifacts, reqs_by_model, args.backend)
        if swapped is None or got["images_per_s"] > swapped["images_per_s"]:
            swapped = got
        got = run_multiserver(artifacts, stream, reqs_by_model,
                              backend=args.backend, max_batch=args.max_batch,
                              max_latency_s=args.max_latency_ms * 1e-3)
        if multi is None or got["images_per_s"] > multi["images_per_s"]:
            multi = got
    print(f"swapped    : {swapped['images_per_s']:8.2f} img/s "
          f"(swap-in {sum(swapped['swap_s'].values()):.2f}s total)")
    per_tenant = {}
    for m in args.models:
        st = multi["stats"]["models"][m]
        per_tenant[m] = {"slo": multi["stats"]["slo"][m],
                         "n_served": st["n_served"],
                         "p50_ms": st["p50_ms"], "p99_ms": st["p99_ms"],
                         "mean_batch": st["mean_batch"]}
        print(f"co-resident[{m}] ({per_tenant[m]['slo']}): "
              f"{st['n_served']} reqs  p50={st['p50_ms']:.2f}ms "
              f"p99={st['p99_ms']:.2f}ms  mean_batch={st['mean_batch']:.2f}")
    print(f"co-resident: {multi['images_per_s']:8.2f} img/s  "
          f"({multi['images_per_s'] / swapped['images_per_s']:.2f}x swapped)")

    exact = {}
    for m in args.models:
        e_swap, e_multi = audit_bit_exact(
            sessions[m], reqs_by_model[m], swapped["outputs"][m],
            multi["outputs"][m])
        exact[m] = {"swapped": e_swap, "co_resident": e_multi}
    print(f"bit-exact vs oracle: {exact}")

    out = {
        "models": args.models, "img": args.img, "mix": args.mix,
        "requests": args.requests, "backend": args.backend,
        "zoo_root": zoo.root, "zoo_keys": {m: compiled[m].key
                                           for m in args.models},
        "compile_s": compile_s, "warm_recompile_s": warm_s,
        "warm_stage_hits": warm_hit, "warm_stage_misses": warm_miss,
        "zoo_reopen_s": reopen_s, "zoo_reopen_stage_misses": reopen_miss,
        "swapped": {k: v for k, v in swapped.items() if k != "outputs"},
        "co_resident": {"images_per_s": multi["images_per_s"],
                        "wall_s": multi["wall_s"],
                        "per_tenant": per_tenant,
                        "ddr_partition":
                            multi["stats"]["ddr_partition"]},
        "co_resident_vs_swapped": (multi["images_per_s"]
                                   / swapped["images_per_s"]),
        "bit_exact": exact,
        "metrics": REGISTRY.snapshot(),
    }
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(out, f, indent=2, default=str)
        print(f"wrote {args.json_path}")

    if args.smoke:
        assert all(e["swapped"] and e["co_resident"]
                   for e in exact.values()), (
            f"served outputs diverged from the oracle: {exact}")
        assert all(v == 0 for v in warm_miss.values()), (
            f"warm recompile rebuilt stages: {warm_miss}")
        assert all(v == float(len(args.models))
                   for v in warm_hit.values()), (
            f"warm recompile must hit all four stage caches per model: "
            f"{warm_hit}")
        assert all(v == 0 for s, v in reopen_miss.items()
                   if s != "wrapped"), (
            f"zoo reopen rebuilt stages past wrap: {reopen_miss}")
        assert zoo_hits >= len(args.models), "zoo reopen missed the store"
        assert multi["images_per_s"] > swapped["images_per_s"], (
            f"co-resident serving must beat sequential swapping: "
            f"{multi['images_per_s']:.2f} <= {swapped['images_per_s']:.2f}")
        for m, t in per_tenant.items():
            assert t["n_served"] == counts[m] and t["p99_ms"] > 0
        print("SMOKE OK: bit-exact, co-resident > swapped, warm recompile "
              "0 stages, zoo reopen 0 stages past wrap")
    return out


if __name__ == "__main__":
    main()
