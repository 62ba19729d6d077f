"""Autotune benchmark: profile-guided search vs the analytic cost model.

For each network this benchmark closes the compiler <-> measurement loop and
reports what it bought:

1. build + quantize the net, search a strategy under the hand-written
   analytic device model (the pre-tuner compiler);
2. calibrate a :class:`~repro.tune.profile.DeviceProfile` on this machine:
   measure the fused-op candidate set through the real executor
   (``tune.MeasurementHarness``) and least-squares fit the cost model's
   coefficients (``tune.calibrate``), reporting the deviation band;
3. search again under the :class:`~repro.tune.evaluator.CalibratedEvaluator`
   and diff the two strategies;
4. when they differ, measure both end-to-end with alternating passes (clock
   drift and interference epochs hit both contenders equally) and report the
   measured delta; identical strategies are reported as a zero delta without
   re-measurement;
5. compile the calibrated strategy under the profile — the artifact records
   the profile hash (``CompiledArtifact.profile_hash``).

--smoke asserts the acceptance gates (calibration deviation within the band,
calibrated strategy measured no slower than the analytic one) and is wired
into ``make ci`` as ``make tune-smoke``.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

import outdir


def build_quantized(model: str, img: int):
    from repro.cnn import build, init_params
    from repro.core import executor, quantize

    g = build(model, img=img, num_classes=10) if img != 224 else build(model)
    params = init_params(g)
    rng = np.random.default_rng(0)
    x = rng.standard_normal(g.shape("data")).astype(np.float32)
    qm = quantize.calibrate(g, params, x, executor.run_float)
    return g, qm


def strategy_key(s) -> tuple:
    return (tuple(tuple(grp) for grp in s.groups),
            tuple(tuple(h) for h in s.horizontal))


def bench_model(model: str, img: int, *, backend: str, max_samples: int,
                repeats: int, passes: int, profile_cache=None) -> dict:
    from repro import asm
    from repro.core import pathsearch
    from repro.hw import ZU2
    from repro.tune import CalibratedEvaluator, MeasurementHarness, calibrate

    dev = ZU2
    g, qm = build_quantized(model, img)

    t0 = time.perf_counter()
    s_analytic = pathsearch.search(g, dev)
    t_search_a = time.perf_counter() - t0

    # calibrate on the candidate set PLUS the analytic strategy's own
    # segments, so the fit covers the groups the search actually compares
    from repro.tune.calibrate import default_candidate_groups
    cands = default_candidate_groups(
        g, max_samples=max_samples,
        extra=[list(grp) for grp in s_analytic.groups])
    t0 = time.perf_counter()
    res = calibrate(g, qm, dev, groups=cands, backend=backend,
                    features="kernel", repeats=repeats,
                    name=f"{dev.name}-{backend}-{model}")
    t_cal = time.perf_counter() - t0
    if profile_cache is not None:
        profile_cache.put(res.profile)

    t0 = time.perf_counter()
    ev = CalibratedEvaluator(g, dev, res.profile)
    s_cal = pathsearch.search(g, dev, evaluator=ev)
    t_search_c = time.perf_counter() - t0

    changed = strategy_key(s_analytic) != strategy_key(s_cal)
    rec = {
        "model": model, "img": img, "backend": backend,
        "deviation": res.report["deviation"],
        # stacked (horizontal) launches are measured directly during
        # calibration now; their own deviation band reports separately
        "stacked": res.report.get("stacked"),
        "deviation_by_form": res.report["deviation_by_form"],
        "within_accept_band": res.report["within_accept_band"],
        "model_refit_mape": res.report.get("model_refit_mape"),
        "n_samples": res.report["n_samples"],
        "n_trimmed": res.report["n_trimmed"],
        "combine": res.profile.combine,
        "profile_hash": res.profile.hash(),
        "effective": res.profile.effective_summary(dev),
        "search_s": {"analytic": t_search_a, "calibrated": t_search_c},
        "calibrate_s": t_cal,
        "strategy_changed": changed,
        "n_groups": {"analytic": len(s_analytic.groups),
                     "calibrated": len(s_cal.groups)},
        "n_horizontal": {"analytic": len(s_analytic.horizontal),
                         "calibrated": len(s_cal.horizontal)},
        "predicted_s": {
            "analytic_strategy": ev.strategy_cost(s_analytic),
            "calibrated_strategy": ev.strategy_cost(s_cal)},
    }

    if changed:
        harness = MeasurementHarness(g, qm, dev, backend=backend,
                                     repeats=passes)
        m_a, m_c = harness.measure_strategy_set([s_analytic, s_cal])
        rec["measured_s"] = {"analytic": m_a.seconds, "calibrated": m_c.seconds}
        rec["measured_delta"] = (m_a.seconds - m_c.seconds) / m_a.seconds
        rec["measured_spread"] = {"analytic": m_a.spread,
                                  "calibrated": m_c.spread}
    else:
        rec["measured_s"] = None
        rec["measured_delta"] = 0.0       # same plan, same launches

    # the calibrated strategy compiles under the profile; the artifact
    # records the hash (a drift profiler checks it against its profile)
    art, _ = asm.PlanCache().get_or_compile(g, s_cal, dev, qm=qm,
                                            profile=res.profile)
    rec["artifact_profile_hash"] = art.profile_hash
    assert art.profile_hash == res.profile.hash()
    return rec


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", action="append", dest="models",
                    choices=["vgg16", "resnet50", "googlenet"], default=None,
                    help="repeatable; default: all three benchmark nets")
    ap.add_argument("--img", type=int, default=32)
    ap.add_argument("--backend", default="pallas", choices=["pallas", "ref"])
    ap.add_argument("--max-samples", type=int, default=32,
                    help="calibration candidate-set cap")
    ap.add_argument("--repeats", type=int, default=12,
                    help="measurement passes per calibration unit")
    ap.add_argument("--passes", type=int, default=16,
                    help="alternating end-to-end A/B passes")
    ap.add_argument("--save-profiles", action="store_true",
                    help="write fitted profiles into the on-disk cache "
                         "(benchmarks/out/profiles)")
    ap.add_argument("--json", dest="json_path", default=None,
                    help="bare names land in benchmarks/out/ (gitignored)")
    ap.add_argument("--smoke", action="store_true",
                    help="assert deviation band + calibrated strategy not "
                         "measured-slower")
    args = ap.parse_args(argv)
    args.json_path = outdir.resolve(args.json_path)
    models = args.models or ["vgg16", "resnet50", "googlenet"]

    profile_cache = None
    if args.save_profiles:
        from repro.tune import ProfileCache
        profile_cache = ProfileCache(outdir.out_path("profiles"))

    records = []
    for model in models:
        rec = bench_model(model, args.img, backend=args.backend,
                          max_samples=args.max_samples, repeats=args.repeats,
                          passes=args.passes, profile_cache=profile_cache)
        records.append(rec)
        eff = rec["effective"]
        print(f"{model}@{args.img} [{args.backend}] calibration deviation "
              f"{rec['deviation']:.1%} ({rec['combine']} form, "
              f"{rec['n_samples']} units, {rec['n_trimmed']} trimmed, "
              f"{rec['calibrate_s']:.0f}s)")
        stk = rec.get("stacked") or {}
        if stk.get("n_samples"):
            print(f"  stacked launches: {stk['n_samples']} units measured "
                  f"directly, deviation {stk['deviation']:.1%}")
        print(f"  effective: conv {eff['conv_macs_per_cycle'] or float('nan'):.2f} "
              f"MAC/cyc-equiv, launch {eff['launch_overhead_us']:.0f}us")
        if rec["strategy_changed"]:
            ms = rec["measured_s"]
            print(f"  strategy CHANGED ({rec['n_groups']['analytic']} -> "
                  f"{rec['n_groups']['calibrated']} groups, horizontal "
                  f"{rec['n_horizontal']['analytic']} -> "
                  f"{rec['n_horizontal']['calibrated']}); measured e2e "
                  f"{ms['analytic']*1e3:.1f} -> {ms['calibrated']*1e3:.1f} ms "
                  f"({rec['measured_delta']:+.1%} vs analytic)")
        else:
            print("  strategy unchanged (calibrated search agrees with the "
                  "analytic plan); delta 0")

    out = {"img": args.img, "backend": args.backend, "models": records}
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(out, f, indent=2, default=str)
        print(f"wrote {args.json_path}")

    if args.smoke:
        for rec in records:
            assert rec["within_accept_band"], (
                f"{rec['model']}: calibration deviation {rec['deviation']:.1%}"
                f" outside the accept band")
            assert rec["measured_delta"] >= -0.05, (
                f"{rec['model']}: calibrated strategy measured slower than "
                f"analytic ({rec['measured_delta']:+.1%})")
        print("TUNE SMOKE OK: deviation in band, calibrated strategy not "
              "measured-slower")
    return out


if __name__ == "__main__":
    main()
