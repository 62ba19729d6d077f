"""Explain benchmark: compile-decision provenance, end to end.

One run exercises the whole PR-9 surface on a real net:

1. **search-tracing overhead gate** — the strategy search is timed with and
   without ``trace=True`` (min of alternating repeats); recording the
   decision provenance must cost <= 5% of search wall-clock, or it is not
   free enough to stay on by default;
2. **report round trip** — compile the net, read the embedded CompileReport
   back off the artifact, validate it against the stable schema
   (``explain.validate_report``), strict-parse its JSON serialization, and
   render the text document (fusion decisions with at least one recorded
   not-chosen alternative and its cost, the DDR map, the bank plan);
3. **retune + plan diff** — re-run the tile search under a synthetic
   kernel-domain profile (forcing one unit to a non-default shape if the
   profile changes nothing) and assert ``explain.diff`` names *exactly* the
   units whose tile shape changed, with each side's predicted seconds;
4. **CLI** — ``python -m repro.explain`` on the saved artifact must emit
   strict-parseable JSON and the ``--diff`` of the pre/post-retune pair;
5. **live scrape** — serve the plan and GET ``/explain/<model>`` off the
   observability endpoint mid-serve; the route must return the same
   schema-valid report.

--smoke asserts all five gates and is wired into ``make ci`` as
``make explain-smoke``; the report JSON lands in benchmarks/out/ where CI
uploads it as an artifact.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import time
import urllib.request

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
    return g, qm, x


def _kernel_profile():
    """Synthetic kernel-domain profile dominated by per-cell overhead — a
    deterministic 'this machine prefers different tiles' world, so the
    retune changes shapes without any wall-clock measurement."""
    from repro.tune.profile import COEF_NAMES, DeviceProfile

    coef = [0.0] * len(COEF_NAMES)
    coef[COEF_NAMES.index("rd")] = 1e-12
    coef[COEF_NAMES.index("conv")] = 1e-12
    coef[COEF_NAMES.index("cells")] = 1e-4
    return DeviceProfile(name="cells", device="zu2", backend="pallas",
                         jax_version="bench", features="kernel",
                         combine="sum", coef=tuple(coef), deviation=0.0,
                         n_samples=3)


def measure_trace_overhead(g, dev, dv, repeats: int) -> dict:
    """min-of-N alternating search timings, trace on vs off."""
    from repro.core import pathsearch

    on, off = [], []
    for _ in range(repeats):
        t0 = time.perf_counter()
        pathsearch.search(g, dev, device_of=dv)
        on.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        pathsearch.search(g, dev, device_of=dv, trace=False)
        off.append(time.perf_counter() - t0)
    return {"search_s": min(on), "search_untraced_s": min(off),
            "overhead": min(on) / min(off) - 1.0}


def retune(g, qm, dev, strategy) -> list:
    """Tile-shape retune under the synthetic profile; guarantees at least one
    changed unit (forcing the first alternative candidate when the profile
    alone changes nothing).  Returns the changed tile keys."""
    from repro.core import lower, tiling
    from repro.tune import search_tile_shapes

    before = dict(strategy.meta.get("tile_shapes") or {})
    search_tile_shapes(g, qm, dev, strategy, profile=_kernel_profile())
    if dict(strategy.meta.get("tile_shapes") or {}) == before:
        for grp in strategy.groups:
            key = lower.tile_key(grp)
            cands = tiling.enumerate_tilings(g, list(grp), dev)
            alts = [(t.t_h, t.t_w, t.t_oc) for t in cands
                    if list((t.t_h, t.t_w, t.t_oc)) != before.get(key)]
            if alts:
                shapes = dict(strategy.meta.get("tile_shapes") or {})
                shapes[key] = [int(v) for v in alts[0]]
                strategy.meta["tile_shapes"] = shapes
                strategy.meta["tile_source"] = "measured"
                break
    after = dict(strategy.meta.get("tile_shapes") or {})
    return sorted(k for k in set(before) | set(after)
                  if before.get(k) != after.get(k))


def run_cli(argv) -> str:
    from repro.explain.__main__ import main as explain_main

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = explain_main(argv)
    assert not rc, f"repro.explain {argv} exited {rc}"
    return buf.getvalue()


def scrape_mid_serve(g, qm, strategy, dev, model: str, x) -> dict:
    """Serve the plan and GET /explain/<model> while requests are in flight."""
    from repro import asm
    from repro.core import quantize
    from repro.explain import live_report, validate_report
    from repro.runtime import Session

    art = asm.compile_strategy(g, strategy, dev, qm=qm)
    sess = Session.from_artifact(art, backend="pallas")
    rng = np.random.default_rng(1)
    reqs = [quantize.quantize_to(
        rng.standard_normal((1,) + tuple(g.shape("data")[1:]))
        .astype(np.float32), qm.f_a["data"]) for _ in range(8)]
    with sess.serve(max_batch=4, labels={"model": model}) as srv:
        obs = srv.serve_metrics(port=0)
        obs.add_explain(model, lambda: live_report(art))
        futs = [srv.submit(r) for r in reqs]
        with urllib.request.urlopen(obs.url("/explain")) as r:
            models = json.load(r)["models"]
        with urllib.request.urlopen(obs.url(f"/explain/{model}")) as r:
            scraped = json.load(r)
        for f in futs:
            f.result(timeout=120)
    assert model in models
    return validate_report(scraped)


def bench_model(model: str, img: int, *, plan_device: str,
                search_repeats: int, json_dir) -> dict:
    import os

    from repro import asm
    from repro.core import partition, pathsearch
    from repro.explain import diff, render_diff, render_report, report_of, \
        validate_report
    from repro.hw import get_device

    dev = get_device(plan_device)
    g, qm, x = build_quantized(model, img)
    dv = partition.device_of(g, "paper")

    overhead = measure_trace_overhead(g, dev, dv, search_repeats)

    # --- compile + report round trip ---------------------------------------
    s_a = pathsearch.search(g, dev, device_of=dv)
    art_a = asm.compile_strategy(g, s_a, dev, qm=qm)
    rep = validate_report(report_of(art_a))
    assert json.loads(json.dumps(rep)) == rep, "report not strictly JSON"
    n_alternatives = sum(len(ch["alternatives"])
                         for ch in rep["fusion"]["search"]["chains"])
    assert n_alternatives >= 1, "no recorded not-chosen alternative"
    text = render_report(rep)
    for marker in ("-- fusion", "-- search", "[not chosen]", "-- tiles",
                   "-- memory", "0x", "ping/pong", "-- schedule"):
        assert marker in text, f"report rendering lost section {marker!r}"

    # --- retune + plan diff -------------------------------------------------
    s_b = pathsearch.search(g, dev, device_of=dv)
    changed = retune(g, qm, dev, s_b)
    assert changed, "retune changed nothing; diff gate would be vacuous"
    art_b = asm.compile_strategy(g, s_b, dev, qm=qm)
    d = diff(art_a, art_b)
    diff_keys = sorted(c["key"] for c in d["tiles"]["changed"])
    assert diff_keys == changed, (
        f"diff named {diff_keys}, retune changed {changed}")
    assert not d["fusion"]["only_a"] and not d["fusion"]["only_b"]
    assert not d["identical"]
    render_diff(d)

    # --- CLI ----------------------------------------------------------------
    pa = os.path.join(json_dir, f"explain_{model}_a.npz")
    pb = os.path.join(json_dir, f"explain_{model}_b.npz")
    asm.save_artifact(art_a, pa)
    asm.save_artifact(art_b, pb)
    cli_rep = json.loads(run_cli([pa, "--format", "json"]))
    validate_report(cli_rep)
    assert cli_rep == rep
    assert "== compile report" in run_cli([pa])
    cli_diff = json.loads(run_cli([pa, "--diff", pb, "--format", "json"]))
    assert sorted(c["key"] for c in cli_diff["tiles"]["changed"]) == changed
    assert f"-- tiles changed" in run_cli([pa, "--diff", pb])

    # --- live scrape --------------------------------------------------------
    scraped = scrape_mid_serve(g, qm, s_a, dev, model, x)
    assert scraped == json.loads(json.dumps(rep))

    return {
        "model": model, "img": img, "plan_device": plan_device,
        **overhead,
        "n_groups": rep["fusion"]["n_groups"],
        "n_alternatives_recorded": n_alternatives,
        "n_regions": rep["memory"]["n_regions"],
        "tiles_changed_on_retune": changed,
        "report": rep,
        "diff": {k: v for k, v in d.items() if k != "report"},
    }


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", action="append", dest="models",
                    choices=["vgg16", "resnet50", "googlenet"], default=None)
    ap.add_argument("--img", type=int, default=32)
    ap.add_argument("--plan-device", default="zu2")
    ap.add_argument("--search-repeats", type=int, default=7,
                    help="alternating traced/untraced search timings")
    ap.add_argument("--json", dest="json_path", default=None,
                    help="bare names land in benchmarks/out/ (gitignored)")
    ap.add_argument("--smoke", action="store_true",
                    help="assert the acceptance gates")
    args = ap.parse_args(argv)
    args.json_path = outdir.resolve(args.json_path)
    import os
    json_dir = os.path.dirname(args.json_path) if args.json_path \
        else outdir.resolve("explain_bench.json").rsplit(os.sep, 1)[0]
    models = args.models or ["vgg16"]

    records = []
    for model in models:
        rec = bench_model(model, args.img, plan_device=args.plan_device,
                          search_repeats=args.search_repeats,
                          json_dir=json_dir)
        records.append(rec)
        print(f"{model}@{args.img} [{args.plan_device}] explain: "
              f"{rec['n_groups']} groups, "
              f"{rec['n_alternatives_recorded']} not-chosen alternatives, "
              f"{rec['n_regions']} DDR regions in report")
        print(f"  search {rec['search_s'] * 1e3:.1f} ms traced vs "
              f"{rec['search_untraced_s'] * 1e3:.1f} ms untraced "
              f"({rec['overhead']:+.1%} overhead)")
        print(f"  retune changed {len(rec['tiles_changed_on_retune'])} "
              f"tiles; diff named them exactly; /explain scrape OK")

    out = {"img": args.img, "plan_device": args.plan_device,
           "models": records}
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(out, f, indent=2, default=str)
        print(f"wrote {args.json_path}")

    if args.smoke:
        for rec in records:
            assert rec["overhead"] <= 0.05, (
                f"{rec['model']}: search tracing costs "
                f"{rec['overhead']:+.1%} > 5%")
            assert rec["n_alternatives_recorded"] >= 1
            assert rec["tiles_changed_on_retune"]
        print("EXPLAIN SMOKE OK: report schema-valid + strict JSON, "
              "diff names exactly the retuned tiles, CLI + /explain route "
              "serve it, tracing overhead within 5%")
    return out


if __name__ == "__main__":
    main()
