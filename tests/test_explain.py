"""Compile-decision provenance (ISSUE 9): search trace, CompileReport,
artifact plan-diff, renderer goldens, and the runtime explain surfaces."""
import copy
import json
import os
import urllib.request

import numpy as np
import pytest

from conftest import make_toy_resnet_graph, toy_params

GOLDEN = os.path.join(os.path.dirname(__file__), "data", "explain_golden.txt")


def _quantized_toy():
    from repro.core import executor, quantize

    g = make_toy_resnet_graph()
    params = toy_params(g)
    x = np.random.default_rng(0).standard_normal(
        g.shape("data")).astype(np.float32)
    qm = quantize.calibrate(g, params, x, executor.run_float)
    xq = quantize.quantize_to(x, qm.f_a["data"])
    return g, qm, xq


@pytest.fixture(scope="module")
def compiled():
    from repro import asm, hw
    from repro.core import pathsearch

    g, qm, xq = _quantized_toy()
    dev = hw.get_device("zu2")
    s = pathsearch.search(g, dev)
    art = asm.compile_strategy(g, s, dev, qm)
    return g, s, dev, qm, xq, art


def _retiled_artifact(compiled):
    """A second compilation of the same strategy with one group's tile shape
    moved to a different feasible candidate — the minimal 'retune' pair."""
    from repro import asm
    from repro.core import lower, tiling

    g, s, dev, qm, _, art = compiled
    for grp in s.groups:
        cands = tiling.enumerate_tilings(g, list(grp), dev)
        current = s.meta.get("tile_shapes", {}).get(lower.tile_key(grp))
        alts = [(t.t_h, t.t_w, t.t_oc) for t in cands
                if list((t.t_h, t.t_w, t.t_oc)) != current]
        if alts:
            key, alt = lower.tile_key(grp), alts[0]
            break
    else:
        pytest.skip("no alternative feasible tiling on the toy net")
    s2 = copy.copy(s)
    s2.meta = dict(s.meta)
    shapes = dict(s2.meta.get("tile_shapes") or {})
    shapes[key] = [int(v) for v in alt]
    s2.meta["tile_shapes"] = shapes
    s2.meta["tile_source"] = "measured"
    return key, asm.compile_strategy(g, s2, dev, qm)


# ------------------------------------------------------------- search trace
def test_search_trace_records_decisions(compiled):
    g, s, dev, *_ = compiled
    tr = s.meta["search_trace"]
    json.dumps(tr)                                   # JSON-native throughout
    assert tr["n_chains"] == len(tr["chains"]) == tr["n_chains_recorded"]
    assert tr["templates"] and tr["n_fusable_pairs"] > 0
    # at least one scored-but-not-chosen alternative with its cost...
    alts = [a for ch in tr["chains"] for a in ch["alternatives"]]
    assert alts and all(a["cost_s"] > 0 for a in alts)
    # ...and at least one rejection with a machine-readable reason
    from repro.core.pathsearch import REJECT_REASONS
    rejects = [ex for ch in tr["chains"] for ex in ch["rejected_examples"]]
    assert rejects and all(ex["reason"] in REJECT_REASONS for ex in rejects)
    assert all(ch["frontier"] >= len(ch["chosen"]) for ch in tr["chains"])
    # every final group has a direct cost on record
    from repro.core.lower import tile_key
    for grp in s.groups:
        assert tile_key(grp) in tr["group_costs"]
    assert tr["total_cost_s"] == pytest.approx(s.cost)
    # the toy net exercises both barrier heuristics
    assert any(e["absorbed"] for e in tr["eltwise_absorb"])
    assert any(h["fused"] for h in tr["horizontal"])


def test_search_trace_optional():
    from repro.core import pathsearch
    from repro.hw import get_device

    g = make_toy_resnet_graph()
    dev = get_device("zu2")
    s_on = pathsearch.search(g, dev)
    s_off = pathsearch.search(g, dev, trace=False)
    assert "search_trace" not in s_off.meta
    # tracing must not change the strategy itself
    assert [list(grp) for grp in s_off.groups] == \
        [list(grp) for grp in s_on.groups]
    assert s_off.cost == pytest.approx(s_on.cost)


# ------------------------------------------------------------ CompileReport
def test_report_embedded_and_schema_stable(compiled):
    from repro.explain import validate_report

    *_, art = compiled
    rep = art.report
    validate_report(rep)
    # strict JSON round trip (what the npz serialization and the HTTP route
    # both do) must preserve the report exactly
    assert json.loads(json.dumps(rep)) == rep
    assert rep["fusion"]["n_groups"] == len(art.groups)
    assert rep["memory"]["regions"], "DDR allocation map must be present"
    offsets = [r["offset"] for r in rep["memory"]["regions"]]
    assert offsets == sorted(offsets)
    assert rep["schedule"]["n_instrs"] == len(art.instrs)
    assert sum(rep["schedule"]["engines"].values()) == len(art.instrs)


def test_report_survives_npz_roundtrip(compiled, tmp_path):
    from repro import asm
    from repro.explain import report_of, validate_report

    *_, art = compiled
    p = os.path.join(tmp_path, "a.npz")
    asm.save_artifact(art, p)
    art2 = asm.load_artifact(p)
    assert art2.report == art.report
    validate_report(report_of(art2))
    assert art2.search_trace == art.search_trace


def test_golden_text_render(compiled):
    from repro.explain import render_report

    *_, art = compiled
    got = render_report(art.report)
    with open(GOLDEN) as f:
        want = f.read()
    assert got == want, (
        "text renderer output drifted from tests/data/explain_golden.txt — "
        "if the change is intentional, regenerate the golden:\n"
        "PYTHONPATH=src:tests python tests/data/gen_explain_golden.py")


def test_report_of_v4_artifact_degrades(compiled, tmp_path):
    """v4 object files (no embedded report) must still load and explain."""
    from repro import asm
    from repro.explain import render_report, report_of, validate_report

    *_, art = compiled
    p = os.path.join(tmp_path, "v5.npz")
    asm.save_artifact(art, p)
    with np.load(p, allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files}
    meta = json.loads(str(arrays["meta_json"]))
    meta["format_version"] = 4
    for key in ("compile_report", "search_trace", "tile_provenance"):
        meta["meta"].pop(key, None)
    arrays["meta_json"] = np.asarray(json.dumps(meta))
    p4 = os.path.join(tmp_path, "v4.npz")
    with open(p4, "wb") as f:
        np.savez_compressed(f, **arrays)

    art4 = asm.load_artifact(p4)
    assert art4.report is None
    rep = report_of(art4)                            # degraded, no crash
    validate_report(rep)
    assert rep["degraded"] is True
    assert rep["fusion"]["n_groups"] == len(art4.groups)
    assert rep["memory"]["regions"] == []            # map not serialized pre-v5
    assert "degraded" in render_report(rep)


def test_tile_provenance_roundtrip_bounded(compiled, tmp_path):
    """Satellite: tile_provenance used to be dropped at serialization; it
    must survive the npz round trip, bounded to top-K candidates per unit."""
    from repro import asm
    from repro.asm.artifact import TILE_PROVENANCE_MAX_CANDIDATES

    g, s, dev, qm, _, _ = compiled
    s2 = copy.copy(s)
    s2.meta = dict(s.meta)
    # synthesize a deep leaderboard (more candidates than the bound keeps)
    s2.meta["tile_provenance"] = [{
        "key": "c1", "nodes": ["c1"], "kind": "chain", "default": [16, 16, 16],
        "chosen": [8, 16, 16], "source": "measured",
        "candidates": [{"shape": [16, 16, 16], "default": True,
                        "predicted": 1e-3, "measured": 2e-3, "spread": 0.01}]
        + [{"shape": [8, 16, 16 + i], "default": False,
            "predicted": 1e-3 + i * 1e-5, "measured": float("nan") if i == 0
            else 2e-3 + i * 1e-5, "spread": 0.01}
           for i in range(20)],
    }]
    art = asm.compile_strategy(g, s2, dev, qm)
    p = os.path.join(tmp_path, "prov.npz")
    asm.save_artifact(art, p)
    art2 = asm.load_artifact(p)

    prov = art2.tile_provenance
    assert len(prov) == 1
    unit = prov[0]
    assert unit["key"] == "c1" and unit["chosen"] == [8, 16, 16]
    assert len(unit["candidates"]) <= TILE_PROVENANCE_MAX_CANDIDATES
    assert unit["n_candidates"] == 21                # full count recorded
    assert unit["candidates"][0]["default"] is True  # default always kept
    # kept non-default candidates are the best-ranked ones, NaN sanitized
    assert unit["candidates"][1]["measured"] is None
    assert art2.report["tiles"]["leaderboard"] == prov


def test_measured_search_provenance_reaches_artifact(compiled):
    """The real tune.tiles leaderboard (not a synthetic one) lands in the
    compiled artifact and names each unit by its tile_key."""
    from repro import asm
    from repro.core import lower
    from repro.tune import MeasurementHarness
    from repro.tune.tiles import search_tile_shapes

    g, s, dev, qm, _, _ = compiled
    s2 = copy.copy(s)
    s2.meta = dict(s.meta)
    harness = MeasurementHarness(g, qm, dev, repeats=1)
    rep = search_tile_shapes(g, qm, dev, s2, harness=harness, top_k=1,
                             min_measurable_s=0.0)
    assert rep.provenance
    assert all(u["key"] == lower.tile_key(u["nodes"]) for u in rep.provenance)
    art = asm.compile_strategy(g, s2, dev, qm)
    assert art.tile_provenance
    keys = {u["key"] for u in art.tile_provenance}
    assert all(k in keys for k in art.tile_shapes)


# ---------------------------------------------------------------------- diff
def test_diff_self_is_empty(compiled):
    from repro.explain import diff

    *_, art = compiled
    d = diff(art, art)
    assert d["identical"] is True
    assert d["fusion"]["only_a"] == d["fusion"]["only_b"] == []
    assert d["tiles"]["changed"] == [] and d["tiles"]["n_changed"] == 0
    assert d["cost"]["total_cost_s"]["delta"] == 0


def test_diff_names_exactly_the_changed_tiles(compiled):
    from repro.explain import diff, negate, render_diff

    *_, art_a = compiled
    key, art_b = _retiled_artifact(compiled)
    d = diff(art_a, art_b)
    assert d["identical"] is False
    assert [c["key"] for c in d["tiles"]["changed"]] == [key]
    (change,) = d["tiles"]["changed"]
    assert change["a"] != change["b"] and change["b"] is not None
    # fusion did not change, only the tile
    assert d["fusion"]["only_a"] == d["fusion"]["only_b"] == []
    # antisymmetry: the diff carries no argument-order information beyond
    # the a/b labelling
    assert diff(art_a, art_b) == negate(diff(art_b, art_a))
    assert diff(art_b, art_a) == negate(diff(art_a, art_b))
    text = render_diff(d)
    assert key in text


def test_diff_emits_plan_diff_event(compiled):
    from repro.explain import diff
    from repro.obs.events import EVENTS

    *_, art = compiled
    seen = []
    sub = seen.append
    EVENTS.subscribe(sub)
    try:
        diff(art, art)
    finally:
        EVENTS.unsubscribe(sub)
    kinds = [e.kind for e in seen]
    assert "plan.diff" in kinds
    ev = next(e for e in seen if e.kind == "plan.diff")
    assert ev.fields["identical"] is True
    assert ev.fields["n_tiles_changed"] == 0


# ------------------------------------------------------------------ runtime
def test_fallback_reason_counters(compiled):
    """Satellite: RefFallback launches export per-reason labelled counters
    (``executor.fallback{reason=...}``), not just the aggregate."""
    from repro.core import partition, pathsearch
    from repro.core.executor import Int8Executor
    from repro.obs.metrics import REGISTRY

    g, _, dev, qm, xq, _ = compiled
    dv = partition.device_of(g, "paper")           # fc1 -> host: a fallback
    s = pathsearch.search(g, dev, device_of=dv)
    run = Int8Executor(g, qm, strategy=s, backend="pallas")
    reasons = {fb.reason for fb in run.program.fallbacks()}
    assert "host_op" in reasons

    def snapshot():
        by = REGISTRY.labelled("executor.fallback", label="reason")
        return {r: (by[r].value if r in by else 0.0) for r in reasons}

    before = snapshot()
    run(xq)
    after = snapshot()
    for r in reasons:
        n = sum(1 for fb in run.program.fallbacks() if fb.reason == r)
        assert after[r] == before[r] + n


def test_session_explain_joins_drift(compiled):
    """``live_report`` joins the artifact's report with the drift samples of
    the session serving it."""
    from repro import asm
    from repro.core.cost import SimulatorEvaluator
    from repro.explain import live_report, render_report, validate_report
    from repro.obs.drift import DriftProfiler
    from repro.obs.events import EVENTS
    from repro.obs.metrics import MetricsRegistry
    from repro.runtime import Session
    from repro.tune import calibrate
    from repro.tune.evaluator import predict_item_seconds

    g, s, dev, qm, xq, _ = compiled
    sim = SimulatorEvaluator(g, dev)
    prof = calibrate(g, qm, dev, measure_fn=lambda grp: sim(grp),
                     features="analytic").profile
    art = asm.compile_strategy(g, s, dev, qm=qm, profile=prof)
    sess = Session.from_artifact(art, backend="pallas")
    rep = live_report(art, sess.drift)
    validate_report(rep)
    assert "drift" not in rep                       # no profiler attached

    # an undrifted world: measurements ARE the profile's own predictions
    dp = DriftProfiler.from_session(
        sess, art, prof, every=1, registry=MetricsRegistry(),
        measure_fn=lambda item: predict_item_seconds(prof, g, dev, item))
    sess.attach_drift(dp)
    dp.sample()
    seen = []
    sub = seen.append
    EVENTS.subscribe(sub)
    try:
        rep = live_report(art, sess.drift)
    finally:
        EVENTS.unsubscribe(sub)
    assert rep["drift"]["units"]
    planned = {n for grp in rep["fusion"]["groups"] for n in grp["nodes"]}
    for u in rep["drift"]["units"]:
        assert u["measured"] == pytest.approx(u["predicted"])
        # report-style keys ("|"-joined), every node from the compiled plan
        assert "+" not in u["key"]
        assert set(u["key"].split("|")) <= planned
    assert rep["drift"]["drifted"] is False
    assert rep["drift"]["profile_match"] is True
    assert any(e.kind == "explain.report" for e in seen)
    text = render_report(rep, drift=rep["drift"]["units"])
    assert "live drift" in text


def test_http_explain_route(compiled):
    from repro.explain import validate_report
    from repro.obs import MetricsRegistry
    from repro.obs.export import ObsHTTPServer

    *_, art = compiled
    rep = art.report
    reg = MetricsRegistry()
    with ObsHTTPServer(reg, port=0) as srv:
        srv.add_explain("toy", lambda: rep)
        with urllib.request.urlopen(srv.url("/explain")) as r:
            assert json.load(r)["models"] == ["toy"]
        with urllib.request.urlopen(srv.url("/explain/toy")) as r:
            got = json.load(r)
        validate_report(got)
        assert got == json.loads(json.dumps(rep))
        try:
            urllib.request.urlopen(srv.url("/explain/nope"))
            assert False, "unknown model must 404"
        except urllib.error.HTTPError as e:
            assert e.code == 404
        assert reg.counter("obs.explain_scrapes",
                           {"model": "toy"}).value == 1
