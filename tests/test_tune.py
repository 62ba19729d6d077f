"""Autotuner (ISSUE 4 tentpole): device-profile round trips, calibration
against simulator ground truth, profile-guided search determinism, and the
artifact profile-hash provenance chain."""
import json
import math

import numpy as np
import pytest

from repro import asm
from repro.core import executor, pathsearch, quantize
from repro.core.cost import SimulatorEvaluator
from repro.hw import ZU2
from repro.runtime import Session
from repro.tune import (CalibratedEvaluator, DeviceProfile, MeasurementHarness,
                        ProfileCache, calibrate, load_profile, save_profile)
from repro.tune.profile import COEF_NAMES
from tests.conftest import make_toy_resnet_graph, toy_params


@pytest.fixture(scope="module")
def toy():
    g = make_toy_resnet_graph()
    params = toy_params(g)
    rng = np.random.default_rng(0)
    x = rng.standard_normal(g.shape("data")).astype(np.float32)
    qm = quantize.calibrate(g, params, x, executor.run_float)
    return g, qm


@pytest.fixture(scope="module")
def sim_calibration(toy):
    """Calibration with the cycle simulator as injected ground truth."""
    g, qm = toy
    sim = SimulatorEvaluator(g, ZU2)
    return calibrate(g, qm, ZU2, measure_fn=lambda grp: sim(grp),
                     features="analytic")


def _profile(**overrides) -> DeviceProfile:
    base = dict(name="unit", device="zu2", backend="pallas",
                jax_version="0.0-test", features="kernel", combine="sum",
                coef=tuple(float(i + 1) * 1e-9
                           for i in range(len(COEF_NAMES))),
                deviation=0.07, n_samples=12)
    base.update(overrides)
    return DeviceProfile(**base)


# ------------------------------------------------------------ profile store
def test_profile_json_round_trip(tmp_path):
    p = _profile()
    path = str(tmp_path / "prof.json")
    save_profile(p, path)
    q = load_profile(path)
    assert q == p
    assert q.hash() == p.hash()


def test_profile_hash_tracks_predictive_fields_only():
    p = _profile()
    # fit provenance does not change identity...
    assert p.hash() == _profile(deviation=0.2, n_samples=99,
                                meta={"x": 1}).hash()
    assert p.hash() == _profile(name="other").hash()
    # ...but any coefficient or form change does
    coef = list(p.coef)
    coef[2] *= 2
    assert p.hash() != _profile(coef=tuple(coef)).hash()
    assert p.hash() != _profile(combine="max").hash()


def test_profile_corruption_detected(tmp_path):
    p = _profile()
    path = str(tmp_path / "prof.json")
    save_profile(p, path)
    payload = json.load(open(path))
    payload["coef"][0] = 123.0          # tamper without updating the hash
    json.dump(payload, open(path, "w"))
    with pytest.raises(ValueError, match="hash mismatch"):
        load_profile(path)


def test_profile_cache_key_stability_and_round_trip(tmp_path):
    cache = ProfileCache(str(tmp_path))
    k1 = cache.key("zu2", "pallas", "0.4.37")
    assert k1 == cache.key("zu2", "pallas", "0.4.37")       # deterministic
    assert k1 != cache.key("zu9", "pallas", "0.4.37")
    assert k1 != cache.key("zu2", "ref", "0.4.37")
    assert "/" not in k1 and " " not in k1                   # path-safe
    p = _profile(jax_version="0.4.37")
    cache.put(p)
    assert cache.get("zu2", "pallas", "0.4.37") == p
    assert cache.get("zu9", "pallas", "0.4.37") is None
    assert cache.get_by_name("unit") == p


def test_profile_effective_summary_and_device_projection():
    p = _profile()
    eff = p.effective_summary(ZU2)
    assert eff["dram_rd_bytes_per_s"] == pytest.approx(1.0 / p.coef[0])
    dev2 = p.to_device_model(ZU2)
    assert dev2.dram_bw_bytes_per_s == pytest.approx(1.0 / p.coef[0])
    assert dev2.peak_ops_override == pytest.approx(2.0 / p.coef[2])
    # unfitted coefficients keep the base device's values
    coef = list(p.coef)
    coef[0] = 0.0
    dev3 = _profile(coef=tuple(coef)).to_device_model(ZU2)
    assert dev3.dram_bw_bytes_per_s == ZU2.dram_bw_bytes_per_s


# ------------------------------------------------------------- calibration
def test_calibration_fits_simulator_ground_truth(sim_calibration):
    """Fitting the analytic pipeline model against the simulator must land
    well inside the paper's learned-model band (5-10%) — the simulator IS
    the pipeline model plus scheduling detail."""
    res = sim_calibration
    assert res.report["n_samples"] >= 10
    assert res.report["deviation"] <= 0.10, res.report["deviation"]
    assert res.report["within_accept_band"]
    # the pipeline (max+fill) form must beat the sequential-sum form on
    # ground truth generated by a pipelined time wheel
    forms = res.report["deviation_by_form"]
    assert forms["max"] <= forms["sum"]
    assert res.profile.combine == "max"


def test_calibration_refits_model_evaluator(sim_calibration, toy):
    res = sim_calibration
    assert res.model is not None
    assert res.model.fit_mape <= 0.15
    g, _ = toy
    got = res.model(["c1"])
    assert math.isfinite(got) and got > 0


def test_calibrated_evaluator_matches_fit_predictions(sim_calibration, toy):
    """The evaluator the search consumes must reproduce the calibration
    report's predictions — same features, same coefficients, same form."""
    g, _ = toy
    ev = CalibratedEvaluator(g, ZU2, sim_calibration.profile)
    for s in sim_calibration.report["samples"]:
        assert ev(list(s["nodes"])) == pytest.approx(s["predicted"], rel=1e-6)


def test_calibrated_evaluator_preserves_infeasibility(sim_calibration, toy):
    g, _ = toy
    ev = CalibratedEvaluator(g, ZU2, sim_calibration.profile)
    from repro.core.cost import AnalyticEvaluator
    ana = AnalyticEvaluator(g, ZU2)
    # every group the calibrated search picks gets a finite calibrated cost
    # AND is still placeable under the analytic tiling solver (a profile must
    # never make an unplaceable group placeable)
    s = pathsearch.search(g, ZU2, evaluator=ev)
    for grp in s.groups:
        assert math.isfinite(ev(list(grp)))
        assert ana.cost(list(grp)).feasible


def test_search_deterministic_under_calibrated_evaluator(sim_calibration, toy):
    g, _ = toy
    p = sim_calibration.profile
    s1 = pathsearch.search(g, ZU2, evaluator=CalibratedEvaluator(g, ZU2, p))
    s2 = pathsearch.search(g, ZU2, evaluator=CalibratedEvaluator(g, ZU2, p))
    assert s1.groups == s2.groups
    assert s1.horizontal == s2.horizontal
    assert s1.cost == pytest.approx(s2.cost)
    assert s1.meta["evaluator"] == "CalibratedEvaluator"
    assert s1.meta["profile_hash"] == p.hash()


# ------------------------------------------------------- measurement harness
def test_harness_measures_lowered_units(toy):
    g, qm = toy
    h = MeasurementHarness(g, qm, ZU2, repeats=3, warmup=1)
    m = h.measure_group(["c1"])
    assert m.seconds > 0 and m.kind == "chain"
    assert len(m.samples) == 3
    assert h.measure_group(["c1"]) is m          # memoized
    ms = h.measure_set([["c1"], ["c2a", "c2b"]], passes=2)
    assert all(x.seconds > 0 for x in ms)
    assert len(ms[1].samples) == 2


def test_harness_strategy_ab(toy):
    g, qm = toy
    h = MeasurementHarness(g, qm, ZU2, repeats=2, warmup=1, backend="ref")
    s = pathsearch.search(g, ZU2)
    n = pathsearch.naive(g, ZU2)
    m_s, m_n = h.measure_strategy_set([s, n], passes=2)
    assert m_s.seconds > 0 and m_n.seconds > 0
    assert m_s.kind == "e2e" and len(m_s.samples) == 2


# ------------------------------------------------- artifact/session plumbing
def test_artifact_records_profile_hash_round_trip(sim_calibration, toy,
                                                  tmp_path):
    g, qm = toy
    p = sim_calibration.profile
    s = pathsearch.search(g, ZU2, evaluator=CalibratedEvaluator(g, ZU2, p))
    art = asm.compile_strategy(g, s, ZU2, qm=qm, profile=p)
    assert art.profile_hash == p.hash()
    assert art.meta["profile_name"] == p.name
    path = str(tmp_path / "tuned.npz")
    asm.save_artifact(art, path)
    loaded = asm.load_artifact(path)
    assert loaded.profile_hash == p.hash()
    assert loaded.meta["profile_name"] == p.name


def test_strategy_carries_profile_provenance_into_cache(sim_calibration, toy):
    """A strategy picked by a profile-guided search keys the plan cache by
    the profile hash even when the Session is not told about the profile."""
    g, qm = toy
    p = sim_calibration.profile
    s_cal = pathsearch.search(g, ZU2,
                              evaluator=CalibratedEvaluator(g, ZU2, p))
    s_ana = pathsearch.search(g, ZU2)
    cache = asm.PlanCache()
    k_cal = cache.key(g, s_cal, ZU2, qm)
    k_ana = cache.key(g, s_ana, ZU2, qm)
    if s_cal.groups == s_ana.groups and s_cal.horizontal == s_ana.horizontal:
        assert k_cal != k_ana                    # same plan, different oracle
    art, _ = cache.get_or_compile(g, s_cal, ZU2, qm=qm)
    assert art.profile_hash == p.hash()


def test_session_compiles_under_named_profile(sim_calibration, toy, tmp_path):
    g, qm = toy
    p = sim_calibration.profile
    cache_dir = str(tmp_path / "profiles")
    ProfileCache(cache_dir).put(p)
    import os
    old = os.environ.get("DNNVM_PROFILE_CACHE")
    os.environ["DNNVM_PROFILE_CACHE"] = cache_dir
    try:
        from repro.stages import wrap
        s = pathsearch.search(g, ZU2)
        lo = wrap(g, qm, ZU2, cache=None).lower(strategy=s, profile=p.name,
                                                cache=None)
        assert lo.profile == p                  # the name resolved
        art = lo.plan(cache=None).compile(cache=None).artifact
        assert art.profile_hash == p.hash()
        assert art.meta["profile_name"] == p.name
        # a session serves the profile-planned artifact as it is
        sess = Session.from_artifact(art)
        out = sess.run(np.zeros(tuple(g.shape("data")[1:]), np.int8))
        assert set(out) == set(sess.outputs)
    finally:
        if old is None:
            os.environ.pop("DNNVM_PROFILE_CACHE", None)
        else:
            os.environ["DNNVM_PROFILE_CACHE"] = old
