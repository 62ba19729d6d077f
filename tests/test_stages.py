"""Staged compile pipeline (ISSUE 7 tentpole): stage-key stability, partial
recompiles, cache-hit accounting, and the artifact-format backcompat pin."""
import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro import asm
from repro.core import executor, pathsearch, quantize
from repro.hw import ZU2
from repro.obs.metrics import MetricsRegistry
from repro.runtime import Session
from repro.stages import (Compiled, StageCache, artifact_stage_keys,
                          compile_model, wrap)
from tests.conftest import make_toy_resnet_graph, toy_params

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def toy():
    g = make_toy_resnet_graph()
    params = toy_params(g)
    x = np.random.default_rng(0).standard_normal(
        g.shape("data")).astype(np.float32)
    qm = quantize.calibrate(g, params, x, executor.run_float)
    return g, qm


def _counts(reg, what):
    return {s: (reg.get(f"stages.{s}.{what}").value
                if reg.get(f"stages.{s}.{what}") else 0.0)
            for s in ("wrapped", "lowered", "planned", "compiled")}


# ------------------------------------------------------ pipeline == monolith
def test_pipeline_matches_compile_strategy(toy):
    """The staged walk must produce the same object file as the one-call
    ``compile_strategy`` it refactors (same strategy search, same plan)."""
    g, qm = toy
    s = pathsearch.search(g, ZU2)
    art = asm.compile_strategy(g, s, ZU2, qm=qm)
    co = compile_model(g, qm, ZU2, cache=StageCache(
        registry=MetricsRegistry()))
    assert co.artifact.graph_sig == art.graph_sig
    assert asm.strategy_signature(co.artifact) == asm.strategy_signature(art)
    assert co.artifact.instrs == art.instrs
    assert co.artifact.sim_total_cycles == art.sim_total_cycles
    assert artifact_stage_keys(co.artifact) == artifact_stage_keys(art)
    assert co.stage_keys == artifact_stage_keys(art)


def test_warm_recompile_hits_all_four_stage_caches(toy):
    g, qm = toy
    reg = MetricsRegistry()
    sc = StageCache(registry=reg)
    co1 = compile_model(g, qm, ZU2, cache=sc)
    assert _counts(reg, "misses") == {s: 1.0 for s in _counts(reg, "misses")}
    co2 = compile_model(g, qm, ZU2, cache=sc)
    assert co2 is co1                    # the same stage object, not a copy
    assert _counts(reg, "hits") == {s: 1.0 for s in _counts(reg, "hits")}
    assert _counts(reg, "misses") == {s: 1.0 for s in _counts(reg, "misses")}


# --------------------------------------------------------- partial recompile
def test_pin_input_replans_without_researching(toy):
    """Changing a planner knob must re-run plan+compile only: Wrapped and
    Lowered are reused (one search total)."""
    g, qm = toy
    reg = MetricsRegistry()
    sc = StageCache(registry=reg)
    w = wrap(g, qm, ZU2, cache=sc)
    lo = w.lower()
    p0 = lo.plan()
    p1 = lo.plan(pin_input=True)
    assert p0.key != p1.key
    assert p1.compile().artifact.pin_input
    assert not p0.compile().artifact.pin_input
    assert reg.get("stages.lowered.misses").value == 1.0
    assert reg.get("stages.planned.misses").value == 2.0


def test_ddr_budget_replans_and_enforces_capacity(toy):
    g, qm = toy
    sc = StageCache(registry=MetricsRegistry())
    lo = wrap(g, qm, ZU2, cache=sc).lower()
    p0 = lo.plan()
    # a roomy budget replans fine (new stage key, same upstream search) ...
    p1 = lo.plan(ddr_budget_bytes=p0.peak_ddr_bytes * 2)
    assert p1.key != p0.key
    assert p1.peak_ddr_bytes == p0.peak_ddr_bytes
    # ... and a budget below the plan's peak is refused by the planner
    with pytest.raises(Exception, match="(?i)ddr|capacity|exceed"):
        lo.plan(ddr_budget_bytes=max(1, p0.peak_ddr_bytes // 2))


def test_profile_perturbation_invalidates_lowered_not_wrapped(toy):
    """A different device profile must invalidate Lowered-and-later only:
    the Wrapped stage (graph + quant + device) is untouched."""
    from repro.tune.profile import COEF_NAMES, DeviceProfile

    def prof(scale):
        return DeviceProfile(name=f"p{scale:g}", device="zu2",
                             backend="pallas", jax_version="t",
                             features="kernel", combine="sum",
                             coef=tuple(scale * (i + 1) * 1e-9
                                        for i in range(len(COEF_NAMES))),
                             deviation=0.0, n_samples=3)

    g, qm = toy
    reg = MetricsRegistry()
    sc = StageCache(registry=reg)
    co_a = compile_model(g, qm, ZU2, profile=prof(1.0), cache=sc)
    co_b = compile_model(g, qm, ZU2, profile=prof(4.0), cache=sc)
    assert reg.get("stages.wrapped.hits").value == 1.0      # reused
    assert reg.get("stages.lowered.misses").value == 2.0    # re-searched
    assert co_a.stage_keys["wrapped"] == co_b.stage_keys["wrapped"]
    assert co_a.stage_keys["lowered"] != co_b.stage_keys["lowered"]
    assert co_a.stage_keys["planned"] != co_b.stage_keys["planned"]
    assert co_a.artifact.profile_hash == prof(1.0).hash()
    assert co_b.artifact.profile_hash == prof(4.0).hash()


def test_retune_copies_strategy_and_reuses_search(toy):
    """``Lowered.retune`` re-runs only the tile search: the input stage's
    strategy is never mutated and pathsearch is not re-run."""
    from repro.tune.profile import COEF_NAMES, DeviceProfile

    prof = DeviceProfile(name="t", device="zu2", backend="pallas",
                         jax_version="t", features="kernel", combine="sum",
                         coef=tuple((i + 1) * 1e-9
                                    for i in range(len(COEF_NAMES))),
                         deviation=0.0, n_samples=3)
    g, qm = toy
    lo = wrap(g, qm, ZU2, cache=None).lower()
    before = dict(lo.strategy.meta)
    lo2 = lo.retune(profile=prof)
    assert lo.strategy.meta == before            # input stage untouched
    assert lo2.wrapped is lo.wrapped
    assert lo2.strategy.meta.get("tile_source") == "profile"
    assert lo2.strategy.groups == lo.strategy.groups   # same partition
    co = lo2.plan().compile()
    assert co.artifact.profile_hash == prof.hash()


# ------------------------------------------------------- cross-process keys
def test_stage_keys_stable_across_processes(toy):
    """Same net + params must reach identical stage hashes in a different
    interpreter — the property the on-disk zoo's addressing relies on."""
    g, qm = toy
    co = wrap(g, qm, ZU2, cache=None).lower().plan().compile()
    code = (
        "import sys, json\n"
        "sys.path.insert(0, 'src'); sys.path.insert(0, '.')\n"
        "import numpy as np\n"
        "from repro.core import executor, quantize\n"
        "from repro.hw import ZU2\n"
        "from repro.stages import wrap\n"
        "from tests.conftest import make_toy_resnet_graph, toy_params\n"
        "g = make_toy_resnet_graph()\n"
        "params = toy_params(g)\n"
        "x = np.random.default_rng(0).standard_normal("
        "g.shape('data')).astype(np.float32)\n"
        "qm = quantize.calibrate(g, params, x, executor.run_float)\n"
        "co = wrap(g, qm, ZU2, cache=None).lower(cache=None)"
        ".plan(cache=None).compile(cache=None)\n"
        "print(json.dumps(co.stage_keys))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=str(REPO),
                         capture_output=True, text=True, check=True)
    assert json.loads(out.stdout.strip().splitlines()[-1]) == co.stage_keys


# ------------------------------------------------------------ backcompat pin
def test_saved_artifact_reopens_with_identical_stage_keys(toy, tmp_path):
    """A format-v4 npz written by the compile path must reopen as a
    ``Compiled`` stage with the SAME content address — otherwise every zoo
    entry would orphan on upgrade."""
    g, qm = toy
    co = compile_model(g, qm, ZU2, cache=StageCache(
        registry=MetricsRegistry()))
    path = str(tmp_path / "m.npz")
    co.save(path)
    re = Compiled.from_artifact(asm.load_artifact(path))
    assert re.key == co.key
    assert re.stage_keys == co.stage_keys
    # and it still serves, bit-exactly
    x = np.random.default_rng(1).integers(-128, 127,
                                          g.shape("data"), np.int8)
    got = Session.from_artifact(re.artifact).run(x)
    want = Session.from_artifact(co.artifact).run(x)
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])


def test_compile_strategy_is_a_thin_stage_wrapper(toy):
    """The legacy one-call API keeps byte-identical behavior: no global
    stage-cache participation (pure recompute), same artifact content."""
    g, qm = toy
    from repro.stages import STAGE_CACHE
    s = pathsearch.search(g, ZU2)
    before = len(STAGE_CACHE)
    a1 = asm.compile_strategy(g, s, ZU2, qm=qm)
    a2 = asm.compile_strategy(g, s, ZU2, qm=qm)
    assert len(STAGE_CACHE) == before          # no pollution of the global
    assert a1 is not a2                        # pure recompute, as before
    assert a1.instrs == a2.instrs
