"""Multi-tenant serving: routing bit-exactness, DDR partitioning, admission
control, and the bounded shared plan cache."""
import numpy as np
import pytest

from repro import asm
from repro.core import executor, pathsearch, quantize
from repro.hw import ZU2
from repro.obs.metrics import REGISTRY
from repro.runtime import AdmissionError, MultiServer, Session
from tests.conftest import make_toy_resnet_graph, toy_params


def _inputs(seed):
    """Graph, quantized model and strategy of one toy model; ``seed``
    differentiates the weights."""
    g = make_toy_resnet_graph()
    params = toy_params(g, seed=seed)
    x = np.random.default_rng(seed).standard_normal(
        g.shape("data")).astype(np.float32)
    qm = quantize.calibrate(g, params, x, executor.run_float)
    return g, qm, pathsearch.search(g, ZU2)


def _model(seed):
    """One compiled toy model: the object file a tenant is admitted with."""
    g, qm, s = _inputs(seed)
    return asm.compile_strategy(g, s, ZU2, qm=qm)


def _request_shape(art):
    return tuple(art.rebuild_graph().shape("data")[1:])


@pytest.fixture(scope="module")
def two_models():
    return _model(0), _model(1)


# ----------------------------------------------------------------- routing
def test_routing_is_bit_exact_per_tenant(two_models):
    """Interleaved streams for two co-resident models must produce exactly
    what each model's own session produces in isolation."""
    sa, sb = two_models
    alone = {"a": Session.from_artifact(sa), "b": Session.from_artifact(sb)}
    rng = np.random.default_rng(3)
    xs = rng.integers(-128, 127, (6,) + _request_shape(sa), np.int8)
    with MultiServer() as ms:
        ms.add_model("a", sa, slo="gold", max_latency_s=1e-4, warmup=False)
        ms.add_model("b", sb, slo="silver", max_latency_s=1e-4, warmup=False)
        futs = [(name, x, ms.submit(name, x))
                for x in xs for name in ("a", "b")]
        for name, x, fut in futs:
            want = alone[name].run(x)
            got = fut.result(timeout=30)
            for k in want:
                np.testing.assert_array_equal(got[k], want[k])
    st = ms.stats()
    assert st["models"]["a"]["n_served"] == len(xs)
    assert st["models"]["b"]["n_served"] == len(xs)
    assert st["slo"] == {"a": "gold", "b": "silver"}


# ---------------------------------------------------------- DDR partitioning
def test_ddr_partition_is_disjoint_and_bounded(two_models):
    sa, sb = two_models
    with MultiServer() as ms:
        ms.add_model("a", sa, warmup=False)
        ms.add_model("b", sb, warmup=False)
        parts = ms.ddr_partition()
    assert parts[0]["base"] == 0
    assert parts[1]["base"] == parts[0]["bytes"]       # disjoint regions
    used = sum(p["bytes"] for p in parts)
    assert used <= ZU2.ddr_bytes
    assert ms.stats()["ddr_used_bytes"] == used


def test_add_model_refused_when_ddr_budget_exhausted(two_models):
    sa, sb = two_models
    budget = int(sa.peak_ddr_bytes * 1.5)              # fits one, not two
    with MultiServer(ddr_budget_bytes=budget) as ms:
        ms.add_model("a", sa, warmup=False)
        with pytest.raises(MemoryError, match="DDR"):
            ms.add_model("b", sb, warmup=False)
        assert ms.models() == ["a"]
        # removing the resident model frees its region
        ms.remove_model("a")
        ms.add_model("b", sb, warmup=False)
        assert ms.ddr_partition()[0]["base"] == 0


def test_device_and_name_conflicts_rejected(two_models):
    sa, _ = two_models
    with MultiServer() as ms:
        ms.add_model("a", sa, warmup=False)
        with pytest.raises(ValueError, match="already registered"):
            ms.add_model("a", sa, warmup=False)
        with pytest.raises(ValueError, match="unknown SLO"):
            ms.add_model("c", sa, slo="platinum", warmup=False)


# --------------------------------------------------------- admission control
def test_admission_control_sheds_load(two_models):
    sa, _ = two_models
    x = np.zeros(_request_shape(sa), np.int8)
    with MultiServer() as ms:
        ms.add_model("a", sa, max_queue=0, warmup=False)
        with pytest.raises(AdmissionError):
            ms.submit("a", x)
    assert REGISTRY.get("serve.rejected{model=a}").value >= 1.0


def test_rejection_feeds_events_and_flight_recorder(two_models):
    """ISSUE 8: shedding load writes an ``admission.reject`` event, a
    ``rejected`` flight record, and an ``admission_rejection`` forensic
    dump carrying the tenant's static context."""
    from repro.obs.events import EventLog
    from repro.obs.flight import FlightRecorder

    sa, _ = two_models
    x = np.zeros(_request_shape(sa), np.int8)
    events = EventLog()
    flight = FlightRecorder(capacity=16, events=events)
    with MultiServer(flight=flight, events=events) as ms:
        ms.add_model("a", sa, max_queue=0, warmup=False)
        with pytest.raises(AdmissionError):
            ms.submit("a", x)
    assert [e.kind for e in events.records(kind="admission")] \
        == ["admission.reject"]
    rec = flight.records()[-1]
    assert rec.status == "rejected" and rec.tenant == "a"
    dump = flight.dumps()[-1]
    assert dump["reason"] == "admission_rejection"
    assert dump["context"]["a"]["slo_class"] == "best_effort"
    assert dump["context"]["a"]["tiles"] == \
        Session.from_artifact(sa).tile_summary()


def test_stats_use_label_index_and_expose_burn(two_models):
    """ISSUE 8 satellite: per-tenant stats come from
    ``MetricsRegistry.labelled`` (no hand-formatted name lookups) and carry
    live burn rates for SLO-targeted tenants."""
    sa, sb = two_models
    x = np.zeros(_request_shape(sa), np.int8)
    with MultiServer(burn_kw=dict(fast_window_s=1.0, slow_window_s=2.0,
                                  min_samples=4)) as ms:
        ms.add_model("a", sa, slo="gold", warmup=False)
        ms.add_model("b", sb, slo="best_effort", warmup=False)
        before = ms.stats()["requests"]["a"]
        [f.result(timeout=30) for f in [ms.submit("a", x) for _ in range(3)]]
        st = ms.stats()
    # the shared registry accumulates across tests: assert the delta
    assert st["requests"]["a"] >= before + 3.0
    assert set(st["requests"]) == set(st["rejected"]) == {"a", "b"}
    assert st["burn"]["b"] is None                 # no target, no tracker
    assert set(st["burn"]["a"]) == {"fast", "slow", "n_fast", "n_slow"}
    assert st["burn"]["a"]["n_fast"] >= 3
    # the burn gauges are scrapeable with model+class+window labels
    g_fast = REGISTRY.get("slo.burn_rate{class=gold,model=a,window=fast}")
    assert g_fast is not None


def test_gold_slo_violation_alerts_and_dumps(two_models):
    """A gold tenant with an unattainable target must burn its error budget,
    fire the burn-rate alert, and freeze a slo_violation flight dump whose
    records carry the offending requests' queue/execute split."""
    from repro.obs.events import EventLog
    from repro.obs.flight import FlightRecorder

    sa, _ = two_models
    x = np.zeros(_request_shape(sa), np.int8)
    events = EventLog()
    flight = FlightRecorder(capacity=64, events=events)
    with MultiServer(flight=flight, events=events,
                     burn_kw=dict(fast_window_s=30.0, slow_window_s=60.0,
                                  min_samples=4, cooldown_s=0.0)) as ms:
        # 1e-6 ms p99 is unattainable: every request violates
        ms.add_model("a", sa, slo="gold", target_p99_ms=1e-6, warmup=False)
        [f.result(timeout=30) for f in [ms.submit("a", x) for _ in range(8)]]
    alerts = events.records(kind="slo.alert")
    assert alerts and alerts[0].fields["model"] == "a"
    assert alerts[0].fields["fast_burn"] >= 2.0
    dumps = [d for d in flight.dumps() if d["reason"] == "slo_violation"]
    assert dumps
    ok = [r for r in dumps[-1]["records"] if r["status"] == "ok"]
    assert ok and all(r["queue_wait_s"] >= 0 and r["execute_s"] > 0
                      and r["batch_size"] >= 1 for r in ok)
    assert REGISTRY.get("slo.alerts{class=gold,model=a}").value >= 1.0


# -------------------------------------------------- bounded shared plan cache
def test_plan_cache_lru_eviction_across_three_models():
    """A plan cache bounded to 2 entries compiling 3 models must evict LRU
    artifacts and count the evictions into the metrics registry."""
    before = (REGISTRY.get("plan_cache.evictions").value
              if REGISTRY.get("plan_cache.evictions") else 0.0)
    cache = asm.PlanCache(max_entries=2)
    models = [_inputs(seed) for seed in (0, 1, 2)]
    arts = [cache.get_or_compile(g, s, ZU2, qm=qm)[0] for g, qm, s in models]
    assert len(cache) == 2
    assert cache.evictions == 1
    assert REGISTRY.get("plan_cache.evictions").value == before + 1
    # model 0 was evicted (LRU): compiling it again is a miss; model 2 hits
    g, qm, _ = models[2]
    art, hit = cache.get_or_compile(g, arts[2], ZU2, qm=qm)
    assert hit and art is arts[2]
    misses = cache.misses
    g, qm, _ = models[0]
    _, hit = cache.get_or_compile(g, arts[0], ZU2, qm=qm)
    assert not hit and cache.misses == misses + 1
    with pytest.raises(ValueError):
        cache.max_entries = 0
