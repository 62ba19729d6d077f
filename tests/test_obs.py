"""Observability layer (ISSUE 6 tentpole): span tracer semantics, Chrome
trace export, metrics registry, drift detection, and the serve-path split
timings the SLO controller consumes."""
import dataclasses
import json
import threading

import numpy as np
import pytest

from repro.obs import (REGISTRY, Counter, DriftProfiler, Gauge, Histogram,
                       MetricsRegistry, Tracer)
from repro.obs.metrics import DEFAULT_BATCH_BUCKETS


# ------------------------------------------------------------------- tracer
def test_span_nesting_and_track_inheritance():
    tr = Tracer(enabled=True)
    with tr.span("outer", cat="compile", track="compile"):
        with tr.span("inner"):
            pass
    recs = tr.records()
    assert [r.name for r in recs] == ["inner", "outer"]   # close order
    inner, outer = recs
    assert outer.depth == 0 and inner.depth == 1
    assert inner.track == "compile"                       # inherited
    assert outer.start <= inner.start <= inner.end <= outer.end


def test_span_records_timing_and_args():
    fake = iter([1.0, 2.5]).__next__
    tr = Tracer(enabled=True, clock=fake)
    with tr.span("work", cat="c", n=3) as sp:
        sp.set(extra="yes")
    (rec,) = tr.records()
    assert rec.start == 1.0 and rec.end == 2.5
    assert rec.duration == pytest.approx(1.5)
    assert rec.args == {"n": 3, "extra": "yes"}


def test_disabled_tracer_records_nothing():
    tr = Tracer(enabled=False)
    with tr.span("x"):
        pass
    tr.add_span("y", 0.0, 1.0)
    tr.instant("z")
    assert tr.add_engine_windows({"CONV": [(0, 10, "CONV", "t")]}, 1e6) == 0
    assert len(tr) == 0 and tr.n_recorded == 0
    # same shared no-op object every time: the hot path allocates nothing
    assert tr.span("a") is tr.span("b")


def test_ring_buffer_bounds_retention():
    tr = Tracer(capacity=16, enabled=True)
    for i in range(100):
        tr.add_span(f"s{i}", float(i), float(i) + 0.5)
    assert len(tr) == 16
    assert tr.n_recorded == 100
    assert tr.n_dropped == 84
    names = [r.name for r in tr.records()]
    assert names == [f"s{i}" for i in range(84, 100)]     # newest survive


def test_tracer_thread_safety():
    tr = Tracer(capacity=100_000, enabled=True)
    n_threads, n_spans = 8, 200

    def work(tid):
        for i in range(n_spans):
            with tr.span(f"t{tid}-{i}"):
                pass

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = tr.records()
    assert len(recs) == n_threads * n_spans
    # per-thread tracks stay distinct and every span landed at depth 0
    assert all(r.depth == 0 for r in recs)
    assert len({r.track for r in recs}) == n_threads


def test_chrome_trace_schema():
    tr = Tracer(enabled=True)
    with tr.span("compile_stage", cat="compile", track="compile"):
        pass
    tr.add_span("queue_wait", 1.0, 2.0, cat="serve", track="req1")
    tr.add_engine_windows({"CONV": [(0, 100, "CONV", "c1@t0")]},
                          freq_hz=1e6, origin=0.0)
    doc = json.loads(json.dumps(tr.to_chrome()))          # JSON-serialisable
    evs = doc["traceEvents"]
    xs = [e for e in evs if e["ph"] == "X"]
    ms = [e for e in evs if e["ph"] == "M"]
    assert len(xs) == 3 and ms
    for e in xs:
        assert e["ts"] >= 0 and e["dur"] >= 0             # µs, non-negative
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    # every (pid, tid) used by an X event has a thread_name metadata row
    named = {(e["pid"], e["tid"]) for e in ms if e["name"] == "thread_name"}
    assert {(e["pid"], e["tid"]) for e in xs} <= named
    procs = {e["args"]["name"] for e in ms if e["name"] == "process_name"}
    assert {"measured", "modeled"} <= procs


def test_engine_windows_become_modeled_tracks():
    tr = Tracer(enabled=True)
    win = {"CONV": [(0, 100, "CONV", "c1@t0"), (150, 300, "CONV", "c2@t0")],
           "LOAD": [(0, 80, "LOAD", "c2@t0")]}
    n = tr.add_engine_windows(win, freq_hz=1e6, origin=10.0)
    assert n == 3
    recs = tr.records()
    assert {r.process for r in recs} == {"modeled"}
    assert {r.track for r in recs} == {"CONV", "LOAD"}
    conv = [r for r in recs if r.track == "CONV"][0]
    assert conv.start == pytest.approx(10.0)
    assert conv.duration == pytest.approx(100 / 1e6)
    assert conv.args["cycles"] == 100


# ------------------------------------------------------------------ metrics
def test_counter_and_gauge():
    reg = MetricsRegistry()
    c = reg.counter("c")
    c.inc()
    c.inc(2)
    assert c.value == 3
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(5)
    g.dec(2)
    assert g.value == 3
    assert reg.counter("c") is c                          # get-or-create


def test_histogram_buckets_and_percentiles():
    h = Histogram("h", bounds=(1.0, 10.0, 100.0))
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 4
    assert snap["buckets"] == {"1.0": 1, "10.0": 1, "100.0": 1, "+inf": 1}
    assert snap["min"] == 0.5 and snap["max"] == 500.0
    assert h.percentile(1.0) == 500.0                     # overflow -> max
    assert 0.0 < h.percentile(0.25) <= 1.0
    with pytest.raises(ValueError):
        Histogram("bad", bounds=(2.0, 1.0))


def test_registry_type_conflict_and_bound():
    reg = MetricsRegistry(max_metrics=2)
    reg.counter("a")
    with pytest.raises(TypeError):
        reg.gauge("a")
    reg.gauge("b")
    with pytest.raises(RuntimeError):
        reg.counter("c")


def test_snapshot_stable_and_json_serialisable():
    reg = MetricsRegistry()
    reg.counter("z.count").inc(7)
    reg.gauge("a.depth").set(2)
    h = reg.histogram("m.lat")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    s1, s2 = reg.snapshot(), reg.snapshot()
    assert s1 == s2                                       # stable
    assert list(s1) == sorted(s1)                         # deterministic order
    json.dumps(s1)
    assert s1["z.count"] == {"type": "counter", "value": 7.0}
    assert s1["m.lat"]["count"] == 3
    assert "p50" in s1["m.lat"] and "p99" in s1["m.lat"]


def test_metrics_thread_safe_increments():
    reg = MetricsRegistry()
    c = reg.counter("n")
    h = reg.histogram("h", bounds=DEFAULT_BATCH_BUCKETS)

    def work():
        for _ in range(1000):
            c.inc()
            h.observe(1.0)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    assert h.count == 8000


# ----------------------------------------------------------- serve plumbing
def test_batcher_splits_queue_wait_from_execute():
    from repro.runtime.batching import DynamicBatcher

    with DynamicBatcher(lambda xs: [x + 1 for x in xs], max_batch=4,
                        max_latency_s=1e-3,
                        registry=MetricsRegistry()) as b:
        futs = [b.submit(i) for i in range(8)]
        assert [f.result() for f in futs] == [i + 1 for i in range(8)]
    assert len(b.latencies) == 8
    assert len(b.queue_waits) == 8                        # per request
    assert 1 <= len(b.execute_s) <= 8                     # per batch
    # wait + execute bound the end-to-end latency from below
    assert max(b.queue_waits) <= max(b.latencies) + 1e-9
    assert all(e >= 0 for e in b.execute_s)


def test_batcher_emits_serve_spans():
    from repro.runtime.batching import DynamicBatcher

    tr = Tracer(enabled=True)
    with DynamicBatcher(lambda xs: list(xs), max_batch=4, max_latency_s=1e-3,
                        registry=MetricsRegistry(), tracer=tr) as b:
        [f.result() for f in [b.submit(i) for i in range(4)]]
    names = {r.name for r in tr.records()}
    assert {"queue_wait", "execute", "batch_form", "batch_execute",
            "resolve"} <= names
    tracks = {r.track for r in tr.records()}
    assert "batch" in tracks
    assert any(t.startswith("req") for t in tracks)


def test_server_stats_carry_split_percentiles(toy_session):
    srv = toy_session.serve(max_batch=4, max_latency_s=1e-3, warmup=False)
    rng = np.random.default_rng(0)
    x = rng.integers(-128, 128, toy_session.graph.shape("data")[1:],
                     endpoint=False).astype(np.int8)
    [f.result() for f in [srv.submit(x) for _ in range(6)]]
    srv.close()
    st = srv.stats()
    assert st["queue_wait_p99_ms"] is not None
    assert st["execute_p99_ms"] is not None
    assert st["slo_shrinks_queue_bound"] == 0             # no SLO configured
    assert st["slo_shrinks_launch_bound"] == 0


# -------------------------------------------------------------------- drift
@pytest.fixture(scope="module")
def toy_tuned():
    """A profile-guided toy compile: (artifact, the profile it was planned
    under)."""
    from tests.conftest import make_toy_resnet_graph, toy_params
    from repro import asm
    from repro.core import executor, pathsearch, quantize
    from repro.core.cost import SimulatorEvaluator
    from repro.hw import ZU2
    from repro.tune import CalibratedEvaluator, calibrate

    g = make_toy_resnet_graph()
    params = toy_params(g)
    rng = np.random.default_rng(0)
    x = rng.standard_normal(g.shape("data")).astype(np.float32)
    qm = quantize.calibrate(g, params, x, executor.run_float)
    sim = SimulatorEvaluator(g, ZU2)
    res = calibrate(g, qm, ZU2, measure_fn=lambda grp: sim(grp),
                    features="analytic")
    p = res.profile
    s = pathsearch.search(g, ZU2, evaluator=CalibratedEvaluator(g, ZU2, p))
    return asm.compile_strategy(g, s, ZU2, qm=qm, profile=p), p


@pytest.fixture(scope="module")
def toy_session(toy_tuned):
    from repro.runtime import Session
    art, _ = toy_tuned
    return Session.from_artifact(art, backend="pallas")


def _prediction_fn(session, p):
    """measure_fn that returns exactly the profile's own predictions — an
    undrifted world, deterministic."""
    from repro.tune.evaluator import predict_item_seconds

    return lambda item: predict_item_seconds(p, session.graph,
                                             session.device, item)


def _drift(session, tuned, **kw):
    art, p = tuned
    return DriftProfiler.from_session(session, art, p,
                                      registry=MetricsRegistry(), **kw)


def test_drift_unperturbed_within_band(toy_session, toy_tuned):
    dp = _drift(toy_session, toy_tuned, every=1,
                measure_fn=_prediction_fn(toy_session, toy_tuned[1]))
    dp.sample()
    rep = dp.report()
    assert rep.units and not rep.skipped
    assert rep.aggregate == pytest.approx(0.0, abs=1e-12)
    assert rep.aggregate <= rep.calibration_band[1]       # inside 5-10% band
    assert rep.profile_match
    assert not rep.drifted
    json.dumps(rep.to_json())


def test_drift_perturbed_profile_flagged(toy_session, toy_tuned):
    art, p = toy_tuned
    p2 = dataclasses.replace(p, coef=tuple(2 * c for c in p.coef))
    dp = DriftProfiler(toy_session.graph, toy_session.qm, art,
                       toy_session.device, p2, every=1,
                       measure_fn=_prediction_fn(toy_session, p),
                       registry=MetricsRegistry())
    dp.sample()
    rep = dp.report()
    # predictions doubled, measurements unchanged -> 50% deviation
    assert rep.aggregate == pytest.approx(0.5, abs=1e-9)
    assert rep.aggregate > rep.band
    assert not rep.profile_match                          # hash moved too
    assert rep.drifted


def test_drift_sampling_cadence(toy_session, toy_tuned):
    calls = []

    def fake_measure(item):
        calls.append(item)
        return 1e-3

    dp = _drift(toy_session, toy_tuned, every=4, measure_fn=fake_measure)
    n_units = len(dp._resolve_units())
    fired = [dp.observe_launch() for _ in range(8)]
    assert fired == [False, False, False, True] * 2       # every 4th
    assert len(calls) == 2 * n_units
    assert dp.n_observed == 8 and dp.n_sampled == 2


def test_drift_gauges_are_labelled_per_model(toy_session, toy_tuned):
    """Drift reports land as per-model gauges
    (``drift.median_deviation{model=}``, ``drift.tripped{model=}``) and the
    first False->True transition emits one ``drift.trip`` event."""
    reg = MetricsRegistry()
    art, p = toy_tuned
    p2 = dataclasses.replace(p, coef=tuple(2 * c for c in p.coef))
    dp = DriftProfiler(toy_session.graph, toy_session.qm, art,
                       toy_session.device, p2, every=1,
                       measure_fn=_prediction_fn(toy_session, p),
                       registry=reg, labels={"model": "toy"})
    # the trip event goes through the shared log; watch it via a subscriber
    from repro.obs.events import EVENTS
    trips = []
    watch = lambda e: trips.append(e) if e.kind == "drift.trip" else None
    EVENTS.subscribe(watch)
    try:
        dp.sample()
        dp.sample()                       # still drifted: no second event
    finally:
        EVENTS.unsubscribe(watch)
    assert reg.get("drift.median_deviation{model=toy}").value \
        == pytest.approx(0.5, abs=1e-9)
    assert reg.get("drift.tripped{model=toy}").value == 1.0
    assert reg.get("drift.samples{model=toy}").value == 2.0
    assert len(trips) == 1
    assert trips[0].fields["model"] == "toy"
    # the cached summary the flight recorder stamps onto records
    assert dp.last["drifted"] and dp.last["aggregate"] \
        == pytest.approx(0.5, abs=1e-9)
    assert toy_session.drift_state() is None   # nothing attached


def test_session_tile_summary_names_every_lowered_unit(toy_session):
    tiles = toy_session.tile_summary()
    assert tiles and len(tiles) == len(toy_session.executor.program.items)
    for t in tiles:
        assert set(t) == {"nodes", "kind", "tile"}
        assert t["kind"] in ("chain", "horizontal", "fallback")


def test_drift_attaches_to_session_serving(toy_session, toy_tuned):
    dp = _drift(toy_session, toy_tuned, every=2,
                measure_fn=_prediction_fn(toy_session, toy_tuned[1]))
    toy_session.attach_drift(dp)
    try:
        rng = np.random.default_rng(1)
        x = rng.integers(-128, 128, toy_session.graph.shape("data")[1:],
                         endpoint=False).astype(np.int8)
        for _ in range(4):
            toy_session.run(x)
    finally:
        toy_session.attach_drift(None)
    assert dp.n_observed == 4 and dp.n_sampled == 2
    assert not dp.report().drifted


def test_from_artifact_keeps_resolved_profile(toy_tuned, tmp_path):
    """A loaded artifact keeps the hash of the profile it was planned under;
    that profile drives the artifact's ddr-slot auto-selection, and a drift
    profiler judges a session on the loaded artifact against it, with no
    recompile."""
    from repro import asm
    from repro.hw import ZU2
    from repro.runtime import Session
    from repro.runtime.schedule import pipeline_report

    art, p = toy_tuned
    path = str(tmp_path / "tuned.npz")
    asm.save_artifact(art, path)
    loaded = asm.load_artifact(path)
    assert loaded.profile_hash == p.hash()

    cache = asm.PlanCache()
    g2, qm2 = loaded.rebuild_graph(), loaded.quantized_model()
    cache.put(g2, loaded, ZU2, loaded, qm=qm2, profile=p)
    _, hit = cache.get_or_compile(g2, loaded, ZU2, qm=qm2, profile=p)
    assert hit and cache.misses == 0                      # no recompile
    # the profile drives ddr-slot auto-selection
    assert pipeline_report(loaded, 2, ddr_slots=None,
                           profile=p).ddr_slots_source == "profile"
    # and a DriftProfiler can be built for a session on the loaded artifact
    sess = Session.from_artifact(loaded, backend="pallas")
    dp = DriftProfiler.from_session(sess, loaded, p,
                                    measure_fn=lambda item: 1e-3,
                                    registry=MetricsRegistry())
    dp.sample()
    assert dp.report().profile_match


def test_global_tracer_disabled_by_default():
    from repro.obs import TRACER
    assert not TRACER.enabled
    assert isinstance(REGISTRY.snapshot(), dict)
