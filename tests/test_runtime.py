"""Runtime supporter (ISSUE 3 tentpole): plan-cached sessions, the dynamic
batching queue, batch-dim execution bit-exactness, executor input validation,
and the hazard-audited cross-request pipeline schedule."""
import dataclasses
import threading
import time

import numpy as np
import pytest

from repro import asm
from repro.cnn import build, init_params
from repro.core import executor, pathsearch, quantize, simulator
from repro.core.executor import Int8Executor
from repro.hw import ZU2
from repro.runtime import (BatcherClosed, DynamicBatcher, Session,
                           pipeline_report, pipeline_stream)
from tests.conftest import make_toy_resnet_graph, toy_params


@pytest.fixture(scope="module")
def toy_compiled():
    g = make_toy_resnet_graph()
    params = toy_params(g)
    rng = np.random.default_rng(0)
    x = rng.standard_normal(g.shape("data")).astype(np.float32)
    qm = quantize.calibrate(g, params, x, executor.run_float)
    s = pathsearch.search(g, ZU2)
    return g, qm, s


# ------------------------------------------------------------- plan cache
def test_plan_cache_counters_and_lru_eviction(toy_compiled):
    g, qm, s = toy_compiled
    cache = asm.PlanCache(maxsize=2)
    a1, hit = cache.get_or_compile(g, s, ZU2, qm=qm)
    assert not hit and cache.misses == 1 and cache.hits == 0
    _, hit = cache.get_or_compile(g, s, ZU2, qm=qm)
    assert hit and cache.hits == 1
    # two more keys evict the LRU entry (capacity 2)
    naive = pathsearch.naive(g, ZU2)
    cache.get_or_compile(g, naive, ZU2, qm=qm)      # key 2; key 1 refreshed
    _, hit = cache.get_or_compile(g, s, ZU2, qm=qm)  # key 1 still resident
    assert hit
    greedy = pathsearch.greedy(g, ZU2)
    cache.get_or_compile(g, greedy, ZU2, qm=qm)     # key 3 evicts naive (LRU)
    assert len(cache) == 2
    _, hit = cache.get_or_compile(g, naive, ZU2, qm=qm)
    assert not hit and cache.misses == 4             # recompiled after evict


def test_plan_cache_signature_stable_across_save_load(toy_compiled, tmp_path):
    """A saved+loaded artifact must map to the SAME cache key as the
    in-memory compilation it came from (graph, strategy and quantization
    signatures all survive the npz round trip)."""
    g, qm, s = toy_compiled
    cache = asm.PlanCache()
    art, _ = cache.get_or_compile(g, s, ZU2, qm=qm)
    path = str(tmp_path / "sess.npz")
    asm.save_artifact(art, path)
    loaded = asm.load_artifact(path)
    g2 = loaded.rebuild_graph()
    qm2 = loaded.quantized_model()
    assert cache.key(g2, loaded, ZU2, qm2) == cache.key(g, s, ZU2, qm)
    # and therefore a session opened on the loaded artifact hits the cache
    cache.put(g2, loaded, ZU2, loaded, qm=qm2)
    _, hit = cache.get_or_compile(g2, loaded, ZU2, qm=qm2)
    assert hit


def test_session_from_artifact_seeds_cache(toy_compiled, tmp_path):
    g, qm, s = toy_compiled
    cache = asm.PlanCache()
    art, _ = cache.get_or_compile(g, s, ZU2, qm=qm)
    path = str(tmp_path / "art.npz")
    asm.save_artifact(art, path)
    loaded = asm.load_artifact(path)
    misses_before = cache.misses
    sess = Session.from_artifact(loaded, cache=cache)
    assert cache.misses == misses_before      # seeded, not recompiled
    assert sess.cache_hit
    out = sess.run(np.zeros((1,) + tuple(g.shape("data")[1:]), np.int8))
    assert set(out) == set(sess.outputs)


# ------------------------------------------------------- dynamic batching
def test_batcher_orders_and_caps_batches():
    calls = []

    def run_batch(xs):
        calls.append(len(xs))
        return [x * 10 for x in xs]

    with DynamicBatcher(run_batch, max_batch=4, max_latency_s=0.05) as b:
        futs = [b.submit(i) for i in range(10)]
        results = [f.result(timeout=10) for f in futs]
    assert results == [i * 10 for i in range(10)]    # per-request mapping
    assert max(calls) <= 4
    assert sum(calls) == 10
    assert sum(b.batch_sizes.values()) == len(calls)
    assert b.n_served == 10


def test_batcher_max_latency_flushes_partial_batch():
    done = threading.Event()

    def run_batch(xs):
        done.set()
        return list(xs)

    b = DynamicBatcher(run_batch, max_batch=64, max_latency_s=0.05)
    try:
        t0 = time.monotonic()
        fut = b.submit("x")
        assert fut.result(timeout=10) == "x"
        waited = time.monotonic() - t0
        # flushed by the latency knob, far below any full-batch horizon
        assert done.is_set() and waited < 5.0
        assert b.batch_sizes.get(1) == 1
    finally:
        b.close()


def test_batcher_empty_queue_shutdown_and_submit_after_close():
    b = DynamicBatcher(lambda xs: list(xs), max_batch=8, max_latency_s=10.0)
    t0 = time.monotonic()
    b.close()                                  # nothing queued: returns fast
    assert time.monotonic() - t0 < 5.0
    assert not b._worker.is_alive()
    with pytest.raises(BatcherClosed):
        b.submit(1)


def test_batcher_close_drains_pending_requests():
    def slow_batch(xs):
        time.sleep(0.01)
        return list(xs)

    b = DynamicBatcher(slow_batch, max_batch=2, max_latency_s=5.0)
    futs = [b.submit(i) for i in range(5)]
    b.close()                                  # flushes the queue first
    assert [f.result(timeout=1) for f in futs] == list(range(5))


def test_batcher_propagates_executor_failure():
    def boom(xs):
        raise RuntimeError("kernel exploded")

    with DynamicBatcher(boom, max_batch=2, max_latency_s=0.01) as b:
        fut = b.submit(1)
        with pytest.raises(RuntimeError, match="kernel exploded"):
            fut.result(timeout=10)


# ------------------------------------- double-buffered dispatch and collect
class _TwoPhase:
    """A fake two-phase runner: each call dispatches a batch and returns a
    pending batch whose ``results()`` waits on that batch's own gate.
    ``fail`` = (phase, batch index) raises in that phase of that batch."""

    def __init__(self, fail=None, open_gates=False):
        self.lock = threading.Lock()
        self.fail = fail
        self.open_gates = open_gates
        self.sizes = []            # batch sizes, in dispatch order
        self.gates = []            # one Event per dispatched batch
        self.collected = []        # batch indices, as results() returns
        self.uncollected = 0
        self.max_uncollected = 0

    def __call__(self, xs):
        with self.lock:
            i = len(self.sizes)
            self.sizes.append(len(xs))
            gate = threading.Event()
            if self.open_gates:
                gate.set()
            self.gates.append(gate)
            if self.fail == ("dispatch", i):
                raise RuntimeError(f"dispatch of batch {i} failed")
            self.uncollected += 1
            self.max_uncollected = max(self.max_uncollected, self.uncollected)
        return _Pending(self, i, list(xs))

    def release(self, i=None):
        """Open batch ``i``'s gate, or every gate now and later."""
        with self.lock:
            if i is None:
                self.open_gates = True
            for g in (self.gates if i is None else [self.gates[i]]):
                g.set()

    def wait_dispatched(self, n, timeout=10.0):
        t_end = time.monotonic() + timeout
        while len(self.sizes) < n and time.monotonic() < t_end:
            time.sleep(0.001)
        return len(self.sizes)


class _Pending:
    def __init__(self, runner, i, xs):
        self.runner, self.i, self.xs = runner, i, xs

    def results(self):
        r = self.runner
        assert r.gates[self.i].wait(timeout=10)
        with r.lock:
            r.uncollected -= 1
            r.collected.append(self.i)
        if r.fail == ("collect", self.i):
            raise RuntimeError(f"collect of batch {self.i} failed")
        return [x * 10 for x in self.xs]


def _batcher(runner, **kw):
    from repro.obs.metrics import MetricsRegistry
    recs = []
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_latency_s", 0.005)
    b = DynamicBatcher(runner, registry=MetricsRegistry(),
                       observers=[recs.append], **kw)
    return b, recs


def test_batcher_dispatches_next_batch_before_collecting_last():
    runner = _TwoPhase()
    b, recs = _batcher(runner)
    try:
        futs = [b.submit(i) for i in range(4)]
        assert runner.wait_dispatched(2) == 2
        assert runner.collected == []           # batch 2 went out first
        runner.release()
        assert [f.result(timeout=10) for f in futs] == [0, 10, 20, 30]
    finally:
        b.close()
    by_batch = {r["batch_id"]: r["inflight_at_dispatch"] for r in recs}
    assert sorted(by_batch.values()) == [0, 1]
    overlapped = b._registry.counter("serve.batches_overlapped").value
    assert overlapped == 1 and b._m_batches.value == 2


def test_batcher_holds_at_most_two_uncollected_and_resolves_in_order():
    runner = _TwoPhase()
    b, recs = _batcher(runner)
    order = []
    try:
        futs = [b.submit(i) for i in range(10)]
        for i, f in enumerate(futs):
            f.add_done_callback(lambda _, i=i: order.append(i))
        assert runner.wait_dispatched(2) == 2
        time.sleep(0.1)
        assert len(runner.sizes) == 2           # the third waits for a slot
        runner.release(1)                       # the later batch ends first
        time.sleep(0.05)
        assert order == [] and len(runner.sizes) == 2
        runner.release()
        assert [f.result(timeout=10) for f in futs] == [
            i * 10 for i in range(10)]
    finally:
        b.close()
    assert runner.max_uncollected == 2
    assert order == list(range(10))             # futures in submit order
    assert runner.collected == list(range(5))


def test_batcher_holds_partial_batch_while_one_is_in_flight():
    runner = _TwoPhase()
    b, recs = _batcher(runner, max_batch=4, max_latency_s=0.01)
    try:
        full = [b.submit(i) for i in range(4)]
        assert runner.wait_dispatched(1) == 1
        late = b.submit(4)
        time.sleep(0.2)                         # 20 deadlines
        assert runner.sizes == [4]              # held: the device is busy
        runner.release(0)
        assert runner.wait_dispatched(2) == 2   # flushed once batch 1 ended
        assert runner.sizes == [4, 1]
        runner.release()
        assert late.result(timeout=10) == 40
        t0 = time.monotonic()
        alone = b.submit(5)                     # nothing in flight: deadline
        assert alone.result(timeout=10) == 50
        assert time.monotonic() - t0 < 5.0
        assert [f.result(timeout=10) for f in full] == [0, 10, 20, 30]
    finally:
        b.close()
    assert runner.sizes == [4, 1, 1]
    assert {r["req_id"]: r["inflight_at_dispatch"] for r in recs}[5] == 0


@pytest.mark.parametrize("phase", ["dispatch", "collect"])
def test_batcher_failure_fails_only_its_own_batch(phase):
    runner = _TwoPhase(fail=(phase, 1), open_gates=True)
    b, recs = _batcher(runner)
    try:
        futs = [b.submit(i) for i in range(6)]
        for i in (2, 3):
            with pytest.raises(RuntimeError, match=f"{phase} of batch 1"):
                futs[i].result(timeout=10)
        assert [futs[i].result(timeout=10) for i in (0, 1, 4, 5)] == [
            0, 10, 40, 50]
        assert b.submit(6).result(timeout=10) == 60   # serving goes on
    finally:
        b.close()
    status = {r["req_id"]: r["status"] for r in recs}
    assert [status[i] for i in range(1, 8)] == [
        "ok", "ok", "error", "error", "ok", "ok", "ok"]
    assert b.n_served == 5


def test_batcher_close_drains_in_flight_batches():
    runner = _TwoPhase()
    b, _ = _batcher(runner)
    futs = [b.submit(i) for i in range(5)]
    assert runner.wait_dispatched(2) == 2
    threading.Timer(0.1, runner.release).start()
    b.close(timeout_s=30)
    assert not b._worker.is_alive() and not b._collector.is_alive()
    assert all(f.done() for f in futs)
    assert [f.result() for f in futs] == [i * 10 for i in range(5)]
    assert runner.sizes == [2, 2, 1]


# ------------------------------------------- batched execution bit-exactness
def test_session_batched_run_bit_exact_vs_per_request(toy_compiled):
    g, qm, s = toy_compiled
    sess = Session(g, s, ZU2, qm, backend="ref", cache=asm.PlanCache())
    rng = np.random.default_rng(3)
    reqs = [rng.integers(-128, 128, g.shape("data")).astype(np.int8)
            for _ in range(5)]
    batched = sess.run_batch(reqs, pad_to=8)   # exercises zero-padding too
    oracle = Int8Executor(g, qm, strategy=None, backend="ref")
    for x, got in zip(reqs, batched):
        ref = oracle(x)
        for k in sess.outputs:
            assert np.array_equal(ref[k], got[k]), k


def test_pallas_backend_batch_dim(rng):
    """One Pallas launch serves N stacked images bit-exactly (the grid's
    leading axis is the batch)."""
    from repro.core import frontend
    from repro.core.xgraph import XGraph

    g = XGraph("b")
    g.input("data", (1, 8, 8, 4))
    g.add("conv", "c1", ("data",), oc=8, kernel=(3, 3), pad="same", relu="relu")
    g.add("maxpool", "p", ("c1",), kernel=(2, 2), stride=(2, 2))
    frontend.lower(g)
    params = init_params(g)
    x = rng.standard_normal(g.shape("data")).astype(np.float32)
    qm = quantize.calibrate(g, params, x, executor.run_float)
    s = pathsearch.Strategy(groups=[["c1", "p"]], horizontal=[], cost=0.0)
    xb = rng.integers(-128, 128, (3, 8, 8, 4)).astype(np.int8)
    got = Int8Executor(g, qm, strategy=s, backend="pallas")(xb)
    ref = Int8Executor(g, qm, strategy=None, backend="ref")
    for i in range(3):
        one = ref(xb[i:i + 1])
        assert np.array_equal(one["p"], got["p"][i:i + 1])


def test_executor_dispatch_is_call_without_the_fetch(toy_compiled):
    """``dispatch`` leaves the outputs on the device; calling the executor
    on the ``Dispatched`` batch fetches them without a second run; a plain
    call is both; all run the one jitted program (one compile)."""
    import jax

    from repro.obs.metrics import REGISTRY

    g, qm, s = toy_compiled
    ex = Int8Executor(g, qm, strategy=s, backend="ref")
    x = np.random.default_rng(5).integers(
        -128, 128, g.shape("data")).astype(np.int8)
    calls = REGISTRY.counter("executor.calls")
    before = calls.value
    out = ex.dispatch(x)
    assert all(isinstance(v, jax.Array) for v in out.values())
    fetched = ex(executor.Dispatched(x, out))
    assert calls.value == before + 1
    host = ex(x)
    assert calls.value == before + 2
    assert set(host) == set(out) == set(fetched)
    for k in out:
        assert isinstance(host[k], np.ndarray)
        assert np.array_equal(fetched[k], host[k]), k
    assert ex._fn._cache_size() == 1


# ------------------------------------------------------- input validation
def test_executor_input_validation(toy_compiled):
    g, qm, s = toy_compiled
    ex = Int8Executor(g, qm, strategy=s, backend="ref")
    shape = g.shape("data")
    with pytest.raises(ValueError, match="int8"):
        ex(np.zeros(shape, np.float32))
    with pytest.raises(ValueError, match="rank-4"):
        ex(np.zeros(shape[1:], np.int8))
    with pytest.raises(ValueError, match="extents"):
        ex(np.zeros((1, shape[1] + 2, shape[2], shape[3]), np.int8))
    ex(np.zeros((2,) + tuple(shape[1:]), np.int8))   # any batch is fine


# -------------------------------------------------- cross-request schedule
def test_pipeline_stream_is_hazard_free(toy_compiled):
    g, qm, s = toy_compiled
    art, _ = asm.PLAN_CACHE.get_or_compile(g, s, ZU2)
    for slots in (2, 3):
        stream = pipeline_stream(art, 6, ddr_slots=slots)
        assert len(stream) == 6 * len(art.instrs)
        simulator.check(stream)                    # raises on any hazard
    # the un-interleaved (request-major) stream must be clean too
    simulator.check(pipeline_stream(art, 4, interleave=False))


def test_pipeline_report_utilization_and_overlap(toy_compiled):
    g, qm, s = toy_compiled
    art, _ = asm.PLAN_CACHE.get_or_compile(g, s, ZU2)
    rep = pipeline_report(art, 6, ddr_slots=4)
    util = rep.utilization()
    assert set(util) == set(rep.busy_cycles)
    assert all(0.0 <= u <= 1.0 for u in util.values())
    assert 0.0 < rep.utilization(rep.bottleneck) <= 1.0
    # pipelining never loses to strictly sequential back-to-back execution
    assert rep.total_cycles <= rep.sequential_cycles
    assert len(rep.request_windows) == 6
    starts = [s0 for s0, _ in rep.request_windows]
    assert starts == sorted(starts)
    assert rep.n_instructions == 6 * len(art.instrs)
    # per-engine start/end windows cover the whole pipelined stream
    from repro.core.isa import ENGINES
    assert set(rep.engine_timeline) == set(ENGINES)
    assert sum(len(v) for v in rep.engine_timeline.values()) == \
        rep.n_instructions
    for wins in rep.engine_timeline.values():   # one engine: no overlap
        assert all(a[1] <= b[0] for a, b in zip(wins, wins[1:]))


def test_cross_request_bank_audit_mechanism():
    """pipeline_report re-keys the bank audit on base group ids because the
    per-request group renumbering would hide cross-request collisions.
    Hand-built stream: request 1's LOAD streams into bank 0 while request 0's
    compute is still reading it — invisible with renumbered gids, flagged
    once the audit sees the shared physical bank."""
    from repro.core.isa import Instr

    def req(off, gid, tile_off, load_deps):
        return [
            Instr(off + 0, "DDR_RD", "LOAD", 10, load_deps, bank=0,
                  group_id=gid, tile=tile_off),
            Instr(off + 1, "CONV", "CONV", 1000, (off + 0,),
                  group_id=gid, tile=tile_off),
            Instr(off + 2, "DDR_WR", "SAVE", 10, (off + 1,), bank=0,
                  group_id=gid, tile=tile_off),
        ]

    # r1's LOAD has no dep on r0's consumer -> starts at cycle 10 while r0's
    # CONV occupies [10, 1010) on the same in-bank
    broken = req(0, 0, 0, ()) + req(3, 1, 1, ())
    _, times = simulator.run_times(broken)
    renumbered = simulator.bank_hazards(broken, times)
    assert renumbered == []                      # per-request gids: blind
    shared = [dataclasses.replace(i, group_id=0) for i in broken]
    flagged = simulator.bank_hazards(shared, times)
    assert flagged and "in-bank hazard" in flagged[0]
    # with the ping/pong continuation dep the schedule threads, it is clean
    fixed = req(0, 0, 0, ()) + req(3, 1, 1, (1,))
    _, times = simulator.run_times(fixed)
    shared = [dataclasses.replace(i, group_id=0) for i in fixed]
    assert simulator.bank_hazards(shared, times) == []


def test_pipeline_without_cross_deps_is_caught_by_oracle():
    """Deliberate-hazard case: strip the cross-request dependency bits
    before the dispatcher merge and the memory-hazard oracle must flag the
    resulting DDR collisions — i.e. the bits pipeline_stream threads are
    load-bearing, not decorative."""
    import dataclasses

    from repro.core import partition
    from repro.runtime.schedule import _interleave

    g = build("vgg16", img=32, num_classes=10)
    dv = partition.device_of(g, "paper")
    s = pathsearch.search(g, ZU2, device_of=dv)
    art, _ = asm.PLAN_CACHE.get_or_compile(g, s, ZU2)
    n_base = len(art.instrs)
    raw = pipeline_stream(art, 6, ddr_slots=2, interleave=False)
    stripped = [dataclasses.replace(x, deps=tuple(d for d in x.deps
                                                  if abs(x.iid - d) < n_base))
                for x in raw]
    with pytest.raises(simulator.MemoryHazardError):
        simulator.check(_interleave(stripped, n_base))


def test_pipeline_overlaps_requests_on_vgg():
    """LOAD of request i+1 must overlap compute of request i: the modeled
    pipelined makespan beats sequential, and adjacent request windows
    intersect."""
    g = build("vgg16", img=32, num_classes=10)
    from repro.core import partition
    dv = partition.device_of(g, "paper")
    s = pathsearch.search(g, ZU2, device_of=dv)
    art, _ = asm.PLAN_CACHE.get_or_compile(g, s, ZU2)
    rep = pipeline_report(art, 6, ddr_slots=4)
    assert rep.modeled_speedup > 1.05, rep.modeled_speedup
    w = rep.request_windows
    assert all(w[i + 1][0] < w[i][1] for i in range(len(w) - 1)), w


def test_session_pipeline_report_and_stats(toy_compiled):
    g, qm, s = toy_compiled
    sess = Session(g, s, ZU2, qm, backend="ref", cache=asm.PlanCache())
    rep = sess.pipeline_report(3)
    assert rep.n_requests == 3
    sess.run(np.zeros((1,) + tuple(g.shape("data")[1:]), np.int8))
    st = sess.stats()
    assert st["images_served"] == 1 and st["n_runs"] == 1
    assert 0.0 <= st["fused_coverage"] <= 1.0


# ------------------------------------------------------------- end to end
def test_server_serves_bit_exact_with_batching(toy_compiled):
    g, qm, s = toy_compiled
    sess = Session(g, s, ZU2, qm, backend="ref", cache=asm.PlanCache())
    rng = np.random.default_rng(7)
    reqs = [rng.integers(-128, 128, g.shape("data")).astype(np.int8)
            for _ in range(6)]
    with sess.serve(max_batch=4, max_latency_s=0.02) as server:
        outs = [f.result(timeout=300)
                for f in [server.submit(x) for x in reqs]]
        stats = server.stats()
    assert stats["n_served"] == 6
    assert sum(k * v for k, v in stats["batch_histogram"].items()) == 6
    oracle = Int8Executor(g, qm, strategy=None, backend="ref")
    for x, got in zip(reqs, outs):
        ref = oracle(x)
        for k in sess.outputs:
            assert np.array_equal(ref[k], got[k]), k


def _submit_concurrently(server, reqs, n_threads=4):
    """Submit ``reqs`` split over ``n_threads`` threads; futures in order."""
    futs = [None] * len(reqs)

    def client(k):
        for i in range(k, len(reqs), n_threads):
            futs[i] = server.submit(reqs[i])

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
        assert not t.is_alive()
    return futs


def test_server_bit_exact_under_concurrent_submitters(toy_compiled):
    g, qm, s = toy_compiled
    sess = Session(g, s, ZU2, qm, backend="ref", cache=asm.PlanCache())
    rng = np.random.default_rng(11)
    reqs = [rng.integers(-128, 128, g.shape("data")).astype(np.int8)
            for _ in range(24)]
    with sess.serve(max_batch=4, allowed_sizes=[4],
                    max_latency_s=0.002) as server:
        outs = [f.result(timeout=300)
                for f in _submit_concurrently(server, reqs)]
        stats = server.stats()
    assert stats["n_served"] == 24
    oracle = Int8Executor(g, qm, strategy=None, backend="ref")
    for x, got in zip(reqs, outs):
        ref = oracle(x)
        for k in sess.outputs:
            assert np.array_equal(ref[k], got[k]), k


# ------------------------------------------------- pin_input planner mode
def test_pin_input_removes_input_recycling_and_guards():
    """With pin_input the network input's DDR region leaves the reuse pool:
    nothing recycles it, the cross-request pre-load guard count drops to
    zero, and the modeled overlap never regresses."""
    g = build("vgg16", img=32, num_classes=10)
    s = pathsearch.search(g, ZU2)
    cache = asm.PlanCache()
    art, _ = cache.get_or_compile(g, s, ZU2)
    artp, hit = cache.get_or_compile(g, s, ZU2, pin_input=True)
    assert not hit                       # pin_input is part of the cache key
    assert artp.pin_input and not art.pin_input

    rep = pipeline_report(art, 4, ddr_slots=2)
    repp = pipeline_report(artp, 4, ddr_slots=2)
    assert rep.n_preload_guards > 0      # fc output recycles the input region
    assert repp.n_preload_guards == 0
    assert repp.pin_input and not rep.pin_input
    assert repp.overlap >= rep.overlap - 1e-9


def test_pin_input_round_trips_through_artifact(toy_compiled, tmp_path):
    g, qm, s = toy_compiled
    cache = asm.PlanCache()
    art, _ = cache.get_or_compile(g, s, ZU2, qm=qm, pin_input=True)
    path = str(tmp_path / "pinned.npz")
    asm.save_artifact(art, path)
    loaded = asm.load_artifact(path)
    assert loaded.pin_input
    # a session opened on the loaded artifact re-keys identically (pin_input
    # inherited from mem_summary) and hits the seeded cache
    sess = Session.from_artifact(loaded, cache=asm.PlanCache())
    assert sess.cache_hit and sess.stats()["pin_input"]


# ------------------------------------------------ latency-SLO batch sizing
def _slo_server(tmp_session, **kw):
    return tmp_session.serve(max_batch=8, max_latency_s=1e-3, warmup=False,
                             **kw)


def test_server_slo_shrinks_effective_batch(toy_compiled):
    g, qm, s = toy_compiled
    sess = Session(g, s, ZU2, qm, backend="ref", cache=asm.PlanCache())
    x = np.zeros(tuple(g.shape("data")), np.int8)
    # an unreachable SLO (0 ms) must walk the cap down the allowed ladder
    with _slo_server(sess, target_p99_ms=0.0) as server:
        for _ in range(4):               # several flushes -> several adjusts
            futs = [server.submit(x) for _ in range(8)]
            [f.result(timeout=60) for f in futs]
        stats = server.stats()
    assert stats["effective_max_batch"] == 1
    assert stats["slo_shrinks"] >= 3
    assert stats["target_p99_ms"] == 0.0


def test_server_slo_regrows_when_latency_clears(toy_compiled):
    g, qm, s = toy_compiled
    sess = Session(g, s, ZU2, qm, backend="ref", cache=asm.PlanCache())
    x = np.zeros(tuple(g.shape("data")), np.int8)
    with _slo_server(sess, target_p99_ms=1e9) as server:
        server._batcher.set_max_batch(1)     # pretend a past SLO violation
        for _ in range(3):
            futs = [server.submit(x) for _ in range(8)]
            [f.result(timeout=60) for f in futs]
        stats = server.stats()
    assert stats["effective_max_batch"] == 8  # fully recovered to max_batch
    assert stats["slo_grows"] >= 1
    assert stats["slo_shrinks"] == 0


def test_server_without_slo_keeps_static_cap(toy_compiled):
    g, qm, s = toy_compiled
    sess = Session(g, s, ZU2, qm, backend="ref", cache=asm.PlanCache())
    x = np.zeros(tuple(g.shape("data")), np.int8)
    with _slo_server(sess) as server:
        futs = [server.submit(x) for _ in range(8)]
        [f.result(timeout=60) for f in futs]
        stats = server.stats()
    assert stats["effective_max_batch"] == 8
    assert stats["slo_shrinks"] == 0 and stats["slo_grows"] == 0


def test_server_slo_resizes_under_collector_appends(toy_compiled):
    """The SLO controller reads the latency windows on the worker while the
    collector appends to them, and ``stats()`` reads them from a third
    thread: no read may fail."""
    import sys

    g, qm, s = toy_compiled
    sess = Session(g, s, ZU2, qm, backend="ref", cache=asm.PlanCache())
    x = np.zeros(tuple(g.shape("data")), np.int8)
    errors, stop = [], threading.Event()
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with _slo_server(sess, target_p99_ms=0.0) as server:
            def poll():
                while not stop.is_set():
                    try:
                        server.stats()
                    except Exception as e:  # noqa: BLE001 -- the finding
                        errors.append(e)

            poller = threading.Thread(target=poll)
            poller.start()
            outs = [f.result(timeout=300)
                    for f in _submit_concurrently(server, [x] * 64)]
            stop.set()
            poller.join(timeout=60)
            stats = server.stats()
    finally:
        stop.set()
        sys.setswitchinterval(old)
    assert not poller.is_alive() and errors == []
    assert len(outs) == 64 and stats["n_served"] == 64
    assert stats["slo_shrinks"] >= 1


def test_batcher_set_max_batch_validates():
    b = DynamicBatcher(lambda xs: list(xs), max_batch=4)
    try:
        with pytest.raises(ValueError):
            b.set_max_batch(0)
        b.set_max_batch(2)
        assert b.max_batch == 2
    finally:
        b.close()
