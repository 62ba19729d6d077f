"""Fleet benchmark + chaos gate: replicated serving under injected faults.

Three phases, all against ONE shared ``CompiledArtifact`` (every replica
dispatches its stored program — the fleet compiles nothing):

* **scaling** — the same burst served by a 1-replica and a 2-replica fleet,
  with a uniform per-launch device cost injected through the chaos hook
  (``ChaosInjector.slow`` on every replica).  The injected cost models the
  accelerator's occupancy — host CPU time is shared between forced-host
  replicas, so without it a 2-replica "speedup" would only measure BLAS
  thread contention, not fleet routing.  Gate: 2 replicas >= 1.7x one.
* **chaos kill** — a paced run during which one replica is killed outright
  mid-stream.  Gate: every submitted request completes bit-exact against
  the unfused int8 oracle (ZERO drops), the dead replica is evicted with
  ``replica.evict`` + a frozen flight dump, retries are observable, and
  after healing the replica is elastically re-admitted (``replica.admit``).
* **load shedding** — a burst into one deliberately slowed replica with a
  tiny queue bound.  Gate: some of the burst is shed via ``AdmissionError``
  (degraded, not wedged), and everything accepted completes bit-exact.

--smoke asserts the gates and is wired into ``make ci`` (`fleet-smoke`).
The JSON (+ flight dumps) land in benchmarks/out/ as CI build artifacts.
"""
from __future__ import annotations

import argparse
import json
import os
import time


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="googlenet",
                    choices=["vgg16", "resnet50", "googlenet"])
    ap.add_argument("--img", type=int, default=32)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-latency-ms", type=float, default=2.0)
    ap.add_argument("--launch-cost-ms", type=float, default=500.0,
                    help="uniform per-launch device cost injected during the "
                         "scaling phase; must dominate the host compute per "
                         "launch so the gate measures routing parallelism "
                         "(sleeps release the GIL and overlap across "
                         "replicas like real accelerators would, while the "
                         "host compute serializes — on a 1-core CI box the "
                         "ceiling is (2s+2c)/(s+2c) for sleep s, compute c)")
    ap.add_argument("--kill-after-launches", type=int, default=2,
                    help="healthy launches the victim replica serves before "
                         "the kill fault arms")
    ap.add_argument("--repeats", type=int, default=1,
                    help="scaling trials per fleet width; best-of wins")
    ap.add_argument("--json", dest="json_path", default=None,
                    help="bare names land in benchmarks/out/ (gitignored)")
    ap.add_argument("--smoke", action="store_true",
                    help="assert the chaos/scaling/shedding gates")
    args = ap.parse_args(argv)

    # forced-host devices BEFORE jax loads: each replica gets its own device
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{max(2, args.replicas)}").strip()

    import outdir
    args.json_path = outdir.resolve(args.json_path)

    from serve_bench import audit_bit_exact, build_session, make_requests
    from repro.obs import REGISTRY
    from repro.obs.flight import FlightRecorder
    from repro.obs.metrics import MetricsRegistry
    from repro.runtime import AdmissionError, ChaosInjector, Fleet

    sess, art, _, compile_times = build_session(args.model, args.img, "ref",
                                                True)
    reqs = make_requests(sess, args.requests)
    import jax
    print(f"{args.model}@{args.img} requests={args.requests} "
          f"devices={[str(d) for d in jax.devices()]} "
          f"(search {compile_times['search_s']:.2f}s, "
          f"compile {compile_times['compile_s']:.2f}s)")

    server_kw = {"max_batch": args.max_batch,
                 "max_latency_s": args.max_latency_ms * 1e-3}
    # generous windows: queue waits behind slow/chaos launches must look like
    # load, not like a stuck replica — the kill gate detects via the error
    # path, not via attempt timeouts
    fleet_kw = {"attempt_timeout_s": 30.0, "request_deadline_s": 240.0}

    # ------------------------------------------------------------- scaling
    def run_width(n: int) -> dict:
        best = None
        for _ in range(max(1, args.repeats)):
            fleet = Fleet(art, n_replicas=n, server_kw=dict(server_kw),
                          registry=MetricsRegistry(), **fleet_kw)
            chaos = ChaosInjector().attach(fleet)
            for rid in fleet.replicas():
                chaos.slow(rid, args.launch_cost_ms * 1e-3)
            try:
                t0 = time.perf_counter()
                futs = [fleet.submit(x) for x in reqs]
                outs = [f.result(timeout=300) for f in futs]
                wall = time.perf_counter() - t0
                st = fleet.stats()
            finally:
                chaos.heal_all()
                fleet.close()
            got = {"replicas": n, "wall_s": wall,
                   "images_per_s": len(reqs) / wall,
                   "served_per_replica": {r: v["n_served"]
                                          for r, v in st["replicas"].items()},
                   "outputs": outs}
            if best is None or got["images_per_s"] > best["images_per_s"]:
                best = got
        return best

    one = run_width(1)
    two = run_width(args.replicas)
    scaling = two["images_per_s"] / one["images_per_s"]
    print(f"scaling    : 1 replica {one['images_per_s']:8.2f} img/s; "
          f"{args.replicas} replicas {two['images_per_s']:8.2f} img/s "
          f"({scaling:.2f}x; injected launch cost "
          f"{args.launch_cost_ms:.0f}ms; served "
          f"{two['served_per_replica']})")

    # ---------------------------------------------------------- chaos kill
    dump_dir = os.path.join(os.path.dirname(args.json_path) or ".",
                            "fleet_flight")
    reg_chaos = MetricsRegistry()        # per-phase counters, not cumulative
    flight = FlightRecorder(dump_dir=dump_dir, registry=reg_chaos)
    fleet = Fleet(art, n_replicas=args.replicas, server_kw=dict(server_kw),
                  flight=flight, registry=reg_chaos, **fleet_kw)
    chaos = ChaosInjector().attach(fleet)
    victim = f"r{args.replicas - 1}"
    chaos.kill(victim, after_launches=args.kill_after_launches)
    try:
        t0 = time.perf_counter()
        futs = []
        for x in reqs:                   # paced: the kill lands mid-stream
            futs.append(fleet.submit(x))
            time.sleep(0.002)
        chaos_outs = [f.result(timeout=300) for f in futs]
        chaos_wall = time.perf_counter() - t0
        st = fleet.stats()
        evict_events = [e.to_json() for e in
                        fleet._events.records(kind="replica.evict")]
        retry_events = fleet._events.records(kind="request.retry")
        n_dumps = len(fleet.flight.dumps())
        # heal -> the victim must pass the warmup probe and rejoin
        chaos.heal(victim)
        readmitted = fleet.wait_active(victim, timeout_s=30.0)
        admit_events = [e.to_json() for e in
                        fleet._events.records(kind="replica.admit")
                        if not e.fields.get("initial")]
        st_after = fleet.stats()
    finally:
        chaos.heal_all()
        fleet.close()
    chaos_phase = {
        "victim": victim,
        "kills_fired": chaos.fired("kill"),
        "submitted": st["submitted"], "completed": st["completed"],
        "dropped": st["submitted"] - st["completed"],
        "retries": st["retries"],
        "evictions": st["replicas"][victim]["evictions"],
        "flight_dumps": n_dumps,
        "readmitted": readmitted,
        "admissions": st_after["replicas"][victim]["admissions"],
        "wall_s": chaos_wall,
        "images_per_s": len(reqs) / chaos_wall,
        "evict_events": evict_events,
        "admit_events": admit_events,
        "n_retry_events": len(retry_events),
    }
    print(f"chaos kill : {victim} killed after "
          f"{args.kill_after_launches} launches -> "
          f"{chaos_phase['completed']}/{chaos_phase['submitted']} completed "
          f"(dropped {chaos_phase['dropped']}), "
          f"retries={chaos_phase['retries']:.0f}, "
          f"evictions={chaos_phase['evictions']}, "
          f"flight dumps={n_dumps}, re-admitted={readmitted}")

    # -------------------------------------------------------- load shedding
    fleet = Fleet(art, n_replicas=1, server_kw=dict(server_kw),
                  max_queue_per_replica=4, registry=MetricsRegistry(),
                  **fleet_kw)
    chaos = ChaosInjector().attach(fleet)
    chaos.slow("r0", 0.05)
    shed, accepted, accepted_ix = 0, [], []
    try:
        for i, x in enumerate(reqs):
            try:
                accepted.append(fleet.submit(x))
                accepted_ix.append(i)
            except AdmissionError:
                shed += 1
        shed_outs = [f.result(timeout=300) for f in accepted]
        st = fleet.stats()
    finally:
        chaos.heal_all()
        fleet.close()
    shedding = {"offered": len(reqs), "accepted": len(accepted),
                "shed": shed, "rejected_metric": st["rejected"]}
    print(f"shedding   : {shed}/{len(reqs)} shed at queue bound 4, "
          f"{len(accepted)} accepted all completed")

    # ---------------------------------------------------------- bit-exact
    exact_one, exact_two, exact_chaos = audit_bit_exact(
        sess, reqs, one["outputs"], two["outputs"], chaos_outs)
    shed_reqs = [reqs[i] for i in accepted_ix]   # capacity frees mid-burst,
    [exact_shed] = audit_bit_exact(sess, shed_reqs, shed_outs) \
        if shed_outs else [True]                 # so accepted != a prefix
    print(f"bit-exact vs oracle: 1-replica={exact_one} "
          f"{args.replicas}-replica={exact_two} chaos={exact_chaos} "
          f"shed-survivors={exact_shed}")

    out = {
        "model": args.model, "img": args.img, "requests": args.requests,
        "replicas": args.replicas, "max_batch": args.max_batch,
        "launch_cost_ms": args.launch_cost_ms,
        **compile_times,
        "scaling": {
            "one": {k: v for k, v in one.items() if k != "outputs"},
            "many": {k: v for k, v in two.items() if k != "outputs"},
            "speedup": scaling,
        },
        "chaos": chaos_phase,
        "shedding": shedding,
        "bit_exact": {"one": exact_one, "many": exact_two,
                      "chaos": exact_chaos, "shed": exact_shed},
        "metrics": REGISTRY.snapshot(),          # serve-side (shared)
        "fleet_metrics": reg_chaos.snapshot(),   # chaos-phase fleet plane
    }
    if args.json_path:
        with open(args.json_path, "w") as f:
            json.dump(out, f, indent=2, default=str)
        print(f"wrote {args.json_path}")

    if args.smoke:
        assert exact_one and exact_two and exact_chaos and exact_shed, (
            "fleet-served outputs diverged from the int8 oracle")
        assert chaos_phase["dropped"] == 0, (
            f"{chaos_phase['dropped']} requests dropped during the kill")
        assert chaos_phase["kills_fired"] >= 1, "the kill fault never fired"
        assert chaos_phase["evictions"] >= 1, "victim was never evicted"
        assert chaos_phase["retries"] >= 1, "no retries observed"
        assert chaos_phase["flight_dumps"] >= 1, (
            "eviction must freeze a flight dump")
        assert chaos_phase["readmitted"] and chaos_phase["admissions"] >= 1, (
            "healed replica was not re-admitted")
        assert chaos_phase["evict_events"] and chaos_phase["admit_events"] \
            and chaos_phase["n_retry_events"] >= 1, (
            "replica.evict / replica.admit / request.retry events missing")
        assert shedding["shed"] >= 1, "queue bound never shed"
        assert shedding["accepted"] >= 1, "queue bound shed everything"
        assert scaling >= 1.7, (
            f"{args.replicas}-replica fleet must scale >= 1.7x one replica "
            f"under a uniform injected launch cost; got {scaling:.2f}x")
        print(f"SMOKE OK: zero drops bit-exact under kill, evict/retry/"
              f"re-admit observable, shedding bounded, {scaling:.2f}x "
              f"scaling")
    return out


if __name__ == "__main__":
    main()
