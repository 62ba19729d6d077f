#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration (``configs/<name>.json``
and its reference ``configs/<name>.py``) and a traffic mix
(``traffic/<name>.json``), which names its loop (``loops/<loop>.py``) and
the program's front end it drives (``fronts/<kind>.py``).  The run builds
the model from the seed, serves it through that front end -> ``Session`` ->
fused Pallas executor, warms every batch shape the mix uses and runs the
mix's own warm-up (all of it set-up), then measures ``--seconds`` of
traffic.  ``--trace 1`` profiles a few seconds of the window and reports the
cell's per-layer metrics, ``--trace 0`` its end-to-end metrics, each read by
its own reader (``metrics/<name>.py``).
After the window the served outputs are checked against the reference
(``check.py``).  The last line of stdout is one JSON object; the last lines
of stderr are the numbers compared, each with its limit.

It prints the platform, device kind and device count, and exits 3 with no
result where JAX finds no TPU or fewer chips than the cell asks for.  The
persistent compile cache is the program's own (``repro.jax_cache``): a fixed
directory in the checkout.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()          # set-up is timed from here

import argparse                          # noqa: E402
import dataclasses                       # noqa: E402
import gc                                # noqa: E402
import json                              # noqa: E402
import os                                # noqa: E402
import shutil                            # noqa: E402
import sys                               # noqa: E402
import threading                         # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np                       # noqa: E402

from bench import check, model, spec, tracing, traffic   # noqa: E402
from bench import reference, roofline    # noqa: E402

OUT = os.path.join(ROOT, "bench", "out")
POOL = 256              # images per run; requests take them round-robin
TRACE_AT, TRACE_S = 0.25, 3.0   # traced share: from 1/4 into the window


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Run:
    """What a metric's reader may read."""
    window: traffic.Window
    seconds: float                # the window's length
    setup_s: float                # process start to the window's open
    records: list                 # batcher records of requests due in window
    pad_s: list                   # TRACER ``pad`` span durations, traced part
    trace: dict | None            # one device's ops/modules, t0, t1
    launches: list                # roofline launch shapes of the program
    batch: int | None             # the mix's one padded batch size, if one
    peak: dict | None
    ops_per_image: int


class _Stamps:
    """Logs the set-up's phases, each with the seconds it took."""

    def __init__(self):
        self.t = T_PROCESS

    def __call__(self, what: str) -> None:
        now = time.perf_counter()
        log(f"set-up: {what} {now - self.t:.2f} s")
        self.t = now


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def _peak_memory(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class _Profile:
    """Profiles [start + TRACE_AT * seconds, + TRACE_S] of the window on a
    thread of its own, so the traffic keeps its schedule."""

    def __init__(self, trace_dir: str):
        self.dir = trace_dir
        self.h0 = self.h1 = None          # time.perf_counter
        self._thread = None

    def start(self, w_start: float, w_end: float) -> None:
        on = w_start + TRACE_AT * (w_end - w_start)
        off = min(w_end, on + TRACE_S)
        self._thread = threading.Thread(target=self._run, args=(on, off),
                                        name="bench-profile", daemon=True)
        self._thread.start()

    def _run(self, on: float, off: float) -> None:
        import jax
        from repro.obs.trace import TRACER

        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 0        # device planes only: the host
        opts.python_tracer_level = 0      # tracer slowed the serving threads
        time.sleep(max(0.0, on - time.perf_counter()))
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        TRACER.clear()
        TRACER.enable()
        self.h0 = time.perf_counter()
        time.sleep(max(0.0, off - time.perf_counter()))
        self.h1 = time.perf_counter()
        TRACER.disable()
        jax.profiler.stop_trace()

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join()


def _device_trace(prof: _Profile) -> dict:
    """The first device's events over the traced window: from its first op
    to its last (the device's clock is the trace's own, not the host's)."""
    data = tracing.read(prof.dir)
    if not data:
        raise RuntimeError("profiler trace holds no TPU plane")
    plane = sorted(data)[0]
    ops, modules = data[plane]["ops"], data[plane]["modules"]
    t0 = min(a for _, a, _ in ops)
    t1 = max(b for _, _, b in ops)
    return {"ops": ops, "modules": modules, "t0": t0, "t1": t1,
            "plane": plane, "n_devices": len(data)}


def run_cell(name: str, cfg: dict, mix: dict, metrics: list, *, seed: int,
             seconds: float, trace: bool, devices) -> dict:
    """Everything after the look for a chip: set-up, window, the reduction
    to ``metrics`` (entries of BENCHMARK.json) and the check.  Returns the
    result line's object, and the compared rows under ``rows``."""
    import jax

    from repro.jax_cache import enable_compile_cache
    from repro.obs.trace import TRACER

    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    stamp = _Stamps()
    specs = spec.reference(cfg["reference"]).layers(cfg)
    in_shape = (cfg["image_size"], cfg["image_size"], cfg["channels"])
    params = reference.make_params(specs, (1,) + in_shape, seed)
    pool = model.images(cfg, POOL, seed)
    calib = model.as_float(cfg, pool[:cfg["calib_images"]])
    params = jax.block_until_ready(params)
    stamp("imports, weights and images")
    sess = model.session(cfg, specs, params, calib)
    params_np = jax.device_get(params)
    del params
    prog = sess.executor.program
    stamp("quantize, search, lower")
    log(f"program: {len(prog.launches())} fused launches "
        f"{dict(prog.meta['kinds'])}, {len(prog.fallbacks())} fallbacks")

    clock_off = time.perf_counter() - time.monotonic()
    records, rec_lock = [], threading.Lock()

    def observe(rec):
        with rec_lock:
            records.append(rec)

    front_kw = mix["front"]
    front_mod = spec.front(front_kw["kind"])
    front = front_mod.build(sess, front_kw, [observe])
    stamp(f"trace and compile of the {front_kw['kind']}'s batch shapes")
    prof = _Profile(os.path.join(OUT, "trace", name)) if trace else None
    if prof is not None:
        shutil.rmtree(prof.dir, ignore_errors=True)
    opened = {}

    def on_window(start, end):
        opened["setup_s"] = start - T_PROCESS
        stamp("the mix's warm-up")
        gc.callbacks.append(pauses)
        if prof is not None:
            prof.start(start, end)

    gc_s, gc_t = [], {}

    def pauses(phase, info):
        if phase == "start":
            gc_t["t"] = time.perf_counter()
        elif info["generation"] == 2:
            gc_s.append(time.perf_counter() - gc_t["t"])

    win = traffic.run(lambda i: front.submit(pool[i]), POOL, mix, seconds,
                      seed, on_window=on_window)
    gc.callbacks.remove(pauses)
    log(f"window: {len(gc_s)} full garbage collections, longest "
        f"{max(gc_s, default=0.0) * 1e3:.1f} ms")
    if prof is not None:
        prof.join()
    log(f"window: batches {front_mod.batches(front)}")
    front.close()
    due = win.due_in_window()
    failed = [r for r in due if r.done is None or r.error is not None]
    answered = [r for r in due if r.done is not None and r.error is None]
    log(f"window: {len(due)} requests due, {len(win.done_in_window())} "
        f"done in it, {len(failed)} failed")

    lo, hi = win.start - clock_off, win.end - clock_off
    run = Run(window=win, seconds=seconds, setup_s=opened["setup_s"],
              records=[r for r in records if lo <= r["submit_s"] < hi],
              pad_s=[], trace=None, launches=model.launches(sess.graph, prog),
              batch=front_mod.padded_batch(front_kw), peak=None,
              ops_per_image=roofline.model_ops(specs, in_shape))
    result = {"attempted": len(due), "failed": len(failed),
              "mem_peak": _peak_memory(devices)}
    if trace:
        run.peak = roofline.peaks(devices[0].device_kind)
        run.pad_s = [s.duration for s in TRACER.records() if s.name == "pad"]
        run.trace = _device_trace(prof)
        t_on, t_off = prof.h0, prof.h1
        n_traced = sum(1 for r in win.requests if r.done is not None
                       and r.error is None and t_on <= r.done < t_off)
        log(f"trace: {run.trace['plane']} of {run.trace['n_devices']}, "
            f"{run.trace['t1'] - run.trace['t0']:.3f} s of device ops "
            f"({t_off - t_on:.3f} s on the host), {len(run.trace['ops'])} "
            f"ops, {len(run.trace['modules'])} programs, "
            f"{len(run.pad_s)} pad spans; {n_traced / (t_off - t_on):.1f} "
            f"images/s done while traced, "
            f"{len(win.done_in_window()) / seconds:.1f} over the window")
        ops = run.trace["ops"]
        result["device_extra"] = {
            "busy_s": tracing.busy_s(ops),
            "window_s": run.trace["t1"] - run.trace["t0"]}
        result["breakdown"] = {
            "device_ops": tracing.top_ops(ops),
            "idle_gaps": tracing.named_gaps(ops, run.trace["modules"],
                                            run.trace["t0"], run.trace["t1"])}
    result["metrics"] = {}
    for m in metrics:
        val = spec.metric(m["name"]).read(run)
        if val is not None:
            result["metrics"][m["name"]] = {"value": float(val),
                                            "unit": m["unit"]}

    # the check: the program's state goes first, then the reference runs
    picked = [answered[i] for i in check.sample(len(answered), seed)]
    limits = cfg["check"]
    numbers = {k: {"value": None, "limit": limits[k]} for k in
               ("logit_rel_err_max", "departure_err_max")}
    numbers["unfinished"] = {"value": len(failed), "limit": 0}
    if len(picked) >= 2:
        served, probs = model.served_values(sess, [r.output for r in picked])
        del front, sess, prog, win, run, due, answered
        gc.collect()
        jax.clear_caches()
        params = jax.device_put(params_np, devices[0])
        fwd = jax.jit(lambda p, x: reference.forward(specs, p, x))
        images = np.array([r.image for r in picked])
        ref = check.reference_outputs(lambda x: fwd(params, x),
                                      model.as_float(cfg, pool[images]))
        for k, v in check.numbers(served, ref, probs).items():
            numbers[k]["value"] = v
        result["rows"] = {"served": served, "ref": ref, "probs": probs,
                          "images": images}
    result["correct"] = check.verdict(numbers)
    result["check"] = numbers
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = spec.benchmark()
    cell = spec.cell(bench, args.workload)
    import jax

    devices = jax.devices()
    dev = device_info(devices)
    print(f"jax {jax.__version__}; platform {dev['platform']}; device_kind "
          f"{dev['kind']}; device count {dev['count']}", flush=True)
    if dev["platform"] != "tpu" or dev["count"] < cell["chips"]:
        log(f"bench: needs {cell['chips']} TPU chip(s), found "
            f"{dev['count']} {dev['platform']} device(s); nothing was run")
        return 3
    used = devices[:cell["chips"]]
    cfg = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    kind = "per_layer" if args.trace else "end_to_end"
    res = run_cell(args.workload, cfg, mix,
                   spec.metrics_of(bench, args.workload, kind),
                   seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), devices=used)
    device = dict(device_info(used), memory_peak_bytes=res.pop("mem_peak", 0))
    device.update(res.pop("device_extra", {}))
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": res["metrics"],
            "device": device}
    if "breakdown" in res:
        line["breakdown"] = res["breakdown"]
    line["check"] = res["check"]
    for k, v in res["check"].items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
