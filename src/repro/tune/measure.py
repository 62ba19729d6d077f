"""On-device measurement harness (paper Table 2, method 1: "<1 s, 0%").

Runs individual ``GroupProgram`` entries — ``FusedLaunch`` chains, horizontal
stacks and ``RefFallback`` groups — through the *real* executor path in
isolation and wall-clocks them with warmup / repeat / median-of-k timing and
MAD-based outlier rejection.  Because measurement reuses the ``core.lower``
descriptors, every candidate group the path search can enumerate is also a
measurable unit: lower the group once, build a standalone jitted callable
around its launch, time it.

The harness is the ground-truth source for :mod:`repro.tune.calibrate`; it is
also usable directly (``measure_strategy`` times a whole compiled strategy
end-to-end for the tune benchmark's A/B comparison).
"""
from __future__ import annotations

import dataclasses
import math
import time

import numpy as np

from repro.core import executor as core_executor
from repro.core import lower
from repro.core.xgraph import XGraph
from repro.hw import DeviceModel


@dataclasses.dataclass(frozen=True)
class Measurement:
    """Robust wall-clock of one measurable unit."""
    nodes: tuple
    kind: str                  # "chain" | "horizontal" | "fallback" | "e2e"
    seconds: float             # median of accepted samples
    spread: float              # MAD / median of accepted samples (rel. jitter)
    n_samples: int             # accepted sample count
    n_rejected: int            # outliers dropped by the MAD filter
    samples: tuple = ()        # raw samples (accepted + rejected), seconds

    def to_json(self) -> dict:
        return {"nodes": list(self.nodes), "kind": self.kind,
                "seconds": self.seconds, "spread": self.spread,
                "n_samples": self.n_samples, "n_rejected": self.n_rejected}


def _robust_center(samples: list, reject_nmad: float,
                   center: str = "median") -> tuple:
    """(center, relative spread, n_accepted, n_rejected) with MAD rejection.

    ``center="median"`` is the classic median-of-k after rejecting samples
    more than ``reject_nmad`` MADs out.  ``center="min"`` takes the fastest
    sample: on shared boxes interference is strictly additive and swings at
    second granularity, so the minimum over many short samples converges to
    the uncontended time — the quantity cross-group ratios must be built on.
    """
    s = np.asarray(samples, dtype=float)
    med = float(np.median(s))
    mad = float(np.median(np.abs(s - med)))
    tol = reject_nmad * max(mad, 1e-12)
    keep = s[np.abs(s - med) <= tol]
    if keep.size == 0:                     # pathological: keep everything
        keep = s
    med = float(np.median(keep))
    spread = float(np.median(np.abs(keep - med)) / max(med, 1e-12))
    loc = float(s.min()) if center == "min" else med
    return loc, spread, int(keep.size), int(s.size - keep.size)


def time_callable(fn, ins, *, warmup: int = 1, repeats: int = 5,
                  reject_nmad: float = 3.5, min_sample_s: float = 0.0,
                  max_calls: int = 512, center: str = "median") -> tuple:
    """Time ``fn(*ins)`` with warmup + per-call block_until_ready.

    With ``min_sample_s > 0`` each timed sample loops the callable until it
    spans that much wall clock (per-sample seconds = loop time / calls) —
    amortizes cgroup throttle bursts at the price of averaging interference
    in.  With the default 0 every sample is a single call, which suits the
    ``center="min"`` estimator (see :func:`_robust_center`).

    Returns (seconds, spread, n_accepted, n_rejected, samples)."""
    import jax

    for _ in range(max(1, warmup)):        # compile + cache warm
        jax.block_until_ready(fn(*ins))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*ins))        # probe sizes the sample loop
    probe = max(time.perf_counter() - t0, 1e-9)
    calls = int(min(max_calls, max(1, math.ceil(min_sample_s / probe))))
    samples = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*ins)
        jax.block_until_ready(out)
        samples.append((time.perf_counter() - t0) / calls)
    loc, spread, n_ok, n_rej = _robust_center(samples, reject_nmad, center)
    return loc, spread, n_ok, n_rej, tuple(samples)


# ------------------------------------------------------------- unit builders
def _rand_int8(rng, shape):
    import jax.numpy as jnp
    # full-range int8 activations (see executor.build_group_callable: near-zero
    # data constant-folds saturation work away and skews timings)
    return jnp.asarray(rng.integers(-128, 128, shape), jnp.int8)


def build_item_callable(g: XGraph, qm, item):
    """One ``GroupProgram`` item as a standalone jitted callable + inputs.

    ``FusedLaunch`` entries run the actual Pallas chain/horizontal kernel;
    ``RefFallback`` entries run their nodes through the int8 ref ops — the
    exact per-item execution path of ``Int8Executor(backend="pallas")``.
    """
    import jax

    rng = np.random.default_rng(0)
    if isinstance(item, lower.RefFallback):
        return core_executor.build_group_callable(g, list(item.nodes), qm)

    from repro.kernels.conv_fused import ops as fused_ops

    in_names = list(dict.fromkeys((item.in_name,) + tuple(item.sides)))
    ins = [_rand_int8(rng, g.shape(nm)) for nm in in_names]

    @jax.jit
    def fn(*xs):
        env = dict(zip(in_names, xs))
        out = fused_ops.run_launch(item, env, qm)
        return tuple(out[k] for k in sorted(out))

    return fn, ins


# ---------------------------------------------------------------- the harness
class MeasurementHarness:
    """Measure groups / program items / whole strategies on this machine.

    ``backend="pallas"`` lowers each group through ``core.lower`` and times
    the fused kernel launch (ref ops only where lowering decides to fall
    back); ``backend="ref"`` times the per-node int8 reference path.  Results
    are memoized per group — the path search revisits segments freely.
    """

    def __init__(self, g: XGraph, qm, dev: DeviceModel | None = None, *,
                 backend: str = "pallas",
                 warmup: int = 1, repeats: int = 12,
                 reject_nmad: float = 3.5, min_sample_s: float = 0.0,
                 center: str = "min"):
        if backend not in ("pallas", "ref"):
            raise ValueError(f"unknown backend {backend!r}")
        if center not in ("median", "min"):
            raise ValueError(f"unknown center {center!r}")
        self.g, self.qm, self.dev = g, qm, dev
        self.backend = backend
        self.warmup, self.repeats = warmup, repeats
        self.reject_nmad = reject_nmad
        self.min_sample_s = min_sample_s
        self.center = center
        self._cache: dict[tuple, Measurement] = {}

    # ------------------------------------------------------------ internals
    def _time(self, fn, ins, nodes, kind) -> Measurement:
        med, spread, n_ok, n_rej, samples = time_callable(
            fn, ins, warmup=self.warmup, repeats=self.repeats,
            reject_nmad=self.reject_nmad, min_sample_s=self.min_sample_s,
            center=self.center)
        return Measurement(nodes=tuple(nodes), kind=kind, seconds=med,
                           spread=spread, n_samples=n_ok, n_rejected=n_rej,
                           samples=samples)

    def _lower_chain(self, group: list):
        return lower.lower_group(self.g, self.qm, list(group))

    def _group_callable(self, group: list) -> tuple:
        if self.backend == "pallas":
            item = self._lower_chain(group)
            kind = (item.kind if isinstance(item, lower.FusedLaunch)
                    else "fallback")
            fn, ins = build_item_callable(self.g, self.qm, item)
        else:
            kind = "fallback"
            fn, ins = core_executor.build_group_callable(
                self.g, list(group), self.qm)
        return fn, ins, kind

    # -------------------------------------------------------------- units
    def measure_item(self, item) -> Measurement:
        kind = (item.kind if isinstance(item, lower.FusedLaunch)
                else "fallback")
        fn, ins = build_item_callable(self.g, self.qm, item)
        return self._time(fn, ins, item.nodes, kind)

    def measure_group(self, group: list) -> Measurement:
        """Measure one chain group through this harness's backend."""
        key = ("chain", tuple(group))
        if key in self._cache:
            return self._cache[key]
        fn, ins, kind = self._group_callable(group)
        m = self._time(fn, ins, group, kind)
        self._cache[key] = m
        return m

    def _round_robin(self, units: list, passes: int | None) -> list:
        """The shared epoch-robust timing core: warm + probe every callable
        first, then each pass times every unit once — a shared-box
        interference epoch inflates whole passes (which MAD rejection
        discards), never one unit's samples relative to another's.

        ``units``: (nodes, kind, fn, ins) per measurable; returns one
        :class:`Measurement` per unit, in order."""
        import jax

        passes = passes if passes is not None else self.repeats
        prepped = []
        for nodes, kind, fn, ins in units:
            for _ in range(max(1, self.warmup)):
                jax.block_until_ready(fn(*ins))
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*ins))
            probe = max(time.perf_counter() - t0, 1e-9)
            calls = int(min(512, max(1, math.ceil(self.min_sample_s / probe))))
            prepped.append((nodes, kind, fn, ins, calls, []))
        for _ in range(max(1, passes)):
            for nodes, kind, fn, ins, calls, samples in prepped:
                t0 = time.perf_counter()
                for _ in range(calls):
                    out = fn(*ins)
                jax.block_until_ready(out)
                samples.append((time.perf_counter() - t0) / calls)
        out_ms = []
        for nodes, kind, fn, ins, calls, samples in prepped:
            loc, spread, n_ok, n_rej = _robust_center(
                samples, self.reject_nmad, self.center)
            out_ms.append(Measurement(
                nodes=tuple(nodes), kind=kind, seconds=loc, spread=spread,
                n_samples=n_ok, n_rejected=n_rej, samples=tuple(samples)))
        return out_ms

    def measure_set(self, groups: list, passes: int | None = None) -> list:
        """Measure many groups in round-robin passes (see
        :meth:`_round_robin` for why cross-group ratios need this)."""
        todo = []
        for grp in groups:
            key = ("chain", tuple(grp))
            if key in self._cache:
                continue
            fn, ins, kind = self._group_callable(grp)
            todo.append((key, (grp, "chain", fn, ins)))
        for (key, _), m in zip(todo,
                               self._round_robin([u for _, u in todo],
                                                 passes)):
            self._cache[key] = m
        return [self._cache[("chain", tuple(grp))] for grp in groups]

    def measure_item_set(self, items: list, passes: int | None = None
                         ) -> list[Measurement]:
        """Measure arbitrary program items in round-robin passes — the same
        epoch-robust machinery as :meth:`measure_set`, but over prebuilt
        ``FusedLaunch`` / ``RefFallback`` descriptors.  This is how the
        tile-shape search times the top-K tile candidates of every lowered
        unit: a tile variant is just another measurable item, and measuring
        all variants of all units in the same passes means interference
        epochs inflate whole passes instead of biasing one candidate.

        Results are NOT memoized: tile variants of one launch share the same
        node cover, so the per-group cache key would collide."""
        units = []
        for item in items:
            kind = (item.kind if isinstance(item, lower.FusedLaunch)
                    else "fallback")
            fn, ins = build_item_callable(self.g, self.qm, item)
            units.append((item.nodes, kind, fn, ins))
        return self._round_robin(units, passes)

    def measure_horizontal(self, heads: list) -> Measurement:
        """Measure a horizontal (shared-input) group: the sum of its lowered
        items (one stacked launch + any individually-lowered leftovers)."""
        key = ("horizontal", tuple(heads))
        if key in self._cache:
            return self._cache[key]
        if self.backend == "pallas":
            items = lower.lower_horizontal(self.g, self.qm, list(heads))
            parts = [self.measure_item(it) for it in items]
        else:
            parts = [self.measure_group([h]) for h in heads]
        m = Measurement(
            nodes=tuple(heads), kind="horizontal",
            seconds=sum(p.seconds for p in parts),
            spread=max((p.spread for p in parts), default=0.0),
            n_samples=min((p.n_samples for p in parts), default=0),
            n_rejected=sum(p.n_rejected for p in parts))
        self._cache[key] = m
        return m

    def measure_program(self, program: lower.GroupProgram) -> list:
        return [self.measure_item(item) for item in program.items]

    # ---------------------------------------------------------- end to end
    def measure_strategy(self, strategy, *, repeats: int | None = None,
                         seed: int = 1) -> Measurement:
        """Wall-clock one full strategy through ``Int8Executor`` (the e2e
        number the tune benchmark compares across search evaluators)."""
        ex = core_executor.Int8Executor(self.g, self.qm, strategy=strategy,
                                        backend=self.backend)
        rng = np.random.default_rng(seed)
        shape = next(self.g.shape(n.name) for n in self.g if n.op == "input")
        x = rng.integers(-128, 128, shape).astype(np.int8)
        med, spread, n_ok, n_rej, samples = time_callable(
            lambda v: _run(ex, v), [x],
            warmup=self.warmup,
            repeats=repeats if repeats is not None else self.repeats,
            reject_nmad=self.reject_nmad, min_sample_s=self.min_sample_s,
            center=self.center)
        nodes = tuple(nm for grp in strategy.groups for nm in grp)
        return Measurement(nodes=nodes, kind="e2e", seconds=med,
                           spread=spread, n_samples=n_ok, n_rejected=n_rej,
                           samples=samples)

    def measure_strategy_set(self, strategies: list, *,
                             passes: int | None = None,
                             seed: int = 1) -> list:
        """Alternate end-to-end passes across ``strategies`` so clock drift
        and interference epochs hit every contender equally (the A/B the tune
        benchmark reports).  Same robust center as ``measure_set``."""
        import jax

        passes = passes if passes is not None else self.repeats
        rng = np.random.default_rng(seed)
        shape = next(self.g.shape(n.name) for n in self.g if n.op == "input")
        x = rng.integers(-128, 128, shape).astype(np.int8)
        units = []
        for s in strategies:
            ex = core_executor.Int8Executor(self.g, self.qm, strategy=s,
                                            backend=self.backend)
            for _ in range(max(1, self.warmup)):
                _run(ex, x)
            t0 = time.perf_counter()
            _run(ex, x)
            probe = max(time.perf_counter() - t0, 1e-9)
            calls = int(min(512, max(1, math.ceil(self.min_sample_s / probe))))
            units.append((s, ex, calls, []))
        for _ in range(max(1, passes)):
            for s, ex, calls, samples in units:
                t0 = time.perf_counter()
                for _ in range(calls):
                    out = _run(ex, x)
                jax.block_until_ready(out)
                samples.append((time.perf_counter() - t0) / calls)
        out_ms = []
        for s, ex, calls, samples in units:
            loc, spread, n_ok, n_rej = _robust_center(
                samples, self.reject_nmad, self.center)
            nodes = tuple(nm for grp in s.groups for nm in grp)
            out_ms.append(Measurement(
                nodes=nodes, kind="e2e", seconds=loc, spread=spread,
                n_samples=n_ok, n_rejected=n_rej, samples=tuple(samples)))
        return out_ms


def _run(ex, x):
    # Int8Executor returns numpy dicts (already device-synced); wrap so
    # time_callable's block_until_ready has something array-like to touch.
    out = ex(x)
    return list(out.values())
