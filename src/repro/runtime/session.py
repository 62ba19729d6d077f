"""The runtime supporter's unit of ownership: one compiled model, served.

A :class:`Session` binds together everything needed to run inference against
one (graph, strategy, device, quantization) tuple:

* the :class:`~repro.asm.artifact.CompiledArtifact`, obtained through a
  :class:`~repro.asm.artifact.PlanCache` — the serving path compiles once and
  every later construction is a dictionary hit;
* the :class:`~repro.core.executor.Int8Executor` over the artifact's lowered
  ``GroupProgram`` (ref oracle or Pallas fused launches);
* the memory plan + addressed instruction stream, from which
  :meth:`pipeline_report` derives the engine-level cross-request schedule.

``run`` serves one request; ``run_batch`` stacks N queued requests into one
batched launch (one Pallas grid covers all N images — the executor's batch
dimension is free); ``serve`` wraps the session in the dynamic-batching
:class:`~repro.runtime.server.Server`.
"""
from __future__ import annotations

import numpy as np


def _resolve_profile(profile):
    """None | DeviceProfile | name/path -> DeviceProfile | None (lazy tune
    import: the runtime must not pay for the tuner unless profiles are used)."""
    if profile is None:
        return None
    from repro.tune.profile import resolve_profile
    return resolve_profile(profile)


class Session:
    """Owns the executor + memory plan for one compiled model."""

    def __init__(self, g, strategy, dev, qm, *, backend: str = "ref",
                 cache=None, profile=None,
                 pin_input: bool | None = None,
                 cache_max_entries: int | None = None, placement=None):
        """``profile`` names the calibrated device profile to compile under —
        a ``tune.DeviceProfile``, a profile name/path resolved through the
        on-disk ``tune.ProfileCache``, or None (the analytic model; a
        strategy picked by a profile-guided search still keys by the profile
        hash it carries).  ``pin_input`` forwards to the memory planner.
        ``cache_max_entries`` rebounds the plan cache this session compiles
        through (a multi-model host sets it once to cap resident artifacts).
        ``placement`` pins every launch to one ``jax.Device`` (the fleet
        layer places data-parallel replicas across ``jax.devices()``)."""
        from repro import asm
        from repro.core.executor import Int8Executor

        self.profile = _resolve_profile(profile)
        self.cache = cache if cache is not None else asm.PLAN_CACHE
        if cache_max_entries is not None:
            self.cache.max_entries = cache_max_entries
        self.artifact, self.cache_hit = self.cache.get_or_compile(
            g, strategy, dev, qm=qm, profile=self.profile,
            pin_input=pin_input)
        self.graph, self.qm, self.device = g, qm, dev
        self.backend = backend
        self.executor = Int8Executor(g, qm, strategy=self.artifact,
                                     backend=backend)
        self.outputs = [n.name for n in g if not g.consumers(n.name)]
        self.n_runs = 0
        self.images_served = 0
        self.drift = None               # optional DriftProfiler (attach_drift)
        self.placement = placement      # optional jax.Device to launch on
        self._launch_hook = None        # optional pre-launch hook (chaos)

    @classmethod
    def from_artifact(cls, art, *, backend: str = "ref", cache=None,
                      profile=None,
                      cache_max_entries: int | None = None,
                      placement=None) -> "Session":
        """Open a session on a loaded DNNVM object file — no recompilation:
        the artifact is seeded into the plan cache under its own key.

        The artifact records the device-profile hash it was planned under;
        loading it under a *different* profile (or under none, when it was
        profile-planned) warns — the plan was tuned for measured rates this
        deployment may not match."""
        import warnings

        from repro import asm
        from repro.hw import get_device

        resolved = _resolve_profile(profile)
        got = resolved.hash() if resolved is not None else None
        want = art.profile_hash
        if got != want:
            warnings.warn(
                f"artifact was planned under device profile "
                f"{want or 'analytic'} ({art.meta.get('profile_name') or 'n/a'}) "
                f"but is being loaded under {got or 'analytic'} — its "
                f"strategy was tuned for measured rates this session may not "
                f"match; recompile under the current profile to re-tune",
                stacklevel=2)
        g = art.rebuild_graph()
        qm = art.quantized_model()
        dev = get_device(art.device)
        cache = cache if cache is not None else asm.PLAN_CACHE
        # seed and construct under the SAME resolved profile so the cache key
        # matches (no recompile) and the session keeps the profile — dropping
        # it here used to lose profile-guided ddr_slots auto-selection in
        # pipeline_report and the session-side profile_hash provenance
        cache.put(g, art, dev, art, qm=qm, profile=resolved)
        return cls(g, art, dev, qm, backend=backend, cache=cache,
                   profile=resolved,
                   cache_max_entries=cache_max_entries, placement=placement)

    # ------------------------------------------------------------- execution
    def _stack(self, xs, pad_to: int | None = None):
        rows = [np.asarray(x) for x in xs]
        rows = [r[None] if r.ndim == 3 else r for r in rows]
        x = np.concatenate(rows, axis=0)
        n = x.shape[0]
        if pad_to is not None and pad_to > n:
            # pad with zero images up to an allowed batch size: bounds the
            # number of distinct batch shapes the jitted executor ever traces
            x = np.concatenate(
                [x, np.zeros((pad_to - n,) + x.shape[1:], x.dtype)], axis=0)
        return x, n

    def attach_drift(self, profiler) -> None:
        """Attach an ``obs.DriftProfiler``; every ``run``/``run_batch`` then
        counts as one observed launch (the profiler samples every Nth)."""
        self.drift = profiler

    def set_launch_hook(self, fn) -> None:
        """Install (or with None, clear) a pre-launch hook: called with the
        stacked input batch immediately before every executor launch.  An
        exception raised here fails the launch exactly as an executor fault
        would — the seam the chaos injector (``runtime.chaos``) uses to kill,
        hang, slow, or poison one replica deterministically."""
        self._launch_hook = fn

    def _launch(self, x):
        """One executor launch, through the hook and onto the placement
        device (``jax.default_device``; a no-op for the numpy ref backend's
        compute, but keeps any jax arrays the launch creates on the replica's
        device)."""
        if self._launch_hook is not None:
            self._launch_hook(x)
        if self.placement is None:
            return self.executor(x)
        import jax
        with jax.default_device(self.placement):
            return self.executor(x)

    def drift_state(self) -> dict | None:
        """The attached profiler's most recent summary (None when no drift
        profiler is attached or it has not sampled yet) — what the flight
        recorder stamps onto request records."""
        return self.drift.last if self.drift is not None else None

    def tile_summary(self) -> list[dict]:
        """Launched tile shape per lowered unit — the static per-tenant
        context the flight recorder carries in forensic dumps.  ``tile``
        is the searched (t_h, t_w, t_oc), or None when the kernel's
        heuristic shapes run."""
        from repro.core import lower
        if self.artifact.program is None:
            return []
        out = []
        for item in self.artifact.program.items:
            if isinstance(item, lower.RefFallback):
                out.append({"nodes": "+".join(item.nodes),
                            "kind": "fallback", "tile": None})
            else:
                out.append({"nodes": "+".join(item.nodes), "kind": item.kind,
                            "tile": list(item.tile) if item.tile else None})
        return out

    def run(self, x) -> dict:
        """One request; accepts (H, W, C) or (1, H, W, C) int8."""
        x = np.asarray(x)
        out = self._launch(x[None] if x.ndim == 3 else x)
        self.n_runs += 1
        self.images_served += 1
        if self.drift is not None:
            self.drift.observe_launch()
        return out

    def run_batch(self, xs, pad_to: int | None = None) -> list[dict]:
        """Serve N queued requests as ONE batched launch; returns one output
        dict per request (leading batch dim 1, so results are directly
        comparable with per-request execution)."""
        from repro.obs.trace import TRACER
        with TRACER.span("pad", cat="serve", track="batch", n=len(xs),
                         pad_to=pad_to):
            x, n = self._stack(xs, pad_to=pad_to)
        with TRACER.span("launch", cat="serve", track="batch",
                         batch=int(x.shape[0])):
            out = self._launch(x)
        self.n_runs += 1
        self.images_served += n
        if self.drift is not None:
            self.drift.observe_launch()
        return [{k: v[i:i + 1] for k, v in out.items()} for i in range(n)]

    # -------------------------------------------------------- schedule view
    def pipeline_report(self, n_requests: int, ddr_slots: int | None = 2,
                        profile=None):
        """Engine-level cross-request schedule of ``n_requests`` pipelined
        copies of this session's instruction stream (hazard-audited).

        ``ddr_slots=None`` selects the double-buffer slot depth from the
        stream's DRAM/compute ratio under ``profile`` (defaulting to the
        profile this session was compiled with)."""
        from repro.runtime.schedule import pipeline_report
        return pipeline_report(self.artifact, n_requests, ddr_slots=ddr_slots,
                               profile=(profile if profile is not None
                                        else self.profile))

    # --------------------------------------------------------------- explain
    def explain(self, *, render: bool = False):
        """This session's compile-decision provenance, joined with live drift.

        Returns the artifact's ``CompileReport`` (``repro.explain``) extended
        with a ``drift`` section when a :class:`~repro.obs.drift.DriftProfiler`
        is attached and has samples: per-unit measured-vs-predicted seconds —
        the static plan's predictions next to what this deployment actually
        measures.  ``render=True`` returns the text rendering instead."""
        from repro.explain import render_report, report_of
        from repro.obs.events import EVENTS

        rep = dict(report_of(self.artifact))
        drift_rows = None
        if self.drift is not None:
            dr = self.drift.report()
            drift_rows = [{
                "key": u.key.replace("+", "|"),
                "kind": u.kind,
                "predicted": u.predicted,
                "measured": u.measured,
                "deviation": u.deviation,
                "n_samples": u.n_samples,
            } for u in dr.units]
            rep["drift"] = {
                "units": drift_rows,
                "drifted": bool(dr.drifted),
                "aggregate_deviation": dr.aggregate,
                "profile_match": dr.profile_match,
            }
        EVENTS.emit("explain.report",
                    message=f"explain {rep['model']} (session"
                            f"{', with drift' if drift_rows else ''})",
                    model=rep["model"], device=rep["device"],
                    degraded=rep.get("degraded", False),
                    n_drift_units=len(drift_rows or []))
        if render:
            return render_report(rep, drift=drift_rows)
        return rep

    # -------------------------------------------------------------- serving
    def serve(self, **kw):
        from repro.runtime.server import Server
        return Server(self, **kw)

    def stats(self) -> dict:
        return {"n_runs": self.n_runs, "images_served": self.images_served,
                "cache_hit": self.cache_hit,
                "cache_hits": self.cache.hits, "cache_misses": self.cache.misses,
                "fused_coverage": self.artifact.fused_coverage,
                "sim_cycles_per_image": self.artifact.sim_total_cycles,
                "profile_hash": self.artifact.profile_hash,
                "session_profile_hash": (self.profile.hash()
                                         if self.profile else None),
                "pin_input": self.artifact.pin_input}
