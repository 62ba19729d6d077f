"""The runtime supporter's unit of ownership: one lowered program, served.

A :class:`Session` binds one (graph, strategy, device, quantization) tuple
to an :class:`~repro.core.executor.Int8Executor`.  With ``backend="pallas"``
the executor lowers the strategy once into its ``GroupProgram`` (or
dispatches the quantized ``.program`` of a loaded object file as it is) and
runs it as ``kernels.conv_fused`` launches; ``backend="ref"`` runs the int8
oracle.  Nothing here plans the FPGA object file: the tiling, memory plan,
instruction stream and hazard audit of ``repro.asm`` are what the paper's
tables, the zoo and ``repro.explain`` build from, not what is served.

``run`` serves one request; ``run_batch`` stacks N queued requests into one
batched launch (one Pallas grid covers all N images — the executor's batch
dimension is free); ``serve`` wraps the session in the dynamic-batching
:class:`~repro.runtime.server.Server`.

A batch runs in two phases.  ``launch`` stacks the requests, copies the
batch in and enqueues the program, and returns a :class:`PendingBatch`
without waiting for the device; ``PendingBatch.results`` waits for the
device, copies the outputs out and slices one dict per request.  The
server's batcher dispatches the next batch between the two, so the host's
stacking and copy-in overlap the device's run of the batch before it;
``run_batch`` is the two phases back to back.
"""
from __future__ import annotations

import numpy as np


class PendingBatch:
    """A dispatched batch whose outputs are still on the device."""

    def __init__(self, executor, batch, n: int):
        self._executor = executor
        self._batch = batch             # executor.Dispatched
        self.n = n                      # requests (rows before padding)

    def results(self) -> list[dict]:
        """Wait for the device; one output dict per request (leading batch
        dim 1, directly comparable with per-request execution)."""
        host = self._executor(self._batch)
        return [{k: v[i:i + 1] for k, v in host.items()}
                for i in range(self.n)]


class Session:
    """Owns the executor of one lowered model."""

    def __init__(self, g, strategy, dev, qm, *, backend: str = "ref",
                 placement=None):
        """``strategy`` is a ``pathsearch.Strategy`` (lowered here, once) or
        anything that duck-types one, such as a loaded
        ``asm.CompiledArtifact``, whose quantized ``.program`` the pallas
        backend dispatches as it is.  ``placement`` pins every launch to one
        ``jax.Device`` (the fleet layer places data-parallel replicas across
        ``jax.devices()``)."""
        from repro.core.executor import Int8Executor

        self.graph, self.qm, self.device = g, qm, dev
        self.backend = backend
        self.executor = Int8Executor(g, qm, strategy=strategy, backend=backend)
        # the program this session serves; the ref backend runs per node, so
        # it reports the program it was handed, if any
        self.program = (self.executor.program
                        if self.executor.program is not None
                        else getattr(strategy, "program", None))
        self.outputs = [n.name for n in g if not g.consumers(n.name)]
        self.n_runs = 0
        self.images_served = 0
        self.drift = None               # optional DriftProfiler (attach_drift)
        self.placement = placement      # optional jax.Device to launch on
        self._launch_hook = None        # optional pre-launch hook (chaos)

    @classmethod
    def from_artifact(cls, art, *, backend: str = "ref",
                      placement=None) -> "Session":
        """Open a session on a loaded DNNVM object file: its graph, device,
        quantized model and stored program, with no re-lowering."""
        from repro.hw import get_device
        return cls(art.rebuild_graph(), art, get_device(art.device),
                   art.quantized_model(), backend=backend,
                   placement=placement)

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

    def _launch(self, x, wait: bool = True):
        """One executor launch, through the hook and onto the placement
        device (``jax.default_device``, so the jit cache key and the arrays
        the launch creates are the replica's).  ``wait``: the executor's
        call, outputs on the host; else its dispatch, outputs still on the
        device."""
        if self._launch_hook is not None:
            self._launch_hook(x)
        run = self.executor if wait else self.executor.dispatch
        if self.placement is None:
            return run(x)
        import jax
        with jax.default_device(self.placement):
            return run(x)

    def drift_state(self) -> dict | None:
        """The attached profiler's most recent summary (None when no drift
        profiler is attached or it has not sampled yet) — what the flight
        recorder stamps onto request records."""
        return self.drift.last if self.drift is not None else None

    def tile_summary(self) -> list[dict]:
        """Launched tile shape per lowered unit of the served program — the
        static per-tenant context the flight recorder carries in forensic
        dumps.  ``tile`` is the searched (t_h, t_w, t_oc), or None when the
        kernel's heuristic shapes run."""
        from repro.core import lower
        if self.program is None:
            return []
        out = []
        for item in self.program.items:
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

    def launch(self, xs, pad_to: int | None = None) -> PendingBatch:
        """Stack N queued requests (zero-padded to ``pad_to``) and dispatch
        them as ONE batched launch, without waiting for the device.  The
        ``launch`` span covers the copy-in and the dispatch only."""
        from repro.core.executor import Dispatched
        from repro.obs.trace import TRACER
        with TRACER.span("pad", cat="serve", track="batch", n=len(xs),
                         pad_to=pad_to):
            x, n = self._stack(xs, pad_to=pad_to)
        with TRACER.span("launch", cat="serve", track="batch",
                         batch=int(x.shape[0])):
            out = self._launch(x, wait=False)
        self.n_runs += 1
        self.images_served += n
        if self.drift is not None:
            self.drift.observe_launch()
        return PendingBatch(self.executor, Dispatched(x, out), n)

    def run_batch(self, xs, pad_to: int | None = None) -> list[dict]:
        """Serve N queued requests as ONE batched launch and wait for it;
        returns one output dict per request (leading batch dim 1, so results
        are directly comparable with per-request execution)."""
        return self.launch(xs, pad_to=pad_to).results()

    # -------------------------------------------------------------- serving
    def serve(self, **kw):
        from repro.runtime.server import Server
        return Server(self, **kw)

    def stats(self) -> dict:
        return {"n_runs": self.n_runs, "images_served": self.images_served,
                "fused_coverage": (self.program.meta["coverage"]
                                   if self.program is not None else 0.0)}
