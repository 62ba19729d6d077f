"""Validation environment (paper §3.2, "Validation Module").

"Our validation tools can prevent even a one-bit difference between the
results by the CPU and the results by the FPGA."

Here: the pure-jnp fixed-point executor is the CPU-side oracle; the Pallas
fused-kernel executor (interpret mode on this container, real MXU on TPU) is
the hardware side.  ``bit_exact`` fails on a single differing int8 value.
It also checks that *fusion itself* never changes numerics: any strategy must
produce the same bits as the unfused naive execution.

``fused_coverage`` audits the *lowering* the same way the bit-exactness bench
audits numerics: what fraction of the strategy's groups actually execute as
fused kernel launches, and an explicit reason for every group that does not
(no silent fallback).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import lower
from repro.core.executor import Int8Executor, build_float_fn
from repro.core.quantize import QuantizedModel
from repro.core.xgraph import XGraph


@dataclasses.dataclass
class ValidationReport:
    bit_exact: bool
    n_outputs: int
    max_abs_diff: int
    sqnr_db: dict  # vs float reference, per output

    def __bool__(self) -> bool:
        return self.bit_exact


@dataclasses.dataclass
class CoverageReport:
    """How much of a strategy the compiler lowered to fused launches."""
    n_groups: int            # strategy groups (excl. host + folded concat)
    n_fused: int             # groups entirely covered by FusedLaunch items
    n_launches: int
    fallback_reasons: dict   # reason -> count (every entry allow-listed)
    kinds: dict              # launch kind -> count

    @property
    def ratio(self) -> float:
        return (self.n_fused / self.n_groups) if self.n_groups else 1.0


def fused_coverage(g: XGraph, strategy, qm: QuantizedModel | None = None
                   ) -> CoverageReport:
    """Lower ``strategy`` (or read a CompiledArtifact's program) and report
    the fused-execution coverage.  Every non-fused group must carry a reason
    from ``lower.FALLBACK_REASONS`` — lowering raises otherwise."""
    prog = getattr(strategy, "program", None)
    if prog is None:
        prog = lower.lower_strategy(g, strategy, qm)
    m = prog.meta
    return CoverageReport(
        n_groups=m["n_units"], n_fused=m["n_fused_units"],
        n_launches=m["n_launches"],
        fallback_reasons=dict(m["fallback_reasons"]), kinds=dict(m["kinds"]))


def bit_exact(g: XGraph, qm: QuantizedModel, x: np.ndarray, strategy=None,
              backend: str = "pallas", float_params=None) -> ValidationReport:
    ref = Int8Executor(g, qm, strategy=None, backend="ref")(x)        # naive, unfused
    got = Int8Executor(g, qm, strategy=strategy, backend=backend)(x)  # fused path
    assert set(ref) == set(got), f"output sets differ: {set(ref)} vs {set(got)}"
    max_diff = 0
    exact = True
    for k in ref:
        r, o = np.asarray(ref[k]), np.asarray(got[k])
        if r.dtype != o.dtype or not np.array_equal(r, o):
            exact = False
            if r.shape == o.shape:
                max_diff = max(max_diff,
                               int(np.max(np.abs(r.astype(np.int64) - o.astype(np.int64)))))
            else:
                max_diff = -1
    sqnr = {}
    if float_params is not None:
        fl = build_float_fn(g, float_params)(x.astype(np.float32))
        for k in ref:
            f = np.asarray(fl[k], np.float64)
            q = np.asarray(ref[k], np.float64)
            if np.issubdtype(np.asarray(ref[k]).dtype, np.integer):
                q = q * 2.0 ** -qm.f_a[k]
            p_sig = float(np.mean(f ** 2)) or 1e-12
            p_err = float(np.mean((f - q) ** 2)) or 1e-12
            sqnr[k] = 10.0 * np.log10(p_sig / p_err)
    return ValidationReport(exact, len(ref), max_diff, sqnr)


def artifact_round_trip(g: XGraph, qm: QuantizedModel, x: np.ndarray,
                        strategy, dev, path: str,
                        backend: str = "ref") -> ValidationReport:
    """Memory-plan analogue of :func:`bit_exact`: compile ``strategy`` to a
    DNNVM object file, save -> load, execute the *loaded* artifact on its
    *rebuilt* graph, and require bit-identity with the in-memory plan's
    execution (which itself must match the unfused oracle).  A single
    differing int8 value anywhere fails the round trip."""
    from repro.asm import (compile_strategy, graph_signature, load_artifact,
                           save_artifact)

    art = compile_strategy(g, strategy, dev, qm=qm)
    save_artifact(art, path)
    loaded = load_artifact(path)
    # re-sign the *reconstructed* graph: catches any attr the npz round trip
    # dropped or mangled, not just a corrupted stored string
    assert graph_signature(loaded.rebuild_graph()) == art.graph_sig, \
        "graph signature drifted through the artifact round trip"

    mem = Int8Executor(g, qm, strategy=art, backend=backend)(x)
    got = Int8Executor(loaded.rebuild_graph(), loaded.quantized_model(),
                       strategy=loaded, backend=backend)(x)
    ref = Int8Executor(g, qm, strategy=None, backend="ref")(x)
    assert set(ref) == set(got) == set(mem), "output sets differ"
    max_diff, exact = 0, True
    for k in ref:
        r = np.asarray(ref[k])
        for o in (np.asarray(mem[k]), np.asarray(got[k])):
            if r.dtype != o.dtype or not np.array_equal(r, o):
                exact = False
                if r.shape == o.shape:
                    max_diff = max(max_diff, int(np.max(np.abs(
                        r.astype(np.int64) - o.astype(np.int64)))))
                else:
                    max_diff = -1
    return ValidationReport(exact, len(ref), max_diff, {})
