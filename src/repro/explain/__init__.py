"""Compile-decision provenance: explainable reports and plan diffs.

DNNVM's value proposition is *search* — fusion templates enumerated by
subgraph isomorphism, strategies picked by shortest path, tile shapes picked
by measured search, DDR regions packed by liveness — and this package makes
every one of those decisions inspectable after the fact:

- :func:`build_report` assembles the structured ``CompileReport`` at the
  ``Compiled`` stage (called by ``asm.assemble_artifact``; embedded in every
  v5 artifact);
- :func:`report_of` returns an artifact's embedded report, or a degraded
  reconstruction for pre-v5 artifacts (never crashes on old files);
- :func:`diff` / :func:`diff_artifacts` compute the structural + cost diff of
  two plans — the audit record the continuous-autotuning hot-swap loop emits;
- :func:`render_report` / :func:`render_diff` are the deterministic text
  renderers behind ``python -m repro.explain``.

Runtime surfaces: :func:`live_report` joins an artifact's report with the
live drift samples of the deployment serving it; ``ObsHTTPServer`` serves
``/explain/<model>``; the event log carries ``explain.report`` /
``plan.diff`` events.
"""
from repro.explain.diff import diff, diff_artifacts, diff_reports, negate
from repro.explain.render import render_diff, render_report
from repro.explain.report import (REPORT_VERSION, build_report, live_report,
                                  report_of, validate_report)

__all__ = [
    "REPORT_VERSION", "build_report", "live_report", "report_of",
    "validate_report",
    "diff", "diff_artifacts", "diff_reports", "negate",
    "render_report", "render_diff",
]
