"""Tile-shape search: make (T_h, T_w, T_oc) a searched compilation decision.

The paper pins T_h/T_oc to the array parallelism and maximizes T_w (Eq. 5/6);
PR 4 calibrated the cost model but still searched only *group partitioning* —
the kernel executed one hard-coded tile heuristic regardless.  This module
closes the ROADMAP's "autotuned tiling" follow-up: for every lowered
``FusedLaunch`` it enumerates the kernel-executable tile shapes that are
feasible under the device's Eq. 6 capacity (:func:`tiling.enumerate_tilings`
— the Pareto frontier over traffic / grid cells / footprint), ranks them
with the fitted :class:`~repro.tune.profile.DeviceProfile` (kernel feature
domain: a tile shape changes the grid-cell count, per-cell staging and
per-tap operand traffic the profile prices), measures the top-K candidates
through the :class:`~repro.tune.measure.MeasurementHarness` (round-robin
passes, MAD rejection — a tile candidate is just another measurable unit),
and records the winner in ``strategy.meta['tile_shapes']``.

From there the shape is a first-class artifact citizen: ``core.lower`` stamps
it onto the launch (``FusedLaunch.tile``), the kernel grids over it, the
memory planner charges its true ping/pong footprints, and the compiled
artifact (format v4) round-trips it.  Groups that are never measured still
get profile-predicted shapes for free through
``CalibratedEvaluator.tile_for`` inside ``pathsearch.search``.
"""
from __future__ import annotations

import dataclasses
import math

from repro.core import lower, tiling
from repro.core.xgraph import XGraph
from repro.hw import DeviceModel
from repro.tune.evaluator import _chain_vec, _horizontal_vec, predict_seconds
from repro.tune.profile import DeviceProfile

# A tuned shape must beat the kernel default by more than noise to be
# recorded: measured winners need 1%, profile-predicted winners 2% (a
# prediction is softer evidence than an A/B on the same round-robin passes).
MEASURED_MARGIN = 0.01
PREDICTED_MARGIN = 0.02


def launch_oc(g: XGraph, item: lower.FusedLaunch) -> int:
    """Output channels the launch's OC grid axis tiles."""
    if item.kind == "horizontal":
        return sum(oc for _, oc, _, _ in item.members)
    conv_pos = [i for i, st in enumerate(item.stages) if st[0] == "conv"]
    if conv_pos:
        return g.shape(item.stages[conv_pos[-1]][1])[3]
    return g.shape(item.in_name)[3]


def default_shape(g: XGraph, item: lower.FusedLaunch) -> tuple:
    """The (t_h, t_w, t_oc) the kernel heuristics run without a tile record
    (the PR-4 baseline every candidate must beat)."""
    from repro.kernels.conv_fused.ops import _resolve_tile

    oh, ow = item.out_hw
    return _resolve_tile((), oh, ow, launch_oc(g, item), _n_conv(item))


def _n_conv(item: lower.FusedLaunch) -> int:
    if item.kind == "horizontal":
        return 1
    return sum(1 for st in item.stages if st[0] == "conv")


def _legal_shapes(g: XGraph, item: lower.FusedLaunch, shapes) -> list:
    """Candidates the chip's block rule admits (``ops._resolve_tile``): T_w
    rounded to a multiple of 8 or the full width, T_oc a 128-multiple
    divisor of OC or OC itself; duplicates after rounding dropped."""
    from repro.kernels.conv_fused.ops import _legal_toc, _legal_tw

    oc, n_conv = launch_oc(g, item), _n_conv(item)
    out, seen = [], set()
    for th, tw, toc in shapes:
        if n_conv and not _legal_toc(int(toc), oc):
            continue
        s = (int(th), _legal_tw(int(tw), item.out_hw[1]), int(toc))
        if s not in seen:
            seen.add(s)
            out.append(s)
    return out


def analytic_shape(g: XGraph, dev: DeviceModel,
                   item: lower.FusedLaunch) -> tuple | None:
    """The paper's Eq. 5/6 shape for this launch's node cover (T_h/T_oc
    pinned to the array parallelism, maximal T_w) — always part of the
    measured candidate set, so the tile search can never do worse than the
    analytic solution it generalizes.  None when it is infeasible or its
    T_oc breaks the chip's block rule."""
    t = (tiling.solve_horizontal(g, list(item.nodes), dev)
         if item.kind == "horizontal"
         else tiling.solve(g, list(item.nodes), dev))
    legal = _legal_shapes(g, item, [(t.t_h, t.t_w, t.t_oc)]) \
        if t.feasible else []
    return legal[0] if legal else None


def shape_candidates(g: XGraph, dev: DeviceModel, item: lower.FusedLaunch,
                     max_candidates: int = 16) -> list:
    """Kernel-executable (t_h, t_w, t_oc) candidates for one lowered launch,
    every one feasible under ``dev``'s Eq. 6 capacity — so a chosen shape is
    guaranteed to compile (the bank planner charges its true footprints)."""
    if item.kind == "horizontal":
        oh, _ = item.out_hw
        oc = launch_oc(g, item)
        shapes, seen = [], set()
        for th in tiling._shape_candidates_1d(dev.h_p, oh):
            for toc in tiling._shape_candidates_1d(dev.oc_p, oc):
                if oc % toc:
                    continue        # the OC grid axis cannot run ragged
                t = tiling.solve_horizontal(g, list(item.nodes), dev,
                                            t_h=th, t_oc=toc)
                if not t.feasible:
                    continue
                w, widths = t.t_w, {t.t_w}
                while w > 1 and len(widths) < 3:
                    w = (w + 1) // 2
                    widths.add(w)
                for w in sorted(widths, reverse=True):
                    if (th, w, toc) not in seen:
                        seen.add((th, w, toc))
                        shapes.append((th, w, toc))
        return _legal_shapes(g, item, shapes)[:max_candidates]
    cands = tiling.enumerate_tilings(g, list(item.nodes), dev,
                                     max_candidates=max_candidates)
    return _legal_shapes(g, item, [(t.t_h, t.t_w, t.t_oc) for t in cands])


def predict_shape_seconds(profile: DeviceProfile, g: XGraph,
                          item: lower.FusedLaunch, shape: tuple) -> float:
    """Price one tile candidate with the fitted profile: the launch's
    kernel-domain work vector under that shape (grid cells, per-cell staging,
    per-tap operand traffic all move with the tile)."""
    it = dataclasses.replace(item, tile=tuple(int(v) for v in shape))
    f = _horizontal_vec(g, it) if it.kind == "horizontal" else _chain_vec(g, it)
    oh, ow = item.out_hw
    th, tw, _ = shape
    n_fill = max(1, math.ceil(oh / max(1, th)) * math.ceil(ow / max(1, tw)))
    return predict_seconds(profile, f, n_fill)


def predict_best_shape(profile: DeviceProfile, g: XGraph, dev: DeviceModel,
                       item: lower.FusedLaunch,
                       margin: float = PREDICTED_MARGIN) -> tuple | None:
    """Profile-predicted best shape for one launch, or ``None`` when the
    kernel-default heuristics win (within ``margin``) — untuned groups get
    their shapes "for free" through this path."""
    cands = shape_candidates(g, dev, item)
    if not cands:
        return None
    base = predict_shape_seconds(profile, g, item, default_shape(g, item))
    best, best_s = None, base
    for s in cands:
        sec = predict_shape_seconds(profile, g, item, s)
        if sec < best_s:
            best, best_s = s, sec
    if best is None or best_s > base * (1.0 - margin):
        return None
    return tuple(int(v) for v in best)


# ------------------------------------------------------------------- search
@dataclasses.dataclass
class TileSearchReport:
    """What the tile search decided, per lowered unit."""
    tile_shapes: dict               # tile_key -> [t_h, t_w, t_oc] (winners)
    provenance: list                # per-unit candidates + timings
    n_units: int                    # launches considered
    n_tuned: int                    # launches with a non-default winner
    source: str                     # "measured" | "profile"

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def search_tile_shapes(g: XGraph, qm, dev: DeviceModel, strategy, *,
                       profile: DeviceProfile | None = None, harness=None,
                       top_k: int = 3, passes: int | None = None,
                       max_candidates: int = 16,
                       min_measurable_s: float = 5e-4) -> TileSearchReport:
    """Search per-launch tile shapes for ``strategy`` and record them in
    ``strategy.meta['tile_shapes']`` (+ ``tile_provenance`` / ``tile_source``).

    With a ``harness`` the top-K profile-ranked candidates of every lowered
    unit (plus the kernel default, always) are measured together in
    round-robin passes and the measured winner is kept; without one the
    profile-predicted best is kept.  Only shapes that beat the default by the
    evidence-appropriate margin are recorded — and only for units whose
    default wall-clock is at least ``min_measurable_s`` (the same 0.5 ms
    resolution floor calibration applies: below it a "winner" is dispatch
    jitter, not evidence).  An empty record IS the PR-4 baseline, so untuned
    programs are byte-identical to before.
    """
    if profile is None and harness is None:
        raise ValueError("search_tile_shapes needs a profile, a harness, "
                         "or both")
    from repro.obs.trace import TRACER
    with TRACER.span("tile_search", cat="compile", track="compile"):
        return _search_tile_shapes(
            g, qm, dev, strategy, profile=profile, harness=harness,
            top_k=top_k, passes=passes, max_candidates=max_candidates,
            min_measurable_s=min_measurable_s)


def _search_tile_shapes(g: XGraph, qm, dev: DeviceModel, strategy, *,
                        profile=None, harness=None, top_k: int = 3,
                        passes: int | None = None, max_candidates: int = 16,
                        min_measurable_s: float = 5e-4) -> TileSearchReport:
    prog = lower.lower_strategy(g, strategy, qm)
    units = []
    for item in prog.launches():
        cands = shape_candidates(g, dev, item, max_candidates=max_candidates)
        default = default_shape(g, item)
        ana = analytic_shape(g, dev, item)
        cands = [s for s in cands if tuple(s) != tuple(default)]
        if profile is not None:
            pred = {tuple(s): predict_shape_seconds(profile, g, item, s)
                    for s in cands}
            cands.sort(key=lambda s: pred[tuple(s)])
            pred[tuple(default)] = predict_shape_seconds(profile, g, item,
                                                         default)
        else:
            # no profile: fewest grid cells first (the dominant interpret-
            # mode cost axis) — measurement arbitrates anyway
            pred = {}
            cands.sort(key=lambda s: (math.ceil(item.out_hw[0] / s[0])
                                      * math.ceil(item.out_hw[1] / s[1])))
        top = cands[:top_k]
        # the Eq. 5/6 shape is always in the measured set: the search result
        # then can never be measured-worse than the analytic solution
        if ana is not None and tuple(ana) != tuple(default) and \
                tuple(ana) not in {tuple(s) for s in top}:
            top.append(tuple(ana))
            if profile is not None:
                pred.setdefault(tuple(ana),
                                predict_shape_seconds(profile, g, item, ana))
        units.append((item, default, top, pred))

    chosen: dict = {}
    provenance: list = []
    source = "measured" if harness is not None else "profile"
    if harness is not None:
        items, index = [], []
        for u, (item, default, top, _) in enumerate(units):
            items.append(item)                     # tile=() == the default
            index.append((u, None))
            for s in top:
                items.append(dataclasses.replace(
                    item, tile=tuple(int(v) for v in s)))
                index.append((u, tuple(s)))
        measured = harness.measure_item_set(items, passes=passes)
        by_unit: dict = {}
        for (u, s), m in zip(index, measured):
            by_unit.setdefault(u, []).append((s, m))
        for u, (item, default, top, pred) in enumerate(units):
            rows = by_unit.get(u, [])
            base = next(m for s, m in rows if s is None)
            win_s, win_m = min(rows, key=lambda r: r[1].seconds)
            keep = (win_s is not None
                    and base.seconds >= min_measurable_s
                    and win_m.seconds < base.seconds * (1 - MEASURED_MARGIN))
            if keep:
                chosen[lower.tile_key(item.nodes)] = [int(v) for v in win_s]
            provenance.append({
                "key": lower.tile_key(item.nodes),
                "nodes": list(item.nodes), "kind": item.kind,
                "default": list(default),
                "chosen": list(win_s) if keep else None,
                "source": "measured",
                "candidates": [
                    {"shape": list(s if s is not None else default),
                     "default": s is None,
                     "predicted": pred.get(s if s is not None
                                           else tuple(default)),
                     "measured": m.seconds, "spread": m.spread}
                    for s, m in rows],
            })
    else:
        for item, default, top, pred in units:
            base = pred[tuple(default)]
            win = min(top, key=lambda s: pred[tuple(s)], default=None)
            keep = (win is not None
                    and pred[tuple(win)] < base * (1 - PREDICTED_MARGIN))
            if keep:
                chosen[lower.tile_key(item.nodes)] = [int(v) for v in win]
            provenance.append({
                "key": lower.tile_key(item.nodes),
                "nodes": list(item.nodes), "kind": item.kind,
                "default": list(default),
                "chosen": list(win) if keep else None,
                "source": "profile",
                "candidates": [
                    {"shape": list(s), "default": tuple(s) == tuple(default),
                     "predicted": pred[tuple(s)], "measured": None}
                    for s in [default] + top],
            })

    report = TileSearchReport(
        tile_shapes=chosen, provenance=provenance, n_units=len(units),
        n_tuned=len(chosen), source=source)
    strategy.meta["tile_shapes"] = dict(chosen)
    strategy.meta["tile_source"] = source
    strategy.meta["tile_provenance"] = provenance
    return report


def tune_lowered(lowered, *, profile=None, harness=None, cache=None,
                 **search_kw):
    """Re-run the tile-shape search over an existing ``stages.Lowered`` and
    return a new ``Lowered`` carrying the tuned shapes.

    This is the staged pipeline's partial-recompile path: pathsearch is NOT
    re-run — the searched group partition is kept, only the per-launch tile
    shapes move.  The input stage is never mutated (its strategy is copied
    before the search writes ``meta['tile_shapes']``), so the untuned and
    tuned lowerings coexist in the stage cache under their own content
    hashes, and downstream ``plan``/``compile`` re-run only for the tuned
    branch.
    """
    import copy

    from repro.tune.profile import resolve_profile

    resolved = resolve_profile(profile) if profile is not None \
        else lowered.profile
    w = lowered.wrapped
    strat = copy.copy(lowered.strategy)
    strat.meta = dict(lowered.strategy.meta)
    search_tile_shapes(w.graph, w.qm, w.device, strat,
                       profile=resolved, harness=harness, **search_kw)
    ph = resolved.hash() if resolved is not None else lowered.profile_hash
    return w.lower(strategy=strat, profile=resolved, profile_hash=ph,
                   cache=cache)
