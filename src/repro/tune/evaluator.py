"""Profile-guided cost evaluation: features + the CalibratedEvaluator.

Two feature domains turn a candidate group into the work-unit vector a
:class:`~repro.tune.profile.DeviceProfile` prices (order =
``profile.COEF_NAMES``):

* ``"analytic"`` — the analytic pipeline model's own stage quantities from
  the tiling solution (DRAM bytes, padded MACs, pool/misc elements, spatial
  tiles).  This is the domain calibration uses when the ground truth *is* the
  modeled accelerator (e.g. fitting against the cycle simulator).
* ``"kernel"``  — the work the lowered Pallas launch actually performs,
  derived from ``core.lower`` descriptors + ``chain_geometry``: per-grid-cell
  block bytes, conv MACs *including the recompute of upstream full-channel
  stages once per final-OC tile*, and the grid-cell count (interpret-mode
  dispatch overhead is per cell).  This is the domain for wall-clock
  calibration of the XLA/Pallas backend, where the abstract tiling's traffic
  numbers do not describe what runs.

:class:`CalibratedEvaluator` prices groups with a fitted profile and is a
drop-in for ``AnalyticEvaluator`` inside ``pathsearch.search(evaluator=...)``:
same call protocol (``__call__`` + ``horizontal_cost``), same INFEASIBLE
semantics (fusion condition 1 still comes from the tiling solver — a profile
never makes an unplaceable group placeable).
"""
from __future__ import annotations

import math

import numpy as np

from repro.core import lower, tiling
from repro.core.cost import INFEASIBLE, AnalyticEvaluator
from repro.core.xgraph import XGraph
from repro.hw import DeviceModel
from repro.tune.profile import COEF_NAMES, DeviceProfile

(_RD, _WR, _CONV, _POOL, _MISC,
 _CONV_STEPS, _POOL_STEPS, _MISC_STEPS, _CELLS, _LAUNCH) = range(len(COEF_NAMES))
_STAGE_IDX = (_RD, _WR, _CONV, _POOL, _MISC)
_OVERHEAD_IDX = (_CONV_STEPS, _POOL_STEPS, _MISC_STEPS, _CELLS, _LAUNCH)


# ------------------------------------------------------------------ features
def _analytic_vec(t: tiling.GroupTiling, dev: DeviceModel):
    f = np.zeros(len(COEF_NAMES))
    f[_RD] = t.load_bytes + t.weight_bytes
    f[_WR] = t.save_bytes
    f[_CONV] = t.conv_cycles * dev.macs_per_cycle_eff
    f[_POOL] = t.pool_cycles * dev.pool_elems_per_cycle
    f[_MISC] = t.misc_cycles * dev.misc_elems_per_cycle
    f[_CELLS] = t.n_spatial_tiles * max(1, t.n_oc_passes)
    f[_LAUNCH] = 1.0
    return f, max(1, t.n_spatial_tiles)


def _chain_vec(g: XGraph, launch: lower.FusedLaunch):
    """Work one chain launch performs, from the same static geometry the
    kernel itself uses (``chain_geometry``), honoring the launch's searched
    tile shape when one is set (``ops._resolve_tile`` is the single source of
    truth for what the kernel will actually run)."""
    from repro.kernels.conv_fused.conv_fused import chain_geometry
    from repro.kernels.conv_fused.ops import _resolve_tile

    stages = launch.stages
    names = [st[1] for st in stages]
    oh, ow = launch.out_hw
    conv_pos = [i for i, st in enumerate(stages) if st[0] == "conv"]
    last_conv = conv_pos[-1] if conv_pos else -1
    oc = (g.shape(names[last_conv])[3] if conv_pos
          else g.shape(launch.in_name)[3])
    th, tw, toc = _resolve_tile(tuple(launch.tile), oh, ow, oc,
                                len(conv_pos))
    geom = chain_geometry(stages, th, oh, ow, tw)
    n = max(1, g.shape(names[-1])[0])

    in_shape = g.shape(launch.in_name)
    ic_in = (in_shape[1] * in_shape[2] * in_shape[3] if launch.fc_reshape
             else in_shape[3])

    row_cells = n * geom["n_h"] * geom["n_w"]
    oc_cells = max(1, oc // toc)

    def out_depth(i: int) -> int:
        full = g.shape(names[i])[3]
        return min(full, toc) if (last_conv >= 0 and i >= last_conv) else full

    def mult(i: int) -> int:
        """How many grid cells actually execute stage ``i``.  Stages strictly
        upstream of the final conv are invariant along the OC-tile grid axis
        (same x block, full weight panel), and XLA hoists loop-invariant work
        out of the interpret-mode grid loop — measured chains confirm the
        upstream stage is NOT re-executed per OC tile."""
        return row_cells * (oc_cells if i >= last_conv else 1)

    f = np.zeros(len(COEF_NAMES))
    # rd = ACTIVATION staging only: the padded image (and eltwise sides) is
    # sliced/masked per executing grid step.  Weight panels are deliberately
    # NOT here — they are grid-invariant, converted once per launch, and
    # priced inside conv_steps; folding them into rd couples the per-cell
    # staging rate to multi-MB panels and wrecks the fit for cheap launches.
    rd = geom["in_rows"] * geom["in_cols"] * ic_in * row_cells
    wr = th * tw * out_depth(len(stages) - 1) * row_cells * oc_cells
    conv = pool = misc = 0.0
    conv_steps = pool_steps = misc_steps = 0.0
    prev_depth = ic_in
    si = 0
    for i, st in enumerate(stages):
        out_r, out_c = geom["rows"][i], geom["cols"][i]
        depth = out_depth(i)
        if st[0] == "conv":
            kh, kw = st[2], st[3]
            m_pos = out_r * out_c
            full_oc = g.shape(names[i])[3]
            conv += m_pos * prev_depth * kh * kw * depth * mult(i)
            # per-tap patch-matmul operand traffic: the x-dependent operands
            # (M*K in, M*N out) stream per executing cell, while the weight
            # panel (K*N_full) is grid-invariant and converts once per launch
            conv_steps += (kh * kw * (m_pos * prev_depth + m_pos * depth)
                           * mult(i) + kh * kw * prev_depth * full_oc)
        elif st[0] == "pool":
            kph, kpw = st[3], st[4]
            pool += out_r * out_c * kph * kpw * depth * mult(i)
            pool_steps += (1 if st[2] == "gap" else kph * kpw) * mult(i)
        else:                                          # eltwise
            sg = geom["sides"][si]
            rd += sg["h_req"] * sg["w_req"] * depth * mult(i)
            misc += out_r * out_c * depth * mult(i)
            misc_steps += mult(i)
            si += 1
        prev_depth = depth
    f[_RD] = rd
    f[_WR] = wr
    f[_CONV] = conv
    f[_POOL] = pool
    f[_MISC] = misc
    f[_CONV_STEPS] = conv_steps
    f[_POOL_STEPS] = pool_steps
    f[_MISC_STEPS] = misc_steps
    f[_CELLS] = row_cells * oc_cells
    f[_LAUNCH] = 1.0
    return f


def _horizontal_vec(g: XGraph, launch: lower.FusedLaunch):
    from repro.kernels.conv_fused.ops import _resolve_tile

    oh, ow = launch.out_hw
    kh, kw = launch.kernel
    sh, sw = launch.stride
    oc = sum(oc_m for _, oc_m, _, _ in launch.members)
    ic = g.shape(launch.in_name)[3]
    n = max(1, g.shape(launch.members[0][0])[0])
    th, tw, toc = _resolve_tile(tuple(launch.tile), oh, ow, oc, 1)
    n_h = -(-oh // th)
    n_w = -(-ow // tw)
    cells = n * n_h * n_w * max(1, oc // toc)
    hp = (th - 1) * sh + kh          # per-cell staged input extents
    wp = (tw - 1) * sw + kw
    f = np.zeros(len(COEF_NAMES))
    f[_RD] = hp * wp * ic * cells          # activation staging (see _chain_vec)
    f[_WR] = th * tw * toc * cells
    f[_CONV] = th * tw * ic * kh * kw * toc * cells
    f[_CONV_STEPS] = (kh * kw * (th * tw * ic + th * tw * toc) * cells
                      + kh * kw * ic * oc)
    f[_CELLS] = cells
    f[_LAUNCH] = 1.0
    return f


def group_features(g: XGraph, dev: DeviceModel, group: list, *,
                   domain: str = "kernel",
                   analytic: AnalyticEvaluator | None = None):
    """Feature vector + fill divisor for one chain group, or ``None`` when the
    group is infeasible on ``dev`` (tiling condition 1)."""
    analytic = analytic or AnalyticEvaluator(g, dev)
    gc = analytic.cost(group)
    if not gc.feasible:
        return None
    t = gc.tiling
    fa, n_fill = _analytic_vec(t, dev)
    if domain == "analytic":
        return fa, n_fill
    item = lower.lower_group(g, None, list(group))
    if isinstance(item, lower.FusedLaunch):
        return _chain_vec(g, item), n_fill
    # ref fallback executes the per-node jnp path: analytic work quantities,
    # one launch, per-node op dispatch
    fa[_CELLS] = len(group)
    fa[_MISC_STEPS] = len(group)
    return fa, n_fill


def horizontal_features(g: XGraph, dev: DeviceModel, heads: list, *,
                        domain: str = "kernel"):
    t = tiling.solve_horizontal(g, heads, dev)
    if not t.feasible:
        return None
    fa, n_fill = _analytic_vec(t, dev)
    if domain == "analytic":
        return [(fa, n_fill)]
    out = []
    for item in lower.lower_horizontal(g, None, list(heads)):
        if isinstance(item, lower.FusedLaunch) and item.kind == "horizontal":
            out.append((_horizontal_vec(g, item), n_fill))
        elif isinstance(item, lower.FusedLaunch):
            out.append((_chain_vec(g, item), n_fill))
        else:
            part = group_features(g, dev, list(item.nodes), domain=domain)
            if part is None:
                return None
            out.append(part)
    return out


# ----------------------------------------------------------------- evaluator
def predict_seconds(profile: DeviceProfile, f, n_fill: int) -> float:
    """Price one feature vector under a fitted profile.  Dispatch overheads
    (steps / cells / launch) are additive in both forms — they are serial
    issue cost, never hidden by the engine pipeline."""
    c = np.asarray(profile.coef)
    f = np.asarray(f)
    stage = c[list(_STAGE_IDX)] * f[list(_STAGE_IDX)]
    fixed = float((c[list(_OVERHEAD_IDX)] * f[list(_OVERHEAD_IDX)]).sum())
    if profile.combine == "sum":
        return float(stage.sum() + fixed)
    steady = float(stage.max())
    return float(steady + (stage.sum() - steady) / max(1, n_fill) + fixed)


def predict_item_seconds(profile: DeviceProfile, g: XGraph, dev: DeviceModel,
                         item) -> float | None:
    """Predicted seconds for one lowered ``GroupProgram`` item under a fitted
    profile, or ``None`` when the item has no finite prediction (host-op
    fallbacks, infeasible tilings, layout-pruned concats).

    Unlike :meth:`CalibratedEvaluator.__call__`, which prices a *candidate
    group* by re-lowering it with default tiles, this prices the item the
    artifact actually carries — honoring its searched ``tile`` — so the drift
    profiler compares measurement against the same prediction the plan was
    built on."""
    if isinstance(item, lower.RefFallback):
        if all(g.nodes[nm].op == "concat" and g.nodes[nm].attrs.get("folded")
               for nm in item.nodes):
            return None                      # pruned at emit; nothing runs
        got = group_features(g, dev, list(item.nodes),
                             domain=profile.features)
        return None if got is None else predict_seconds(profile, *got)
    if item.kind == "horizontal":
        heads = [m[0] for m in item.members]
        t = tiling.solve_horizontal(g, heads, dev)
        if not t.feasible:
            return None
        fa, n_fill = _analytic_vec(t, dev)
        f = _horizontal_vec(g, item) if profile.features == "kernel" else fa
        return predict_seconds(profile, f, n_fill)
    gc = AnalyticEvaluator(g, dev).cost(list(item.nodes))
    if not gc.feasible:
        return None
    fa, n_fill = _analytic_vec(gc.tiling, dev)
    f = _chain_vec(g, item) if profile.features == "kernel" else fa
    return predict_seconds(profile, f, n_fill)


class CalibratedEvaluator:
    """Group cost = profile-priced measured-world work (drop-in for
    ``AnalyticEvaluator`` inside ``pathsearch.search``)."""

    def __init__(self, g: XGraph, dev: DeviceModel, profile: DeviceProfile):
        self.g, self.dev, self.profile = g, dev, profile
        self._analytic = AnalyticEvaluator(g, dev)
        self._cache: dict[tuple, float] = {}

    def __call__(self, group: list) -> float:
        key = ("c", tuple(group))
        if key in self._cache:
            return self._cache[key]
        if all(self.g.nodes[nm].op == "concat" and
               self.g.nodes[nm].attrs.get("folded") for nm in group):
            cost = 0.0                      # layout-pruned, like the analytic
        else:
            got = group_features(self.g, self.dev, group,
                                 domain=self.profile.features,
                                 analytic=self._analytic)
            cost = (INFEASIBLE if got is None
                    else predict_seconds(self.profile, *got))
        self._cache[key] = cost
        return cost

    def horizontal_cost(self, heads: list) -> float:
        key = ("h", tuple(heads))
        if key in self._cache:
            return self._cache[key]
        got = horizontal_features(self.g, self.dev, heads,
                                  domain=self.profile.features)
        cost = (INFEASIBLE if got is None else
                sum(predict_seconds(self.profile, f, n) for f, n in got))
        self._cache[key] = cost
        return cost

    def strategy_cost(self, strategy) -> float:
        """Predicted end-to-end seconds of a whole strategy (sum of groups)."""
        total = sum(self(list(grp)) for grp in strategy.groups)
        total += sum(self.horizontal_cost(list(h)) for h in strategy.horizontal)
        return total if math.isfinite(total) else INFEASIBLE

    # ------------------------------------------------------------ tile shapes
    def tile_for(self, group: list) -> tuple | None:
        """Profile-predicted best kernel tile shape for ``group``, or ``None``
        when the kernel-default heuristics win.  ``pathsearch.search`` calls
        this on every searched group, so strategies picked under a calibrated
        profile carry predicted shapes even before anything is measured.
        Only meaningful in the "kernel" feature domain — an "analytic"
        profile prices the abstract tiling, not what the launch executes."""
        if self.profile.features != "kernel":
            return None
        key = ("tile", tuple(group))
        if key in self._cache:
            return self._cache[key]
        from repro.tune import tiles
        item = lower.lower_group(self.g, None, list(group))
        shape = None
        if isinstance(item, lower.FusedLaunch):
            shape = tiles.predict_best_shape(self.profile, self.g, self.dev,
                                             item)
        self._cache[key] = shape
        return shape

    def tile_for_horizontal(self, heads: list) -> dict:
        """Predicted shapes for a horizontal group's lowered launches, keyed
        by ``lower.tile_key`` of each launch's node cover ({} = defaults)."""
        if self.profile.features != "kernel":
            return {}
        key = ("tile-h", tuple(heads))
        if key in self._cache:
            return self._cache[key]
        from repro.tune import tiles
        out = {}
        for item in lower.lower_horizontal(self.g, None, list(heads)):
            if isinstance(item, lower.FusedLaunch):
                shape = tiles.predict_best_shape(self.profile, self.g,
                                                 self.dev, item)
                if shape:
                    out[lower.tile_key(item.nodes)] = shape
        self._cache[key] = out
        return out
