"""Autotuned tiling: enumerate/solve_shape, width-tiled kernels, tile-shape
serialization (artifact v4 + v3 backcompat), the tile search itself, the
stacked-launch calibration rows, and the profile-guided ddr_slots pick."""
import dataclasses
import json
import math
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import executor, int8_ops, lower, pathsearch, quantize, tiling
from repro.core.xgraph import XGraph
from repro.hw import TPU_V5E, ZU2
from repro.kernels.conv_fused.ops import interpret_mode
from tests.conftest import make_toy_resnet_graph, toy_params


def _quantized_toy():
    g = make_toy_resnet_graph()
    params = toy_params(g)
    rng = np.random.default_rng(0)
    x = rng.standard_normal(g.shape("data")).astype(np.float32)
    qm = quantize.calibrate(g, params, x, executor.run_float)
    xq = quantize.quantize_to(x, qm.f_a["data"])
    return g, qm, xq


def _kernel_profile(cell_s=1e-4, launch_s=0.0):
    """Synthetic kernel-domain profile dominated by per-cell overhead — under
    it fewer, larger tiles always predict faster (the interpret-mode truth)."""
    from repro.tune.profile import COEF_NAMES, DeviceProfile

    coef = [0.0] * len(COEF_NAMES)
    coef[COEF_NAMES.index("rd")] = 1e-12
    coef[COEF_NAMES.index("conv")] = 1e-12
    coef[COEF_NAMES.index("cells")] = cell_s
    coef[COEF_NAMES.index("launch")] = launch_s
    return DeviceProfile(name="cells", device="tpu_v5e", backend="pallas",
                         jax_version="test", features="kernel", combine="sum",
                         coef=tuple(coef), deviation=0.0, n_samples=3)


# ------------------------------------------------------- enumerate / solve
def test_solve_unchanged_and_enumerate_caps_capacity():
    g = make_toy_resnet_graph()
    t0 = tiling.solve(g, ["c1"], ZU2)
    # Eq. 5 pins: solve() keeps the paper's shape exactly
    assert (t0.t_h, t0.t_oc) == (min(ZU2.h_p, 16), min(ZU2.oc_p, 16))
    cands = tiling.enumerate_tilings(g, ["c1"], ZU2)
    assert cands, "a feasible group must enumerate at least one shape"
    for t in cands:
        assert t.feasible
        # every candidate respects the Eq. 6 capacity check of solve_shape
        again = tiling.solve_shape(g, ["c1"], ZU2, t_w=t.t_w, t_h=t.t_h,
                                   t_oc=t.t_oc)
        assert again.feasible and (again.t_w, again.t_h, again.t_oc) == \
            (t.t_w, t.t_h, t.t_oc)
        # kernel-executable OC axis
        assert 16 % t.t_oc == 0


def test_enumerate_pareto_no_dominated():
    g = make_toy_resnet_graph()
    cands = tiling.enumerate_tilings(g, ["c2b", "add1"], TPU_V5E)

    def axes(t):
        return (t.dram_bytes, tiling._cells(t),
                t.in_tile_bytes + t.out_tile_bytes + t.resident_bytes)

    for a in cands:
        for b in cands:
            if a is b:
                continue
            assert not (all(x <= y for x, y in zip(axes(b), axes(a)))
                        and any(x < y for x, y in zip(axes(b), axes(a)))), \
                f"{axes(b)} dominates {axes(a)} but both survived"


def test_solve_shape_rejects_over_capacity():
    g = XGraph()
    g.input("x", (1, 64, 64, 64))
    g.add("conv", "c", ("x",), oc=64, kernel=(3, 3), pad="same")
    t = tiling.solve_shape(g, ["c"], ZU2, t_w=64, t_h=64, t_oc=64)
    assert not t.feasible and "exceeds on-chip buffers" in t.reason


# ------------------------------------------------------ width-tiled kernels
def _conv_data(rng, h, w, ic, oc, k):
    x = jnp.asarray(rng.integers(-128, 128, (1, h, w, ic)).astype(np.int8))
    wt = jnp.asarray(rng.integers(-128, 128, (k, k, ic, oc)).astype(np.int8))
    b = jnp.asarray(rng.integers(-2000, 2000, oc).astype(np.int32))
    return x, wt, b


def _grid_split(tile, oh, ow, oc, n_conv=1) -> tuple:
    """(width tiles, OC tiles) the launch runs ``tile`` with; the shape must
    already be legal for the chip, so the launcher runs it unchanged."""
    from repro.kernels.conv_fused.ops import _resolve_tile

    th, tw, toc = _resolve_tile(tile, oh, ow, oc, n_conv)
    assert (th, tw, toc) == tuple(tile), "tile was legalized"
    return -(-ow // tw), oc // toc


@pytest.mark.parametrize("h,k,s,d,oc,tile", [
    (19, 3, 1, 1, 256, (4, 8, 128)),  # ragged right edge (19 % 8 != 0)
    (34, 3, 2, 1, 8, (3, 8, 8)),      # stride-2 halo between width tiles
    (20, 3, 1, 2, 8, (5, 8, 8)),      # dilated halo
    (35, 5, 2, 1, 8, (4, 8, 8)),      # 5x5 stride-2, everything ragged
])
def test_width_tiled_conv_bit_exact(h, k, s, d, oc, tile):
    from repro.kernels.conv_fused.ops import _run_chain

    rng = np.random.default_rng(h * k + s)
    x, wt, b = _conv_data(rng, h, h, 4, oc, k)
    p = d * (k - 1) // 2
    oh = (h + 2 * p - (d * (k - 1) + 1)) // s + 1
    n_w, n_oc = _grid_split(tile, oh, oh, oc)
    assert n_w > 1 and (n_oc > 1 or oc == tile[2])
    want = int8_ops.conv2d(x, wt, b, stride=(s, s), pad=(p, p),
                           dilation=(d, d), shift=6, relu=True)
    chain = (("conv", "c", k, k, s, s, p, p, d, d, 6, True, oh, oh),)
    got = _run_chain(x, (wt,), (b,), (), chain=chain, oh=oh, ow=oh, oc=oc,
                     interpret=interpret_mode(), tile=tile)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_width_tiled_pool_tail_chain_bit_exact():
    """conv -> ceil-mode maxpool across width and OC tiles: the padded-
    coordinate masking must hold at interior tile boundaries, not just the
    right edge, and the pool rides the final conv's OC slice."""
    from repro.kernels.conv_fused.ops import _run_chain
    from repro.kernels.conv_fused.ref import fused_conv_ref

    rng = np.random.default_rng(5)
    x, wt, b = _conv_data(rng, 33, 33, 4, 256, 3)
    y_c = fused_conv_ref(x, wt, b, stride=(1, 1), pad=(1, 1), shift=6,
                         relu=True)
    for kp, sp, pp in [(3, 2, 0), (3, 2, 1), (2, 2, 1)]:
        want = int8_ops.maxpool(y_c, kernel=(kp, kp), stride=(sp, sp),
                                pad=(pp, pp), ceil_mode=True)
        oh = math.ceil((33 + 2 * pp - kp) / sp) + 1     # 16, 17, 18
        chain = (("conv", "c", 3, 3, 1, 1, 1, 1, 1, 1, 6, True, 33, 33),
                 ("pool", "p", "max", kp, kp, sp, sp, pp, pp, oh, oh, kp * kp))
        for tile, split in [((2, 8, 128), (True, True)),
                            ((3, 8, 256), (True, False)),
                            ((oh, oh, 256), (False, False))]:
            n_w, n_oc = _grid_split(tile, oh, oh, 256)
            assert (n_w > 1, n_oc > 1) == split
            got = _run_chain(x, (wt,), (b,), (), chain=chain, oh=oh, ow=oh,
                             oc=256, interpret=interpret_mode(), tile=tile)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_width_tiled_eltwise_chain_bit_exact():
    """conv -> eltwise_add: the side input rides the same width tiling and
    the final conv's OC slice."""
    from repro.kernels.conv_fused.ops import _run_chain

    rng = np.random.default_rng(7)
    x, wt, b = _conv_data(rng, 20, 20, 4, 256, 3)
    side = jnp.asarray(rng.integers(-128, 128, (1, 20, 20, 256))
                       .astype(np.int8))
    y_c = int8_ops.conv2d(x, wt, b, stride=(1, 1), pad=(1, 1), shift=6)
    want = int8_ops.eltwise_add([y_c, side], [1, 2], 0, relu=True)
    chain = (("conv", "c", 3, 3, 1, 1, 1, 1, 1, 1, 6, False, 20, 20),
             ("elt", "e", 1, 2, True, 20, 20))
    for tile in [(4, 8, 128), (3, 8, 256), (7, 16, 128)]:
        n_w, n_oc = _grid_split(tile, 20, 20, 256)
        assert n_w > 1 and n_oc == 256 // tile[2]
        got = _run_chain(x, (wt,), (b,), (side,), chain=chain, oh=20, ow=20,
                         oc=256, interpret=interpret_mode(), tile=tile)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_width_tiled_horizontal_bit_exact():
    """Two stacked siblings (64 + 192 OC): a 128-lane OC tile straddles the
    member boundary, so the per-channel shift/ReLU rows must follow it."""
    from repro.kernels.conv_fused.ops import _run_horizontal

    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.integers(-128, 128, (1, 19, 19, 4)).astype(np.int8))
    wa = jnp.asarray(rng.integers(-128, 128, (3, 3, 4, 64)).astype(np.int8))
    wb = jnp.asarray(rng.integers(-128, 128, (3, 3, 4, 192)).astype(np.int8))
    ba = jnp.asarray(rng.integers(-2000, 2000, 64).astype(np.int32))
    bb = jnp.asarray(rng.integers(-2000, 2000, 192).astype(np.int32))
    ya = int8_ops.conv2d(x, wa, ba, stride=(1, 1), pad=(1, 1), shift=5,
                         relu=True)
    yb = int8_ops.conv2d(x, wb, bb, stride=(1, 1), pad=(1, 1), shift=7)
    for tile, split in [((3, 8, 128), (True, True)),     # 19 % 8 != 0
                        ((4, 16, 256), (True, False)),
                        ((19, 19, 128), (False, True))]:
        n_w, n_oc = _grid_split(tile, 19, 19, 256)
        assert (n_w > 1, n_oc > 1) == split
        y = _run_horizontal(
            x, jnp.concatenate([wa, wb], axis=-1), jnp.concatenate([ba, bb]),
            jnp.asarray(np.repeat([5, 7], [64, 192]).astype(np.int32)),
            jnp.asarray(np.repeat([1, 0], [64, 192]).astype(np.int32)),
            stride=(1, 1), pad=(1, 1), oh=19, ow=19, interpret=interpret_mode(),
            tile=tile)
        np.testing.assert_array_equal(np.asarray(y[..., :64]), np.asarray(ya))
        np.testing.assert_array_equal(np.asarray(y[..., 64:]), np.asarray(yb))


# ----------------------------------------------------- lowering + execution
def test_lower_strategy_applies_tile_map_and_stays_bit_exact():
    g, qm, xq = _quantized_toy()
    s = pathsearch.search(g, TPU_V5E)
    s.meta["tile_shapes"] = {
        lower.tile_key(grp): [16, 7, int(g.shape(grp[-1])[3])]
        for grp in s.groups
        if isinstance(lower.lower_group(g, qm, list(grp)), lower.FusedLaunch)
        and g.shape(grp[-1])[3] > 1}
    assert s.meta["tile_shapes"], "toy strategy must have tunable launches"
    prog = lower.lower_strategy(g, s, qm)
    tiled = [it for it in prog.launches() if it.tile]
    assert len(tiled) == len(s.meta["tile_shapes"])
    assert prog.meta["n_tiled_launches"] == len(tiled)
    ref = executor.Int8Executor(g, qm, strategy=s, backend="ref")(xq)
    got = executor.Int8Executor(g, qm, strategy=s, backend="pallas")(xq)
    for k in ref:
        np.testing.assert_array_equal(ref[k], got[k])


def test_artifact_v4_tile_round_trip(tmp_path):
    from repro import asm

    g, qm, xq = _quantized_toy()
    s = pathsearch.search(g, TPU_V5E)
    s.meta["tile_shapes"] = {lower.tile_key(s.groups[0]):
                             [16, 8, int(g.shape(s.groups[0][-1])[3])]}
    art = asm.compile_strategy(g, s, TPU_V5E, qm=qm)
    assert art.tile_shapes == s.meta["tile_shapes"]
    p = os.path.join(tmp_path, "a.npz")
    asm.save_artifact(art, p)
    art2 = asm.load_artifact(p)
    assert art2.tile_shapes == art.tile_shapes
    got = {it.nodes: it.tile for it in art2.program.launches() if it.tile}
    assert got == {tuple(s.groups[0]):
                   tuple(s.meta["tile_shapes"][lower.tile_key(s.groups[0])])}
    # the loaded artifact re-keys identically (tile shapes are identity)
    assert asm.strategy_signature(art2) == asm.strategy_signature(s)


def test_artifact_v3_backward_compat(tmp_path):
    """A v3 artifact (no tile records) must still load — missing tiles mean
    the kernel-heuristic shapes, exactly what v3 executed."""
    from repro import asm

    g, qm, xq = _quantized_toy()
    s = pathsearch.search(g, TPU_V5E)
    art = asm.compile_strategy(g, s, TPU_V5E, qm=qm)
    p = os.path.join(tmp_path, "v4.npz")
    asm.save_artifact(art, p)
    # rewrite as a v3 object file: drop every v4-only field
    with np.load(p, allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files}
    meta = json.loads(str(arrays["meta_json"]))
    meta["format_version"] = 3
    meta["meta"].pop("tile_shapes", None)
    meta["meta"].pop("tile_source", None)
    for item in meta["program"]["items"]:
        item.pop("tile", None)
    meta["program"]["meta"].pop("n_tiled_launches", None)
    arrays["meta_json"] = np.asarray(json.dumps(meta))
    p3 = os.path.join(tmp_path, "v3.npz")
    with open(p3, "wb") as f:
        np.savez_compressed(f, **arrays)
    art3 = asm.load_artifact(p3)
    assert art3.tile_shapes == {}
    assert all(it.tile == () for it in art3.program.launches())
    from repro.runtime import Session
    out = Session.from_artifact(art3, backend="pallas").run(xq)
    ref = executor.Int8Executor(g, qm, strategy=s, backend="ref")(xq)
    for k in ref:
        np.testing.assert_array_equal(ref[k], out[k])


def test_plan_cache_distinguishes_tile_shapes():
    from repro import asm

    g, qm, _ = _quantized_toy()
    s = pathsearch.search(g, TPU_V5E)
    sig0 = asm.strategy_signature(s)
    s.meta["tile_shapes"] = {lower.tile_key(s.groups[0]): [16, 8, 16]}
    assert asm.strategy_signature(s) != sig0, \
        "same partition + different tiles must not collide in the plan cache"


# ------------------------------------------------------------- tile search
def test_profile_predicted_tiles_recorded_by_search():
    from repro.tune import CalibratedEvaluator

    g, qm, xq = _quantized_toy()
    profile = _kernel_profile()
    ev = CalibratedEvaluator(g, TPU_V5E, profile)
    s = pathsearch.search(g, TPU_V5E, evaluator=ev)
    # under a per-cell-dominated profile, bigger tiles always predict faster
    # than the row/oc heuristics, so the search must record shapes
    assert s.meta.get("tile_shapes"), "profile-guided search must record tiles"
    assert s.meta["tile_source"] == "profile"
    # and the program they produce still matches the oracle bit for bit
    ref = executor.Int8Executor(g, qm, strategy=s, backend="ref")(xq)
    got = executor.Int8Executor(g, qm, strategy=s, backend="pallas")(xq)
    for k in ref:
        np.testing.assert_array_equal(ref[k], got[k])


def test_search_tile_shapes_measured_winner():
    from repro.tune import MeasurementHarness, search_tile_shapes

    g, qm, xq = _quantized_toy()
    s = pathsearch.search(g, TPU_V5E)
    h = MeasurementHarness(g, qm, TPU_V5E, repeats=3)
    rep = search_tile_shapes(g, qm, TPU_V5E, s, harness=h, top_k=2)
    assert rep.n_units >= 4
    assert rep.source == "measured"
    assert s.meta.get("tile_provenance")
    for unit in rep.provenance:
        default = next(c for c in unit["candidates"] if c["default"])
        if unit["chosen"] is not None:
            win = min(unit["candidates"], key=lambda c: c["measured"])
            assert win["measured"] <= default["measured"]
    # chosen shapes compile hazard-free and stay bit-exact
    from repro import asm
    art = asm.compile_strategy(g, s, TPU_V5E, qm=qm)
    ref = executor.Int8Executor(g, qm, strategy=s, backend="ref")(xq)
    got = executor.Int8Executor(g, qm, strategy=art, backend="pallas")(xq)
    for k in ref:
        np.testing.assert_array_equal(ref[k], got[k])


# ------------------------------------------------- stacked calibration rows
def _quantized_fork():
    """Tiny inception-style fork with two STACKABLE siblings (same 3x3
    class), so lower_horizontal emits one OC-stacked launch."""
    from repro.core import frontend

    g = XGraph("fork")
    g.input("data", (1, 16, 16, 8))
    g.add("conv", "c0", ("data",), oc=8, kernel=(3, 3), pad="same")
    g.add("conv", "ba", ("c0",), oc=16, kernel=(3, 3), pad="same", relu=True)
    g.add("conv", "bb", ("c0",), oc=8, kernel=(3, 3), pad="same")
    g.add("concat", "cat", ("ba", "bb"))
    frontend.lower(g)
    from repro.cnn import init_params
    params = init_params(g)
    rng = np.random.default_rng(1)
    x = rng.standard_normal(g.shape("data")).astype(np.float32)
    qm = quantize.calibrate(g, params, x, executor.run_float)
    return g, qm


def test_default_horizontal_candidates_compatibility():
    from repro.tune.calibrate import default_horizontal_candidates

    g, _ = _quantized_fork()
    assert ["ba", "bb"] in default_horizontal_candidates(g)
    # the toy resnet fork (3x3 vs 1x1 siblings) is NOT stackable
    assert default_horizontal_candidates(make_toy_resnet_graph()) == []


def test_calibrate_measures_stacked_launches_directly():
    from repro.tune import calibrate

    g, qm = _quantized_fork()
    res = calibrate(g, qm, ZU2, repeats=2, warmup=1, min_measurable_s=0.0)
    stk = res.report["stacked"]
    assert stk["n_samples"] >= 1
    assert stk["deviation"] is not None and np.isfinite(stk["deviation"])
    stacked_rows = [m for m in res.measurements if len(m.nodes) > 1
                    and m.kind == "horizontal"]
    assert stacked_rows, "stacked measurement must enter the fit set"


def test_calibrate_injected_ground_truth_skips_stacked():
    """Simulator-ground-truth calibration (injected measure_fn) measures
    chain groups only — the stacked section must not break it."""
    from repro.core.cost import SimulatorEvaluator
    from repro.tune import calibrate

    g, qm, _ = _quantized_toy()
    sim = SimulatorEvaluator(g, ZU2)
    res = calibrate(g, qm, ZU2, measure_fn=lambda grp: sim(grp),
                    features="analytic")
    assert res.report["stacked"]["n_samples"] == 0
    assert res.report["deviation"] < 0.5


# ------------------------------------------------------ solve_horizontal fix
def test_horizontal_reload_counts_re_streams():
    """3-sibling inception-style branch whose members re-stream the shared
    input: the reload factor must ceil per member, not floor to 1."""
    from repro.core import frontend

    g = XGraph()
    g.input("x", (1, 64, 64, 256))
    g.add("conv", "b1", ("x",), oc=96, kernel=(3, 3), pad="same")
    g.add("conv", "b3", ("x",), oc=128, kernel=(3, 3), pad="same")
    g.add("conv", "b5", ("x",), oc=64, kernel=(5, 5), pad="same")
    g.add("concat", "cat", ("b1", "b3", "b5"))
    frontend.lower(g)
    sibs = ["b1", "b3", "b5"]
    in_bytes = g.fmap_bytes("x", ZU2.elem_bytes)
    parts = [tiling.solve(g, [s], ZU2) for s in sibs]
    # the fixture must actually exercise re-streaming (input not resident)
    assert all(p.load_bytes > in_bytes for p in parts)
    expected = in_bytes * min(
        max(1, math.ceil(p.load_bytes / in_bytes)) for p in parts)
    t = tiling.solve_horizontal(g, sibs, ZU2)
    assert t.feasible
    assert t.load_bytes == expected
    # the old floor formula undercounted for this branch
    old = in_bytes * max(1, min(p.load_bytes // in_bytes or 1 for p in parts))
    assert expected > old


def test_solve_horizontal_shape_override():
    g = make_toy_resnet_graph()
    t = tiling.solve_horizontal(g, ["c2a", "c2s"], ZU2, t_w=4, t_h=8, t_oc=16)
    assert t.feasible and (t.t_w, t.t_h, t.t_oc) == (4, 8, 16)
    bad = tiling.solve_horizontal(g, ["c2a", "c2s"], ZU2, t_w=10 ** 6,
                                  t_h=10 ** 6, t_oc=10 ** 6)
    assert not bad.feasible or bad.t_w <= 16


# ------------------------------------------------------- ddr_slots selection
def _toy_artifact(dev=ZU2):
    from repro import asm

    g, qm, xq = _quantized_toy()
    s = pathsearch.search(g, dev)
    return asm.compile_strategy(g, s, dev, qm=qm), g, qm


def test_choose_ddr_slots_profile_guided():
    from repro.runtime.schedule import choose_ddr_slots, pipeline_report
    from repro.tune.profile import COEF_NAMES, DeviceProfile

    art, g, qm = _toy_artifact()

    def prof(bw):
        coef = [0.0] * len(COEF_NAMES)
        coef[COEF_NAMES.index("rd")] = 1.0 / bw
        return DeviceProfile(name=f"bw{bw:g}", device="zu2",
                             backend="pallas", jax_version="t",
                             features="kernel", combine="sum",
                             coef=tuple(coef), deviation=0.0, n_samples=3)

    # measured bandwidth far above the model: DDR time shrinks -> default
    fast = choose_ddr_slots(art, prof(ZU2.dram_bw_bytes_per_s * 1e3))
    assert fast == 2
    # measured bandwidth far below: DDR-bound stream -> deeper buffering
    slow = choose_ddr_slots(art, prof(ZU2.dram_bw_bytes_per_s / 1e3))
    assert slow > 2
    assert choose_ddr_slots(art, None) >= 2
    rep = pipeline_report(art, 4, ddr_slots=None)
    assert rep.ddr_slots_source == "auto" and rep.ddr_slots >= 2
    repp = pipeline_report(art, 4, ddr_slots=None,
                           profile=prof(ZU2.dram_bw_bytes_per_s / 1e3))
    assert repp.ddr_slots_source == "profile" and repp.ddr_slots == slow
    repe = pipeline_report(art, 4, ddr_slots=3)
    assert repe.ddr_slots_source == "explicit" and repe.ddr_slots == 3
