"""The chain kernel's own VMEM footprint, shared by the kernel and the fusion
search: ``ops.launch_blocks`` gives the blocks ``fused_chain_pallas``
allocates, the search admits a segment under ``TPU_V5E`` only if that
footprint fits the scoped-VMEM limit, and the executor exports what its
launches occupy and execute.  Planning only, on the CPU."""
import json
import math
import os
import types

import numpy as np
import pytest

from repro.cnn import build
from repro.core import lower, pathsearch
from repro.hw import TPU_V5E
from repro.hw.device import V5E_VMEM_BYTES
from repro.obs.metrics import REGISTRY

PINS = os.path.join(os.path.dirname(__file__), "data", "launch_pins.json")


def _plan(net, **kw):
    g = build(net, img=224, num_classes=1000, **kw)
    return g, lower.lower_strategy(g, pathsearch.search(g, TPU_V5E))


@pytest.fixture(scope="module")
def resnet50():
    return _plan("resnet50", softmax=False)


@pytest.fixture(scope="module")
def vgg16():
    return _plan("vgg16")


@pytest.fixture(scope="module")
def googlenet():
    return _plan("googlenet")


def _launch(prog, first):
    return next(it for it in prog.launches() if it.nodes[0] == first)


def _padded(shape, itemsize):
    """Bytes of a VMEM buffer: minor dim to 128 lanes, second-minor to 8
    rows (int32) or 32 rows (int8)."""
    *lead, sub, lane = shape
    rows = {1: 32, 4: 8}[itemsize]
    return (math.prod(lead) * math.ceil(sub / rows) * rows
            * math.ceil(lane / 128) * 128 * itemsize)


def _allocated(g, launch, monkeypatch, batch=2):
    """VMEM of the ``pallas_call`` the launcher makes for ``launch``: every
    block it is handed double-buffered, plus its scratch."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.conv_fused import conv_fused, ops

    seen = {}

    def fake_pallas_call(kern, *, grid, in_specs, out_specs, out_shape,
                         scratch_shapes, **_):
        seen.update(grid=grid, in_specs=in_specs, out_specs=out_specs,
                    scratch=scratch_shapes)
        return lambda *args: seen.update(args=args) or jnp.zeros(
            out_shape.shape, out_shape.dtype)

    monkeypatch.setattr(conv_fused, "pl", types.SimpleNamespace(
        BlockSpec=conv_fused.pl.BlockSpec, pallas_call=fake_pallas_call))
    in_hwc, weights, sides = lower.launch_operands(g, launch)
    x = jnp.zeros((batch,) + in_hwc, jnp.int8)
    ws = tuple(jnp.zeros(w, jnp.int8) for w in weights)
    bs = tuple(jnp.zeros(w[-1], jnp.int32) for w in weights)
    ss = tuple(jnp.zeros((batch,) + s, jnp.int8) for s in sides)
    oh, ow = launch.out_hw
    with jax.disable_jit():
        ops._run_chain(x, ws, bs, ss, chain=launch.stages, oh=oh, ow=ow,
                       oc=weights[-1][-1], interpret=True, tile=())
    blocks = [(s.block_shape, a.dtype.itemsize)
              for s, a in zip(seen["in_specs"], seen["args"])]
    blocks.append((seen["out_specs"].block_shape, 1))
    return (2 * sum(_padded(s, i) for s, i in blocks)
            + sum(_padded(s.shape, s.dtype.itemsize)
                  for s in seen["scratch"])), seen["grid"]


@pytest.mark.parametrize("first", ["conv1", "s0b1/c1", "fc"],
                         ids=["stem", "bottleneck", "fc"])
def test_footprint_is_what_the_kernel_allocates(first, resnet50,
                                                monkeypatch):
    from repro.kernels.conv_fused import ops

    g, prog = resnet50
    launch = _launch(prog, first)
    got, grid = _allocated(g, launch, monkeypatch)
    blocks = ops.launch_blocks(launch, *lower.launch_operands(g, launch),
                               batch=2)
    assert blocks.grid == grid
    assert blocks.vmem_bytes() == got == lower.launch_vmem_bytes(g, launch)


def test_weight_fetches_by_hand(resnet50, vgg16):
    """A block is fetched again whenever its index changes between grid
    steps: an fc tiled over OC re-reads its whole panel for every image, an
    untiled one reads it once, a resident upstream panel once per launch."""
    from repro.kernels.conv_fused import ops

    def fetched(g, launch, batch):
        return ops.launch_blocks(launch, *lower.launch_operands(g, launch),
                                 batch=batch).param_bytes_fetched()

    g, prog = vgg16
    fc6 = _launch(prog, "fc6")                   # 25088 x 4096, T_oc 128
    assert fetched(g, fc6, 32) == 32 * (25088 * 4096 + 4 * 4096)
    fc8 = _launch(prog, "fc8")                   # 4096 x 1000: one OC tile
    assert fetched(g, fc8, 32) == 4096 * 1000 + 4 * 1000
    g, prog = resnet50
    bott = _launch(prog, "s0b1/c1")              # 1x1, 3x3, 1x1 at 56x56
    panels = (256 * 64 + 4 * 64) + (9 * 64 * 64 + 4 * 64) + (64 * 256
                                                              + 4 * 256)
    assert fetched(g, bott, 32) == panels


def test_macs_of_a_chain_by_hand(resnet50):
    """The stem (7x7/2 conv, 3x3/2 pool) at 8 pooled rows a tile computes
    17 conv rows for 16 pooled-over ones: one halo row per row tile."""
    from repro.kernels.conv_fused import ops

    g, prog = resnet50
    stem = _launch(prog, "conv1")
    executed, model = ops.launch_macs(stem, *lower.launch_operands(
        g, stem)[:2])
    per_pos = 7 * 7 * 3 * 64
    assert model == 112 * 112 * per_pos
    assert executed == 7 * 17 * 113 * per_pos     # 7 row tiles, 113 cols


def test_vgg16_launches_fit_the_kernel_vmem(vgg16):
    g, prog = vgg16
    rejected = REGISTRY.get(
        "pathsearch.segments_rejected{reason=exceeds_kernel_vmem}")
    assert rejected is not None and rejected.value > 0
    launches = prog.launches()
    assert launches and all(la.kind == "chain" for la in launches)
    for la in launches:
        assert lower.launch_vmem_bytes(g, la) <= V5E_VMEM_BYTES, la.nodes
    assert [(fb.nodes, fb.detail) for fb in prog.fallbacks()] == [
        (("prob",), "softmax")]
    covered = {n for la in launches for n in la.nodes}
    assert {n.name for n in g if n.op in ("conv", "fc")} <= covered


def test_search_trace_names_the_kernel_vmem_rejections(vgg16):
    from repro.explain.render import render_report

    g, _ = vgg16
    s = pathsearch.search(g, TPU_V5E)
    (chain,) = [ch for ch in s.meta["search_trace"]["chains"]
                if "conv1" in ch["nodes"]]
    assert chain["n_rejected"]["exceeds_kernel_vmem"] > 0
    ex = next(e for e in chain["rejected_examples"]
              if e["reason"] == "exceeds_kernel_vmem")
    assert ex["vmem_bytes"] > ex["limit_bytes"] == V5E_VMEM_BYTES
    rep = {"model": g.name, "device": TPU_V5E.name,
           "fusion": {"n_groups": len(s.groups), "n_horizontal": 0,
                      "coverage": 1.0, "groups": [], "fallbacks": [],
                      "search": s.meta["search_trace"]},
           "tiles": {"n_units": 0, "n_tuned": 0, "leaderboard": []},
           "memory": {"peak_bytes": 0, "no_reuse_bytes": 0,
                      "reuse_factor": 1.0, "regions": [], "n_regions": 0,
                      "banks": []},
           "schedule": {"sim_total_cycles": 0, "n_instrs": 0, "engines": {}}}
    text = render_report(rep)
    assert "exceeds_kernel_vmem=" in text
    assert "reason=exceeds_kernel_vmem (" in text and "limit 96 MiB)" in text


def test_other_devices_are_not_judged_by_the_tpu_kernel():
    from repro.hw import ZU2

    g = build("vgg16", img=224)
    assert pathsearch.kernel_vmem_check(g, ZU2) is None
    assert pathsearch.kernel_vmem_check(g, TPU_V5E) is not None


def _launch_list(prog):
    """(kind, input, output, stage or node names) of every item."""
    out = []
    for it in prog.items:
        if isinstance(it, lower.FusedLaunch):
            out.append([it.kind, it.in_name, it.out_name,
                        [st[1] for st in it.stages] or list(it.nodes)])
        else:
            out.append(["fallback", it.reason, "", list(it.nodes)])
    return out


@pytest.mark.parametrize("cfg", ["resnet50-224", "googlenet-224"])
def test_resnet50_and_googlenet_programs_are_pinned(cfg):
    """The kernel-VMEM check only removes segments that overflow: both
    accepted programs stay launch for launch what they were before it
    (``tests/data/launch_pins.json``, made by ``_launch_list`` on the tree
    without the check)."""
    with open(PINS) as f:
        want = json.load(f)[cfg]
    net = cfg.split("-")[0]
    _, prog = _plan(net, **({"softmax": False} if net == "resnet50" else {}))
    assert _launch_list(prog) == want


def test_executor_exports_its_launch_counters(rng):
    from repro.cnn import init_params
    from repro.core import executor, quantize
    from tests.conftest import make_toy_resnet_graph

    g = make_toy_resnet_graph()
    x = rng.standard_normal(g.shape("data")).astype(np.float32)
    qm = quantize.calibrate(g, init_params(g), x, executor.run_float)
    ex = executor.Int8Executor(g, qm, pathsearch.search(g, TPU_V5E),
                               backend="pallas")
    xq = quantize.quantize_to(np.concatenate([x, x, x]), qm.f_a["data"])
    ex(xq)
    want = lower.program_stats(g, ex.program, 3)
    assert want["conv_macs_executed"] >= want["conv_macs_model"] > 0
    assert 0 < want["kernel_vmem_bytes_max"] <= V5E_VMEM_BYTES
    for name in ("kernel_vmem_bytes_max", "conv_macs_executed",
                 "conv_macs_model", "taps_folded_launches"):
        assert REGISTRY.get(f"executor.{name}").value == want[name]
    assert (REGISTRY.get("executor.weight_bytes_fetched{batch=3}").value
            == want["weight_bytes_fetched"] > 0)


def test_vgg16_program_is_pinned():
    """VGG-16's 8 launches and softmax fallback (``launch_pins.json``, made
    by ``_launch_list`` on the tree before the stem's taps were folded), and
    the 13 segments the kernel-VMEM check rejects on the way."""
    name = "pathsearch.segments_rejected{reason=exceeds_kernel_vmem}"
    before = REGISTRY.get(name)
    before = before.value if before is not None else 0
    with open(PINS) as f:
        want = json.load(f)["vgg16-224"]
    _, prog = _plan("vgg16")
    assert _launch_list(prog) == want
    assert REGISTRY.get(name).value - before == 13


# lower.program_stats at batch 32 of the programs before the fold
UNFOLDED_STATS = {
    "resnet50": {"conv_macs_executed": 4_849_230_144,
                 "conv_macs_model": 4_089_184_256,
                 "weight_bytes_fetched": 793_332_576},
    "googlenet": {"conv_macs_executed": 1_641_486_656,
                  "conv_macs_model": 1_582_671_872,
                  "weight_bytes_fetched": 221_331_744},
    "vgg16": {"conv_macs_executed": 23_576_422_912,
              "conv_macs_model": 15_470_264_320,
              "weight_bytes_fetched": 3_845_081_184},
}


@pytest.mark.parametrize("net", sorted(UNFOLDED_STATS))
def test_stem_taps_fold_and_the_counters_hold(net, request, monkeypatch):
    """Each program folds exactly its stem (IC = 3) into one contraction:
    the MACs and weight bytes it counts are what they were unfolded, and
    the footprint the search admits the folded stem by is what the kernel
    allocates for it."""
    from repro.kernels.conv_fused import ops

    g, prog = request.getfixturevalue(net)
    stats = lower.program_stats(g, prog, 32)
    assert stats["taps_folded_launches"] == 1
    assert {k: stats[k] for k in UNFOLDED_STATS[net]} == UNFOLDED_STATS[net]
    stem = prog.launches()[0]
    operands = lower.launch_operands(g, stem)
    assert stem.nodes[0] == "conv1" and operands[1][0][2] == 3
    assert ops.fold_taps(stem.stages, operands[1])[2]
    got, grid = _allocated(g, stem, monkeypatch)
    blocks = ops.launch_blocks(stem, *operands, batch=2)
    assert blocks.grid == grid
    assert blocks.vmem_bytes() == got
