"""Compile-time group lowering (ISSUE 2 tentpole): every strategy group
either lowers to a FusedLaunch or carries an allow-listed machine-readable
fallback reason (no silent fallback), each lowered kind is bit-exact with the
int8 oracle, and the GroupProgram survives the artifact round trip."""
import numpy as np
import pytest

from repro import asm
from repro.cnn import build, init_params
from repro.core import (executor, frontend, lower, partition, pathsearch,
                        quantize, validate)
from repro.core.lower import FALLBACK_REASONS, FusedLaunch, RefFallback
from repro.core.pathsearch import Strategy
from repro.core.xgraph import XGraph
from repro.hw import ZU2
from tests.conftest import make_toy_resnet_graph, toy_params


def _calibrated(g, rng):
    params = init_params(g)
    x = rng.standard_normal(g.shape("data")).astype(np.float32)
    qm = quantize.calibrate(g, params, x, executor.run_float)
    xq = quantize.quantize_to(x, qm.f_a["data"])
    return qm, xq


def _assert_bit_exact(g, strategy, rng):
    qm, xq = _calibrated(g, rng)
    rep = validate.bit_exact(g, qm, xq, strategy=strategy, backend="pallas")
    assert rep.bit_exact, rep.max_abs_diff
    return lower.lower_strategy(g, strategy, qm)


# ------------------------------------------------- no silent fallback
@pytest.mark.parametrize("model", ["vgg16", "resnet50", "googlenet"])
def test_benchmark_strategies_lower_fully(model):
    """At the paper's 224 benchmark resolution, search() strategies for the
    acceptance models must execute >= 90% fused, and every fallback must
    carry an allow-listed reason."""
    g = build(model)
    dv = partition.device_of(g, "paper")
    s = pathsearch.search(g, ZU2, device_of=dv)
    prog = lower.lower_strategy(g, s)
    for item in prog.items:
        if isinstance(item, RefFallback):
            assert item.reason in FALLBACK_REASONS, item
    rep = validate.fused_coverage(g, s)
    assert rep.ratio >= 0.9, (rep.ratio, rep.fallback_reasons)


@pytest.mark.parametrize("model,img", [("vgg16", 32), ("resnet50", 32),
                                       ("googlenet", 64), ("yolo_lite", 64)])
def test_small_strategies_never_fall_back_silently(model, img):
    """Small resolutions produce the deepest fused chains (buffers fit);
    whatever the search emits, lowering must classify every group."""
    g = build(model, img=img, num_classes=10) if model != "yolo_lite" \
        else build(model, img=img)
    s = pathsearch.search(g, ZU2)
    prog = lower.lower_strategy(g, s)
    covered = set()
    for item in prog.items:
        if isinstance(item, RefFallback):
            assert item.reason in FALLBACK_REASONS, item
        covered |= set(item.nodes)
    assert covered == set(g.compute_nodes())


@pytest.mark.parametrize("model", ["resnet50", "resnet152"])
def test_resnet_logits_form_lowers_without_fallback(model):
    """``softmax=False`` ends a ResNet at the fc logits: under the TPU plan
    every node then runs in a fused launch (softmax is a host op)."""
    from repro.hw import TPU_V5E

    g = build(model, img=32, num_classes=10, softmax=False)
    assert [n.name for n in g if not g.consumers(n.name)] == ["fc"]
    prog = lower.lower_strategy(g, pathsearch.search(g, TPU_V5E))
    assert not prog.fallbacks()
    assert {n for it in prog.launches() for n in it.nodes} == \
        set(g.compute_nodes())


# ------------------------------------------------- bit-exactness per kind
def test_conv_eltwise_maxpool_chain_bit_exact(rng):
    g = XGraph("cep")
    g.input("data", (1, 13, 13, 4))
    g.add("conv", "side", ("data",), oc=8, kernel=(1, 1), pad="same")
    g.add("conv", "main", ("data",), oc=8, kernel=(3, 3), pad="same")
    g.add("eltwise_add", "add", ("main", "side"))
    g.add("relu", "r", ("add",))
    g.add("maxpool", "pool", ("r",), kernel=(2, 2), stride=(2, 2))  # ceil: 13->7
    frontend.lower(g)
    s = Strategy(groups=[["side"], ["main", "add", "pool"]], horizontal=[],
                 cost=0.0)
    prog = _assert_bit_exact(g, s, rng)
    (launch,) = [i for i in prog.items if len(i.nodes) == 3]
    assert isinstance(launch, FusedLaunch)
    assert [st[0] for st in launch.stages] == ["conv", "elt", "pool"]


def test_conv_maxpool_ceil_and_padding_bit_exact(rng):
    g = XGraph("cp")
    g.input("data", (1, 13, 13, 3))
    g.add("conv", "c", ("data",), oc=8, kernel=(3, 3), pad="same", relu="relu")
    g.add("maxpool", "p", ("c",), kernel=(3, 3), stride=(2, 2), pad=(1, 1))
    s = Strategy(groups=[["c", "p"]], horizontal=[], cost=0.0)
    prog = _assert_bit_exact(g, s, rng)
    assert all(isinstance(i, FusedLaunch) for i in prog.items)


def test_conv_avgpool_bit_exact(rng):
    g = XGraph("ca")
    g.input("data", (1, 12, 12, 3))
    g.add("conv", "c", ("data",), oc=8, kernel=(3, 3), pad="same", relu="relu")
    g.add("avgpool", "p", ("c",), kernel=(2, 2), stride=(2, 2))
    s = Strategy(groups=[["c", "p"]], horizontal=[], cost=0.0)
    prog = _assert_bit_exact(g, s, rng)
    assert all(isinstance(i, FusedLaunch) for i in prog.items)


def test_conv_avgpool_ceil_extended_bit_exact(rng):
    """Ceil-mode avgpool (Caffe count-include-pad: extended windows read
    zeros, divisor stays kh*kw) lowers to a fused launch — no fallback."""
    assert "avgpool_ceil" not in FALLBACK_REASONS
    g = XGraph("cac")
    g.input("data", (1, 12, 12, 3))
    g.add("conv", "c", ("data",), oc=8, kernel=(3, 3), pad="same", relu="relu")
    g.add("avgpool", "p", ("c",), kernel=(3, 3), stride=(2, 2))  # ceil: 12->6
    assert g.shape("p")[1:3] == (6, 6)      # floor semantics would give 5x5
    s = Strategy(groups=[["c", "p"]], horizontal=[], cost=0.0)
    prog = _assert_bit_exact(g, s, rng)
    assert all(isinstance(i, FusedLaunch) for i in prog.items)


def test_avgpool_ceil_matches_zero_padded_reference(rng):
    """int8_ops.avgpool ceil semantics: the bottom/right extension behaves
    exactly like zero padding with an unchanged kh*kw divisor."""
    import jax.numpy as jnp
    from repro.core import int8_ops
    x = jnp.asarray(rng.integers(-128, 128, (1, 5, 5, 2)), jnp.int8)
    got = int8_ops.avgpool(x, kernel=(2, 2), stride=(2, 2))       # ceil: 5->3
    xp = jnp.pad(x, ((0, 0), (0, 1), (0, 1), (0, 0)))
    want = int8_ops.avgpool(xp, kernel=(2, 2), stride=(2, 2),
                            ceil_mode=False)
    assert got.shape == (1, 3, 3, 2)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_multi_conv_chain_and_gap_bit_exact(rng):
    g = XGraph("mc")
    g.input("data", (1, 12, 12, 3))
    g.add("conv", "c1", ("data",), oc=8, kernel=(3, 3), pad="same", relu="relu")
    g.add("conv", "c2", ("c1",), oc=16, kernel=(3, 3), pad="same", relu="relu")
    g.add("maxpool", "p", ("c2",), kernel=(2, 2), stride=(2, 2))
    g.add("conv", "c3", ("p",), oc=8, kernel=(1, 1), pad="same")
    g.add("global_avgpool", "gap", ("c3",))
    s = Strategy(groups=[["c1", "c2", "p"], ["c3", "gap"]], horizontal=[],
                 cost=0.0)
    prog = _assert_bit_exact(g, s, rng)
    assert all(isinstance(i, FusedLaunch) for i in prog.items)
    chains = [[st[0] for st in i.stages] for i in prog.items]
    assert ["conv", "conv", "pool"] in chains
    assert ["conv", "pool"] in chains


def test_fc_lowers_as_1x1_conv_bit_exact(rng):
    g = XGraph("fc")
    g.input("data", (1, 8, 8, 4))
    g.add("conv", "c", ("data",), oc=8, kernel=(3, 3), pad="same", relu="relu")
    g.add("fc", "fc1", ("c",), oc=10, relu="relu")
    g.add("fc", "fc2", ("fc1",), oc=5)
    s = Strategy(groups=[["c"], ["fc1"], ["fc2"]], horizontal=[], cost=0.0)
    prog = _assert_bit_exact(g, s, rng)
    fc_launches = [i for i in prog.items
                   if isinstance(i, FusedLaunch) and i.fc_reshape]
    assert len(fc_launches) == 2


def test_horizontal_group_batches_stacked_weights(rng):
    g = XGraph("hz")
    g.input("data", (1, 12, 12, 4))
    g.add("conv", "ca", ("data",), oc=8, kernel=(3, 3), pad="same", relu="relu")
    g.add("conv", "cb", ("data",), oc=12, kernel=(3, 3), pad="same")
    g.add("conv", "cc", ("data",), oc=8, kernel=(1, 1), pad="same")
    s = Strategy(groups=[], horizontal=[["ca", "cb", "cc"]], cost=0.0)
    prog = _assert_bit_exact(g, s, rng)
    (hz,) = [i for i in prog.items
             if isinstance(i, FusedLaunch) and i.kind == "horizontal"]
    # ca/cb share (3,3)/stride/pad -> one batched launch; cc launches alone
    assert {m[0] for m in hz.members} == {"ca", "cb"}
    assert sum(isinstance(i, FusedLaunch) for i in prog.items) == 2


# ------------------------------------------------- fallback classification
def test_fallback_reasons_are_explicit():
    g = make_toy_resnet_graph()
    dv = partition.device_of(g, "paper")   # fc1 -> host
    s = pathsearch.search(g, ZU2, device_of=dv)
    prog = lower.lower_strategy(g, s)
    reasons = prog.meta["fallback_reasons"]
    assert reasons.get("host_op", 0) >= 1            # fc1 on the host
    assert set(reasons) <= FALLBACK_REASONS
    with pytest.raises(ValueError):
        RefFallback(("x",), "because")               # not machine-readable


def test_unquantized_conv_falls_back_with_reason(rng):
    g = make_toy_resnet_graph()
    qm, _ = _calibrated(g, rng)
    del qm.weights["c1"]
    prog = lower.lower_strategy(g, pathsearch.naive(g, ZU2), qm)
    fb = {i.nodes[0]: i.reason for i in prog.fallbacks()}
    assert fb.get("c1") == "unquantized"


def test_executor_dispatch_is_precompiled(rng):
    """Zero runtime pattern matching: the pallas executor dispatches from a
    GroupProgram resolved at construction/compile time."""
    from repro.kernels.conv_fused import ops as fused_ops
    assert not hasattr(fused_ops, "group_descriptor")
    g = make_toy_resnet_graph()
    qm, xq = _calibrated(g, rng)
    s = pathsearch.search(g, ZU2)
    ex = executor.Int8Executor(g, qm, strategy=s, backend="pallas")
    assert ex.program is not None and ex.program.meta["quantized"]
    assert all(isinstance(i, (FusedLaunch, RefFallback))
               for i in ex.program.items)


# ------------------------------------------------- artifact round trip
def test_artifact_carries_program_and_round_trips(rng, tmp_path):
    g = make_toy_resnet_graph()
    qm, xq = _calibrated(g, rng)
    s = pathsearch.search(g, ZU2)
    art = asm.compile_strategy(g, s, ZU2, qm=qm)
    assert art.program is not None and art.program.meta["quantized"]
    assert art.fused_coverage > 0.0

    path = str(tmp_path / "prog.npz")
    asm.save_artifact(art, path)
    loaded = asm.load_artifact(path)
    assert lower.program_to_json(loaded.program) == \
        lower.program_to_json(art.program)
    # a session on the loaded artifact dispatches the STORED program (no
    # re-lowering, no graph inspection: the artifact is self-contained)
    from repro.runtime import Session
    ex = Session.from_artifact(loaded, backend="pallas").executor
    assert ex.program is loaded.program

    rep = validate.artifact_round_trip(g, qm, xq, s, ZU2,
                                       str(tmp_path / "rt.npz"),
                                       backend="pallas")
    assert rep.bit_exact, rep.max_abs_diff


def test_structural_program_without_qm_reports_coverage():
    g = make_toy_resnet_graph()
    s = pathsearch.search(g, ZU2)
    art = asm.compile_strategy(g, s, ZU2)          # plan-only, no weights
    assert art.program is not None
    assert not art.program.meta["quantized"]
    assert 0.0 < art.fused_coverage <= 1.0


# ------------------------------------------------- satellite regressions
def test_group_callable_uses_full_range_int8(rng):
    import jax.numpy as jnp
    g = make_toy_resnet_graph()
    qm, _ = _calibrated(g, rng)
    fn, ins = executor.build_group_callable(g, ["c1"], qm)
    assert all(i.dtype == jnp.int8 for i in ins)
    a = np.asarray(ins[0])
    assert a.min() < -100 and a.max() > 100    # not near-all-zero activations
    fn(*ins)
