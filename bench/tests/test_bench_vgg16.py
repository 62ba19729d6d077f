"""The ``vgg16-224`` configuration: its reference against the published
counts, the served program's launches against the reference's operations,
the served path at a small size against the int8 oracle and the float32
reference, and the readers of the metrics it brought."""
import math
import types

import numpy as np
import pytest

from bench import check, model, reference, roofline, spec

CFG = spec.config("vgg16-224")


def _specs(cfg=CFG):
    return spec.reference(cfg["reference"]).layers(cfg)


def test_reference_has_the_published_counts():
    """Table 1, column D: 138,357,544 parameters, and two operations per
    multiply-accumulate of the 13 convs and 3 fc layers at 224."""
    shapes = reference.param_shapes(_specs(), (1, 224, 224, 3))
    assert sum(math.prod(w) + math.prod(b) for w, b in shapes.values()) \
        == CFG["weights"] == 138_357_544
    assert roofline.model_ops(_specs(), (224, 224, 3)) \
        == CFG["int8_ops_per_image"] == 30_940_528_640


def test_reference_names_are_the_served_graph_s():
    g = model.graph(CFG)
    ref = {sp["name"]: sp["op"] for sp in _specs()}
    served = {n.name: n.op for n in g}
    assert {n for n, op in ref.items() if op in ("conv", "fc")} \
        == {n for n, op in served.items() if op in ("conv", "fc")} \
        == {f"conv{i}" for i in range(1, 14)} | {"fc6", "fc7", "fc8"}
    assert {n for n, op in ref.items() if op == "maxpool"} \
        == {n for n, op in served.items() if op == "maxpool"}


def test_launches_cover_every_operation_of_the_program():
    """The lowered launches at 224, read into roofline shapes, add up to the
    reference's operations: no conv is lost or counted twice."""
    from repro.core import lower, pathsearch
    from repro.hw import TPU_V5E

    g = model.graph(CFG)
    prog = lower.lower_strategy(g, pathsearch.search(g, TPU_V5E))
    las = model.launches(g, prog)
    assert sum(roofline.ops(la, 1) for la in las) \
        == CFG["int8_ops_per_image"]
    assert [fb.nodes for fb in prog.fallbacks()] == [("prob",)]


@pytest.fixture(scope="module")
def served_small():
    """VGG-16 at 32x32 with 10 classes, weights and images from seed 7,
    served by a ``Session`` on the fused Pallas path (interpreted on the
    CPU): (session, images, outputs)."""
    cfg = dict(CFG, image_size=32, num_classes=10)
    specs = _specs(cfg)
    params = reference.make_params(specs, (1, 32, 32, 3), 7)
    pool = model.images(cfg, 16, 7)
    sess = model.session(cfg, specs, params, model.as_float(cfg, pool))
    x = pool[:4]
    return cfg, specs, params, sess, x, sess.executor(x)


def test_served_path_is_bit_exact_with_the_int8_oracle(served_small):
    from repro.core.executor import Int8Executor

    *_, sess, x, out = served_small
    assert len(sess.executor.program.launches()) >= 4
    want = Int8Executor(sess.graph, sess.qm, None, backend="ref")(x)
    for name, y in out.items():
        np.testing.assert_array_equal(y, want[name])


def test_served_path_is_near_the_float32_reference(served_small):
    """Against the float32 reference the served answers carry the rounding
    of 16 int8 layers.  The tolerances are the configuration's own ``check``
    limits: the same comparison, with the same limits, decides ``correct``
    for a run on the chip at 224."""
    import jax

    cfg, specs, params, sess, x, out = served_small
    served, probs = model.served_values(
        sess, [{k: v[i] for k, v in out.items()} for i in range(len(x))])
    assert probs                          # the softmax is kept
    ref = np.asarray(jax.jit(lambda p, x: reference.forward(specs, p, x))(
        params, model.as_float(cfg, x)))
    got = check.numbers(served, ref, probs)
    for k, limit in CFG["check"].items():
        assert got[k] <= limit, (k, got[k])


def _run(batch=32):
    return types.SimpleNamespace(batch=batch)


def _registry(gauges=None):
    """A registry holding ``gauges`` ({full name: value}) and nothing else."""
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    for name, v in (gauges or {}).items():
        reg.gauge(name).set(v)
    return reg


def test_halo_recompute_share_reader():
    read = spec.metric("halo_recompute_share").read
    reg = _registry({"executor.conv_macs_executed": 1_523.0,
                     "executor.conv_macs_model": 1_000.0})
    assert read(_run(), reg) == pytest.approx(52.3)
    assert read(_run(), _registry()) is None
    assert read(_run(), _registry({"executor.conv_macs_model": 0.0,
                                   "executor.conv_macs_executed": 5.0})) \
        is None


def test_weight_hbm_mb_per_image_reader():
    read = spec.metric("weight_hbm_mb_per_image").read
    reg = _registry({"executor.weight_bytes_fetched{batch=32}":
                     3_845_081_184.0})
    assert read(_run(32), reg) == pytest.approx(3_845.081184 / 32)
    assert read(_run(8), reg) is None            # no gauge at that batch
    assert read(_run(None), reg) is None         # no one padded batch
    assert read(_run(32), _registry()) is None
