"""Operations, bytes and roofline time of fused launches, against counts
made by hand for ResNet-50's stem and one bottleneck at 224x224."""
import pytest

from bench import roofline

STEM = {"kind": "chain", "in": (224, 224, 3), "sides": [],
        "convs": [(7, 7, 3, 64, 112, 112)], "out": (56, 56, 64)}
# s0b1: 1x1 256->64, 3x3 64->64, 1x1 64->256 at 56x56, plus the shortcut add
BOTTLENECK = {"kind": "chain", "in": (56, 56, 256), "sides": [(56, 56, 256)],
              "convs": [(1, 1, 256, 64, 56, 56), (3, 3, 64, 64, 56, 56),
                        (1, 1, 64, 256, 56, 56)], "out": (56, 56, 256)}
V5E = {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}


def test_stem_by_hand():
    # 7*7*3*64 MACs per output pixel, 112*112 pixels, two ops per MAC
    assert roofline.ops(STEM, 1) == 2 * 9408 * 12544 == 236_027_904
    # image in + pooled map out, int8; weights int8 + biases int32 once
    assert roofline.bytes_moved(STEM, 1) == 150_528 + 200_704 + 9_408 + 256
    assert roofline.bytes_moved(STEM, 32) == 32 * 351_232 + 9_664
    # compute bound: 7.55 GOP / 393 TOP/s against 11.2 MB / 819 GB/s
    assert roofline.roofline_s(STEM, 32, V5E) == pytest.approx(
        32 * 236_027_904 / 393e12)


def test_bottleneck_by_hand():
    macs = 3136 * (256 * 64 + 9 * 64 * 64 + 64 * 256)
    assert macs == 218_365_952
    assert roofline.ops(BOTTLENECK, 1) == 436_731_904
    params = (16_384 + 256) + (36_864 + 256) + (16_384 + 1_024)
    assert roofline.bytes_moved(BOTTLENECK, 1) == 3 * 802_816 + params
    # at batch 1 the weights tip it: still compute bound on a v5e
    t = roofline.roofline_s(BOTTLENECK, 1, V5E)
    assert t == pytest.approx(max(436_731_904 / 393e12,
                                  (3 * 802_816 + params) / 819e9))
    # a pool-only launch has no operations and is bound by its bytes
    pool = {"kind": "chain", "in": (28, 28, 192), "sides": [], "convs": [],
            "out": (28, 28, 192)}
    assert roofline.ops(pool, 4) == 0
    assert roofline.roofline_s(pool, 4, V5E) == pytest.approx(
        4 * 2 * 150_528 / 819e9)


def test_model_ops_of_the_references():
    from bench import spec
    for name, want in (("resnet50-224", 8_178_368_512),
                       ("googlenet-224", 3_165_343_744)):
        cfg = spec.config(name)
        specs = spec.reference(cfg["reference"]).layers(cfg)
        got = roofline.model_ops(specs, (224, 224, 3))
        assert got == want == cfg["int8_ops_per_image"]


def test_peaks_table():
    p = roofline.peaks("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_launch_shapes_cover_every_operation_of_the_program():
    """The program's lowered launches at 224, read into roofline shapes, add
    up to the reference's operations: no conv is lost or counted twice."""
    from bench import model, spec
    from repro.core import lower, pathsearch
    from repro.hw import TPU_V5E

    cfg = spec.config("resnet50-224")
    g = model.graph(cfg)
    las = model.launches(g, lower.lower_strategy(
        g, pathsearch.search(g, TPU_V5E)))
    assert las[0] == STEM
    assert BOTTLENECK in las
    assert sum(roofline.ops(la, 1) for la in las) == cfg["int8_ops_per_image"]
