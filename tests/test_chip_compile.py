"""Ahead-of-time compiles of the fused kernels for a described TPU v5e.

Each case lowers one launch kind of ResNet-50 @224, or one launch of
VGG-16 @224, exactly as the planner emits it and compiles it with
``interpret=False`` against one chip of a described ``v5e:2x2`` topology:
what Mosaic refuses (layouts, strided loads, block shapes, VMEM) fails here
without a chip.  Nothing runs.
"""
import os

import pytest

LAUNCHES = {
    "stem_conv7x7s2_maxpool3x3s2": "conv1",
    "bottleneck_s1_eltwise": "s0b1/c1",
    "downsample_s2_eltwise": "s1b0/c2",
    "shortcut_1x1s2": "s2b0/sc",
    "global_avgpool": "gap",
    "fc_1000": "fc",
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def resnet50():
    """(graph, lowered program) of ResNet-50 @224 under the TPU plan."""
    from repro.cnn import build
    from repro.core import lower, pathsearch
    from repro.hw import TPU_V5E

    g = build("resnet50", softmax=False)
    return g, lower.lower_strategy(g, pathsearch.search(g, TPU_V5E))


@pytest.fixture(scope="module")
def vgg16():
    """(graph, lowered program) of VGG-16 @224 under the TPU plan."""
    from repro.cnn import build
    from repro.core import lower, pathsearch
    from repro.hw import TPU_V5E

    g = build("vgg16")
    return g, lower.lower_strategy(g, pathsearch.search(g, TPU_V5E))


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep it out."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)


def _compile(fn, *args):
    import jax
    return jax.jit(fn).lower(*args).compile()


def _chain_args(g, launch, sharding, batch=4):
    import jax
    import jax.numpy as jnp

    from repro.core.lower import launch_operands

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(tuple(shape), dt, sharding=sharding)

    in_hwc, weights, sides = launch_operands(g, launch)
    ws = tuple(spec(w, jnp.int8) for w in weights)
    bs = tuple(spec((w[-1],), jnp.int32) for w in weights)
    sides = tuple(spec((batch,) + s, jnp.int8) for s in sides)
    oc = weights[-1][-1] if weights else in_hwc[-1]
    return spec((batch,) + in_hwc, jnp.int8), ws, bs, sides, oc


def _compile_launch(g, prog, first, sharding, tile=None, batch=4):
    from repro.kernels.conv_fused import ops

    launch = next(it for it in prog.launches() if it.nodes[0] == first)
    x, ws, bs, sides, oc = _chain_args(g, launch, sharding, batch)
    oh, ow = launch.out_hw
    tile = tuple(launch.tile) if tile is None else tile

    def run(x, ws, bs, sides):
        return ops._run_chain(x, ws, bs, sides, chain=launch.stages, oh=oh,
                              ow=ow, oc=oc, interpret=False, tile=tile)

    return _compile(run, x, ws, bs, sides)


@pytest.mark.parametrize("kind", sorted(LAUNCHES))
def test_resnet50_launch_compiles_for_v5e(kind, one_chip, resnet50,
                                          no_persistent_cache):
    compiled = _compile_launch(*resnet50, LAUNCHES[kind], one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("first", ["conv1", "conv3", "pool7", "pool10",
                                   "pool13", "fc6", "fc7", "fc8"])
def test_vgg16_launch_compiles_for_v5e(first, one_chip, vgg16,
                                       no_persistent_cache):
    """Every launch of the VGG-16 program, at the served batch of 32: the
    segments the kernel-VMEM check admits compile within the scoped-VMEM
    limit (the whole 13-conv chain the search chose without it did not)."""
    g, prog = vgg16
    assert {la.nodes[0] for la in prog.launches()} == {
        "conv1", "conv3", "pool7", "pool10", "pool13", "fc6", "fc7", "fc8"}
    compiled = _compile_launch(g, prog, first, one_chip, batch=32)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kind,tile,n_w,n_oc", [
    ("stem_conv7x7s2_maxpool3x3s2", (8, 8, 64), 7, 1),
    ("downsample_s2_eltwise", (4, 8, 512), 4, 1),      # ragged: 28 % 8
    ("bottleneck_s1_eltwise", (8, 56, 128), 1, 2),     # side on the OC tile
    ("downsample_s2_eltwise", (7, 8, 128), 4, 4),
])
def test_resnet50_tiled_launch_compiles_for_v5e(kind, tile, n_w, n_oc,
                                                one_chip, resnet50,
                                                no_persistent_cache):
    """Searched tile shapes split the width (T_w < OW: dynamic, 8-aligned
    width offsets) and the OC axis (T_oc < OC: weight, bias and side blocks
    indexed by the OC tile); both must compile, not only the defaults."""
    from repro.kernels.conv_fused.ops import _resolve_tile

    g, prog = resnet50
    launch = next(it for it in prog.launches()
                  if it.nodes[0] == LAUNCHES[kind])
    oh, ow = launch.out_hw
    oc = g.shape(launch.nodes[-1])[3]
    n_conv = sum(1 for st in launch.stages if st[0] == "conv")
    assert _resolve_tile(tile, oh, ow, oc, n_conv) == tile
    assert (-(-ow // tile[1]), oc // tile[2]) == (n_w, n_oc)
    compiled = _compile_launch(g, prog, LAUNCHES[kind], one_chip, tile=tile)
    assert "tpu_custom_call" in compiled.as_text()


def test_horizontal_oc_stacked_launch_compiles_for_v5e(one_chip,
                                                       no_persistent_cache):
    """s0b0's two 1x1 heads on pool1 (64 + 256 OC) as one stacked launch."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.conv_fused import ops

    def spec(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    oc = 64 + 256
    args = (spec((4, 56, 56, 64), jnp.int8), spec((1, 1, 64, oc), jnp.int8),
            spec((oc,), jnp.int32), spec((oc,), jnp.int32),
            spec((oc,), jnp.int32))

    def run(x, w, b, shift, relu):
        return ops._run_horizontal(x, w, b, shift, relu, stride=(1, 1),
                                   pad=(0, 0), oh=56, ow=56, interpret=False)

    assert "tpu_custom_call" in _compile(run, *args).as_text()
