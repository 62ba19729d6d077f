"""conv_fused Pallas kernel: bit-exact vs the int8 oracle across a
shape/stride/pool/eltwise sweep (interpret mode on the CPU, compiled on
a chip)."""
import jax.numpy as jnp
import numpy as np
import pytest

try:  # dev-only dep (requirements-dev.txt); only the property sweep needs it
    import hypothesis.strategies as st
    from hypothesis import given, settings
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.kernels.conv_fused.ops import (fused_conv_block, interpret_mode,
                                          supports)
from repro.kernels.conv_fused.ref import fused_conv_ref


def _data(rng, h, w, ic, oc, k):
    x = rng.integers(-128, 128, (1, h, w, ic)).astype(np.int8)
    wt = rng.integers(-128, 128, (k, k, ic, oc)).astype(np.int8)
    b = rng.integers(-2000, 2000, oc).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(wt), jnp.asarray(b)


CASES = [
    # (h, w, ic, oc, k, stride, pad, relu, shift)
    (8, 8, 4, 8, 3, 1, 1, True, 6),
    (8, 8, 4, 8, 3, 1, 1, False, 6),
    (9, 9, 3, 5, 3, 1, 0, True, 7),       # ragged dims
    (12, 12, 8, 16, 5, 1, 2, True, 8),
    (12, 12, 8, 16, 3, 2, 1, True, 7),    # stride 2
    (16, 16, 16, 4, 1, 1, 0, True, 5),    # 1x1
    (7, 7, 2, 3, 3, 2, 1, False, 4),      # everything ragged
]


@pytest.mark.parametrize("h,w,ic,oc,k,s,p,relu,shift", CASES)
def test_plain_conv_bit_exact(h, w, ic, oc, k, s, p, relu, shift):
    rng = np.random.default_rng(h * w + oc)
    x, wt, b = _data(rng, h, w, ic, oc, k)
    got = fused_conv_block(x, wt, b, stride=(s, s), pad=(p, p), shift=shift,
                           relu=relu)
    want = fused_conv_ref(x, wt, b, stride=(s, s), pad=(p, p), shift=shift,
                          relu=relu)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


POOL_CASES = [
    # (h, w, ic, oc, k, pad, kp, sp)
    (8, 8, 4, 8, 3, 1, 2, 2),
    (10, 10, 4, 8, 3, 1, 2, 2),
    (8, 8, 4, 8, 3, 1, 3, 1),
    (12, 12, 3, 6, 5, 2, 2, 2),
    (14, 14, 8, 16, 3, 1, 3, 1),
]


@pytest.mark.parametrize("h,w,ic,oc,k,p,kp,sp", POOL_CASES)
def test_conv_pool_bit_exact(h, w, ic, oc, k, p, kp, sp):
    rng = np.random.default_rng(h + kp * 10)
    x, wt, b = _data(rng, h, w, ic, oc, k)
    oh = h + 2 * p - k + 1
    assert supports(kernel=(k, k), stride=(1, 1), pool=(kp, sp),
                    conv_oh=oh, conv_ow=oh)
    got = fused_conv_block(x, wt, b, stride=(1, 1), pad=(p, p), shift=7,
                           relu=True, pool=(kp, sp))
    want = fused_conv_ref(x, wt, b, stride=(1, 1), pad=(p, p), shift=7,
                          relu=True, pool=(kp, sp))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("relu_out", [False, True])
def test_conv_eltwise_bit_exact(relu_out):
    rng = np.random.default_rng(3)
    x, wt, b = _data(rng, 8, 8, 4, 8, 3)
    side = jnp.asarray(rng.integers(-128, 128, (1, 8, 8, 8)).astype(np.int8))
    elt = (side, 1, 2, relu_out)
    got = fused_conv_block(x, wt, b, stride=(1, 1), pad=(1, 1), shift=6,
                           relu=False, eltwise=elt)
    want = fused_conv_ref(x, wt, b, stride=(1, 1), pad=(1, 1), shift=6,
                          relu=False, eltwise=elt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


if HAVE_HYPOTHESIS:
    @settings(max_examples=15, deadline=None)
    @given(st.integers(4, 12), st.integers(4, 12),
           st.sampled_from([1, 2, 3, 4]), st.sampled_from([1, 2, 4, 8]),
           st.sampled_from([1, 3]), st.integers(0, 10), st.booleans())
    def test_property_sweep(h, w, ic, oc, k, shift, relu):
        rng = np.random.default_rng(h * 31 + w)
        x, wt, b = _data(rng, h, w, ic, oc, k)
        p = (k - 1) // 2
        got = fused_conv_block(x, wt, b, stride=(1, 1), pad=(p, p),
                               shift=shift, relu=relu)
        want = fused_conv_ref(x, wt, b, stride=(1, 1), pad=(p, p),
                              shift=shift, relu=relu)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_support_predicate():
    # depthwise is the chain kernel's only structural exclusion
    assert not supports(kernel=(3, 3), stride=(1, 1), depthwise=True)
    # the staged kernel's padded-coordinate masking handles all of these
    assert supports(kernel=(3, 3), stride=(1, 1), dilation=(2, 2))
    assert supports(kernel=(3, 3), stride=(1, 2))
    assert supports(kernel=(3, 3), stride=(1, 1), pool=(3, 2),
                    conv_oh=8, conv_ow=8)   # ceil-extended pool windows


def test_dilated_conv_bit_exact():
    from repro.core import int8_ops
    from repro.kernels.conv_fused.ops import _run_chain

    rng = np.random.default_rng(11)
    x, wt, b = _data(rng, 12, 12, 4, 8, 3)
    want = int8_ops.conv2d(x, wt, b, stride=(1, 1), pad=(2, 2),
                           dilation=(2, 2), shift=6, relu=True)
    chain = (("conv", "c", 3, 3, 1, 1, 2, 2, 2, 2, 6, True, 12, 12),)
    got = _run_chain(x, (wt,), (b,), (), chain=chain, oh=12, ow=12, oc=8,
                     interpret=interpret_mode())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ceil_pool_chain_bit_exact():
    """conv -> maxpool with pool padding AND a ceil-extended last window —
    the pre-padded-slack path the lowering pass emits for ResNet's pool1."""
    import math

    from repro.core import int8_ops
    from repro.kernels.conv_fused.ops import _run_chain

    rng = np.random.default_rng(12)
    x, wt, b = _data(rng, 13, 13, 4, 8, 3)
    y_c = fused_conv_ref(x, wt, b, stride=(1, 1), pad=(1, 1), shift=6,
                         relu=True)
    for kp, sp, pp in [(3, 2, 0), (3, 2, 1), (2, 2, 1)]:
        want = int8_ops.maxpool(y_c, kernel=(kp, kp), stride=(sp, sp),
                                pad=(pp, pp), ceil_mode=True)
        oh = math.ceil((13 + 2 * pp - kp) / sp) + 1
        chain = (("conv", "c", 3, 3, 1, 1, 1, 1, 1, 1, 6, True, 13, 13),
                 ("pool", "p", "max", kp, kp, sp, sp, pp, pp, oh, oh, kp * kp))
        got = _run_chain(x, (wt,), (b,), (), chain=chain, oh=oh, ow=oh,
                         oc=8, interpret=interpret_mode())
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_avgpool_chain_bit_exact():
    from repro.core import int8_ops
    from repro.kernels.conv_fused.ops import _run_chain

    rng = np.random.default_rng(13)
    x, wt, b = _data(rng, 12, 12, 4, 8, 3)
    y_c = fused_conv_ref(x, wt, b, stride=(1, 1), pad=(1, 1), shift=6,
                         relu=True)
    want = int8_ops.avgpool(y_c, kernel=(2, 2), stride=(2, 2))
    chain = (("conv", "c", 3, 3, 1, 1, 1, 1, 1, 1, 6, True, 12, 12),
             ("pool", "p", "avg", 2, 2, 2, 2, 0, 0, 6, 6, 4))
    got = _run_chain(x, (wt,), (b,), (), chain=chain, oh=6, ow=6, oc=8,
                     interpret=interpret_mode())
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_horizontal_stacked_bit_exact():
    """Two siblings with different shifts/ReLU in one stacked launch must
    match each sibling computed alone (per-channel requantization)."""
    import jax.numpy as jnp

    from repro.core import int8_ops
    from repro.kernels.conv_fused.ops import _run_horizontal

    rng = np.random.default_rng(14)
    x = jnp.asarray(rng.integers(-128, 128, (1, 10, 10, 4)).astype(np.int8))
    wa = jnp.asarray(rng.integers(-128, 128, (3, 3, 4, 8)).astype(np.int8))
    wb = jnp.asarray(rng.integers(-128, 128, (3, 3, 4, 12)).astype(np.int8))
    ba = jnp.asarray(rng.integers(-2000, 2000, 8).astype(np.int32))
    bb = jnp.asarray(rng.integers(-2000, 2000, 12).astype(np.int32))
    ya = int8_ops.conv2d(x, wa, ba, stride=(1, 1), pad=(1, 1), shift=5,
                         relu=True)
    yb = int8_ops.conv2d(x, wb, bb, stride=(1, 1), pad=(1, 1), shift=7)
    y = _run_horizontal(
        x, jnp.concatenate([wa, wb], axis=-1), jnp.concatenate([ba, bb]),
        jnp.asarray(np.repeat([5, 7], [8, 12]).astype(np.int32)),
        jnp.asarray(np.repeat([1, 0], [8, 12]).astype(np.int32)),
        stride=(1, 1), pad=(1, 1), oh=10, ow=10, interpret=interpret_mode())
    np.testing.assert_array_equal(np.asarray(y[..., :8]), np.asarray(ya))
    np.testing.assert_array_equal(np.asarray(y[..., 8:]), np.asarray(yb))


def _conv_stage(name, k, s, p, d, shift, relu, oh):
    return ("conv", name, k, k, s, s, p, p, d, d, shift, relu, oh, oh)


# (x shape, [(k, stride, pad, dilation, oc)], pool (k, s) or None, tile)
FOLD_CASES = {
    # ResNet's stem: 7x7/2 conv, IC 3, then a ceil-mode 3x3/2 max pool
    "stem_7x7s2_ceil_pool": ((2, 29, 29, 3), [(7, 2, 3, 1, 16)], (3, 2),
                             (4, 7, 16)),
    # VGG's conv1_1 into a second 3x3 conv, th < OH: halo rows recomputed
    "3x3_into_3x3_halo": ((1, 12, 12, 3), [(3, 1, 1, 1, 8), (3, 1, 1, 1, 8)],
                          None, (4, 12, 8)),
    # ragged row and width tiles: 13 = 4 + 4 + 4 + 1 rows, 8 + 5 columns
    "ragged_width_tile": ((1, 13, 13, 3), [(3, 1, 1, 1, 8)], None, (4, 8, 8)),
    "dilated_3x3": ((1, 12, 12, 3), [(3, 1, 2, 2, 8)], None, ()),
    # 5x5x6 = 150 contraction rows: two 128-lane groups
    "two_lane_groups": ((1, 10, 10, 6), [(5, 1, 2, 1, 8)], None, (5, 10, 8)),
}


@pytest.mark.parametrize("case", sorted(FOLD_CASES))
def test_folded_first_conv_bit_exact(case):
    """A lane-starved first conv runs as one matmul over its im2col patches
    (``ops.fold_taps``), bit-exact with the int8 oracle downstream of any
    pool, second conv or ragged tile."""
    import math

    from repro.core import int8_ops
    from repro.kernels.conv_fused.ops import _run_chain, fold_taps

    shape, convs, pool, tile = FOLD_CASES[case]
    rng = np.random.default_rng(sum(map(ord, case)))
    x = jnp.asarray(rng.integers(-128, 128, shape).astype(np.int8))
    y, chain, ws, bs = x, [], [], []
    for n, (k, s, p, d, oc) in enumerate(convs):
        ic = y.shape[-1]
        w = jnp.asarray(rng.integers(-128, 128, (k, k, ic, oc)).astype(np.int8))
        b = jnp.asarray(rng.integers(-2000, 2000, oc).astype(np.int32))
        shift = 7 + n
        y = int8_ops.conv2d(y, w, b, stride=(s, s), pad=(p, p),
                            dilation=(d, d), shift=shift, relu=True)
        chain.append(_conv_stage(f"c{n}", k, s, p, d, shift, True,
                                 y.shape[1]))
        ws.append(w)
        bs.append(b)
    if pool:
        kp, sp = pool
        y = int8_ops.maxpool(y, kernel=(kp, kp), stride=(sp, sp),
                             ceil_mode=True)
        oh = math.ceil((chain[-1][12] - kp) / sp) + 1
        assert y.shape[1] == oh
        chain.append(("pool", "p", "max", kp, kp, sp, sp, 0, 0, oh, oh,
                      kp * kp))
    chain = tuple(chain)
    assert fold_taps(chain, [w.shape for w in ws])[2]
    oh = y.shape[1]
    got = _run_chain(x, tuple(ws), tuple(bs), (), chain=chain, oh=oh, ow=oh,
                     oc=y.shape[-1], interpret=interpret_mode(), tile=tile)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(y))


@pytest.mark.parametrize("first,w_shape", [
    (_conv_stage("c", 3, 1, 1, 1, 6, True, 8), (3, 3, 64, 64)),
    (_conv_stage("fc", 1, 1, 0, 1, 6, False, 1), (1, 1, 4096, 1000)),
    (_conv_stage("c", 1, 1, 0, 1, 6, True, 8), (1, 1, 3, 64)),
], ids=["3x3_ic64", "fc", "1x1_ic3"])
def test_fold_leaves_full_or_single_tap_convs(first, w_shape):
    """A conv whose taps fill more than two lane groups, or that has one
    tap, runs as it is."""
    from repro.kernels.conv_fused.ops import fold_taps

    chain = (first,)
    assert fold_taps(chain, [w_shape]) == (chain, [w_shape], False)
